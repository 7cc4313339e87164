"""GPT-124M train-step batch/seq sweep on the attached chip.

Finds the MFU-maximal single-chip config (the bench.py default shape
was picked before any chip run).  Reference precedent for sweeping op
configs in CI: tools/ci_op_benchmark.sh.  TPU only, like bench.py.

Usage:  python benchmarks/bench_sweep.py [--configs B,S B,S ...]
Emits one JSON line per config and a final "best" line.
"""

import argparse
import json
import sys
import time

import numpy as np


def measure(batch, seq, steps=12, warmup=2, flash=True):
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import gpt_124m

    paddle.seed(0)
    model = gpt_124m(hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0,
                     max_position_embeddings=max(1024, seq),
                     use_flash_attention=flash)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    n_params = sum(p.size for p in model.parameters())
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = TrainStep(model,
                     lambda logits, labels: model.loss(logits, labels),
                     opt)
    rng = np.random.RandomState(0)
    vocab = model.config.vocab_size
    ids = paddle.to_tensor(
        rng.randint(0, vocab, (batch, seq)).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, vocab, (batch, seq)).astype(np.int32))
    for _ in range(warmup):
        loss = step(ids, labels)
    float(loss.numpy())
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss.numpy())
    dt = time.perf_counter() - t0
    assert np.isfinite(final)
    tok_s = batch * seq * steps / dt
    from bench import peak_flops_per_chip
    mfu = tok_s * 6.0 * n_params / peak_flops_per_chip()
    return tok_s, mfu


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--configs", nargs="*",
                    default=["8,512", "8,512,xla", "16,512", "32,512",
                             "32,512,xla", "8,1024", "16,1024",
                             "8,2048", "16,2048", "4,4096"])
    args = ap.parse_args()
    import os
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from bench import peak_flops_per_chip
    from paddle_tpu.framework.device import enable_compile_cache

    peak_flops_per_chip()       # no TPU, or an unknown one: fail now
    enable_compile_cache()
    best = None
    for cfg in args.configs:
        parts = cfg.split(",")
        b, s = int(parts[0]), int(parts[1])
        flash = True
        if len(parts) > 2:
            if parts[2] not in ("xla", "flash"):
                raise SystemExit(
                    f"config {cfg!r}: third token must be 'flash' or "
                    "'xla'")
            flash = parts[2] == "flash"
        try:
            tok_s, mfu = measure(b, s, flash=flash)
        except Exception as e:  # OOM etc: record and continue
            print(json.dumps({"batch": b, "seq": s, "flash": flash,
                              "error": str(e)[:200]}), flush=True)
            continue
        rec = {"batch": b, "seq": s, "flash": flash,
               "tokens_per_sec": round(tok_s, 1), "mfu": round(mfu, 4)}
        print(json.dumps(rec), flush=True)
        if best is None or mfu > best["mfu"]:
            best = rec
    print(json.dumps({"best": best}), flush=True)


if __name__ == "__main__":
    main()
