"""Benchmark: GPT-124M causal-LM training throughput on one TPU chip.

One process, and a TPU or nothing: it raises when JAX finds no TPU and
when the device_kind has no known peak, and a phase that fails ends the
run with a traceback and a non-zero exit code.  There is no CPU mode.

Prints one JSON line per finished phase; the last line is the full
record: {"metric", "value", "unit", "vs_baseline", "device", ...}.
vs_baseline = achieved MFU / 0.45 (the BASELINE.md north-star MFU target) —
the reference repo publishes no absolute numbers (SURVEY §6), so the target
ratio is the honest comparison.  MFU here is 6 * params * tokens/s over the
chip's bf16 peak: attention FLOPs are left out.
"""

import json
import time

import numpy as np

# bf16 peak FLOP/s per chip (Google Cloud TPU system-architecture pages),
# keyed by the device_kind libtpu 0.0.34 reports for v4, v5e, v5p, v6e.
_PEAK_BF16 = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


def peak_flops_per_chip():
    """bf16 peak of the attached TPU; an unknown device is an error."""
    from paddle_tpu.framework.device import require_tpu

    kind = require_tpu().device_kind
    if kind not in _PEAK_BF16:
        raise ValueError(
            f"no bf16 peak known for device_kind {kind!r} "
            f"(known: {sorted(_PEAK_BF16)}); add it with its source")
    return _PEAK_BF16[kind]


def _measure(model, cfg, steps, warmup, seed):
    """Shared measurement scaffold: warmup, synced timed loop, MFU."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer
    from paddle_tpu.jit import TrainStep

    n_params = sum(p.size for p in model.parameters())
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    step = TrainStep(model,
                     lambda logits, labels: model.loss(logits, labels), opt)
    rng = np.random.RandomState(seed)
    vocab = model.config.vocab_size
    ids = paddle.to_tensor(
        rng.randint(0, vocab, (cfg["batch"], cfg["seq"])).astype(np.int32))
    labels = paddle.to_tensor(
        rng.randint(0, vocab, (cfg["batch"], cfg["seq"])).astype(np.int32))
    for _ in range(warmup):
        loss = step(ids, labels)
    float(loss.numpy())  # sync
    t0 = time.perf_counter()
    for _ in range(steps):
        loss = step(ids, labels)
    final = float(loss.numpy())  # sync
    dt = time.perf_counter() - t0
    if not np.isfinite(final):
        raise FloatingPointError(f"loss diverged during bench: {final}")
    tok_s = cfg["batch"] * cfg["seq"] * steps / dt
    mfu = tok_s * 6.0 * n_params / peak_flops_per_chip()
    return tok_s, mfu


def main():
    from paddle_tpu.framework.device import (
        describe_devices,
        enable_compile_cache,
    )

    peak_flops_per_chip()       # no TPU, or an unknown one: fail now
    enable_compile_cache()

    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_124m
    from paddle_tpu.models.llama import llama_160m

    paddle.seed(0)
    model = gpt_124m(hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = paddle.amp.decorate(model, level="O2", dtype="bfloat16")
    tok_s, mfu = _measure(model, dict(batch=8, seq=512), 20, 3, seed=0)
    out = {
        "metric": "gpt124m_train_tokens_per_sec_per_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.45, 4),
        "device": describe_devices(),
    }
    print(json.dumps(out), flush=True)

    # batch 8 x 512 under-saturates the MXU (r5 measured 30.9% MFU);
    # 124M params use ~2.5GB for params+grads+opt, leaving v5e HBM room
    # for much larger batches.  Measure batch 32 and take the better
    # number as the headline.
    tok32, mfu32 = _measure(model, dict(batch=32, seq=512), 12, 2, seed=0)
    out["b32_tokens_per_sec"] = round(tok32, 1)
    out["b32_mfu"] = round(mfu32, 4)
    if mfu32 > mfu:
        out["value"] = round(tok32, 1)
        out["vs_baseline"] = round(mfu32 / 0.45, 4)
        out["config"] = "batch=32,seq=512"
    print(json.dumps(out), flush=True)

    # Second measured config: Llama-family decoder (RoPE/GQA/SwiGLU).
    paddle.seed(1)
    lmodel = paddle.amp.decorate(llama_160m(), level="O2",
                                 dtype="bfloat16")
    ltok_s, lmfu = _measure(lmodel, dict(batch=8, seq=512), 10, 2, seed=1)
    out.update({
        "llama_metric": "llama160m_train_tokens_per_sec_per_chip",
        "llama_value": round(ltok_s, 1),
        "llama_vs_baseline": round(lmfu / 0.45, 4),
    })
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
