"""chip_smoke.py — the quickest proof that paddle-tpu still starts on the chip.

Drives the two paths the repo exists for, once, through the entry points
a user calls, at the full width of GPT-124M with random weights from a
seed:

- train: ``amp.decorate(O2, bfloat16)`` -> ``jit.TrainStep`` + ``AdamW``,
  a few steps on one repeated batch at seq 1024 (flash + fused layernorm
  Pallas kernels on the path) and at seq 512 (fused layernorm only);
- serve: ``LLMEngine(dtype="bfloat16")`` -> ``warmup()`` ->
  ``HttpLLMServer``, concurrent ``POST /v1/completions`` from threads of
  this process, one of them streamed, so prefill chunks and decode rows
  mix in one launch of the ragged Pallas kernel;
- four chips (when ``jax.device_count() >= 4``, else printed as
  skipped): ``SpmdTrainStep`` on a dp=2 x mp=2 mesh and
  ``LLMEngine(tensor_parallel=4)`` on the same batch and requests.

Every check prints ``[ok]`` or ``[FAIL]``; any failure makes the exit
code non-zero.  ONE process: it starts no child, so nothing competes for
the chip.  There is no CPU mode, flag or environment switch — without a
TPU it exits non-zero before doing anything.  The phases are plain
functions over a model builder and sizes, so tests/test_chip_smoke.py
drives the same code at ``gpt_tiny`` on the CPU (kernels interpreted);
only ``main`` insists on the chip.

Timings printed here are SMOKE TIMINGS (did it compile, did the cache
hit, did it finish), not rates: nothing is warmed for measurement.

The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.
"""

import collections
import gc
import http.client
import json
import math
import re
import sys
import threading
import time
import traceback
import warnings

import numpy as np

# Stated tolerances.  bf16 keeps 8 significant bits: neighbouring values
# are 2**-8 to 2**-7 (0.4-0.8%) apart, and the compared paths round at
# different points.  Both feed the MXU bf16 operands and accumulate in
# f32; the flash kernel rounds a block's probabilities before the row's
# sum is known and divides at the end, XLA rounds the normalised row,
# and the ragged kernel takes q in f32.  Attention outputs are compared
# as |kernel - XLA| / max(1, |XLA|): 2e-2 is two to five bf16 steps.
# Measured on the v5e: flash 0.8e-2 (PR 21, and 7.81e-3 again in PR 25
# with 512 x 512 blocks and explicit bf16 operands), ragged 0.8e-2 — one
# step.
ATTN_TOL_BF16 = 2e-2
# engine log-probability vs an exact-as-possible f32 dense forward: the
# engine's [T, V] logits are bf16 (spacing 2**-6 = 0.016 at the chosen
# token's logit ~2.3) after 12 bf16 layers.  A wrong mask or page shows
# as >= 0.3 at random init (logit std 0.55).  Measured on the v5e
# (PR 21): 2.2e-2 over 80 tokens.
LOGPROB_TOL_BF16 = 8e-2
# one chip vs four chips: same math, different reduction order
LOSS_TOL_CHIPS = 5e-2


class Report:
    """Named checks, grouped by phase; a phase that raised is a failed
    check carrying the traceback."""

    def __init__(self):
        self.failed = []

    def check(self, phase, name, ok, detail=""):
        print(f"  [{'ok' if ok else 'FAIL'}] {phase}: {name}"
              + (f" — {detail}" if detail else ""), flush=True)
        if not ok:
            self.failed.append(f"{phase}: {name}")
        return ok

    def run(self, phase, fn, *args, **kwargs):
        """Run one phase; an exception fails it without hiding the
        phases after it."""
        print(f"== {phase}", flush=True)
        try:
            return fn(self, *args, **kwargs)
        except Exception:       # noqa: BLE001 — phase boundary, reported
            self.check(phase, "ran to the end", False,
                       traceback.format_exc())
            return None


def _mosaic_kernels(lowered_text):
    """kernel_name of every Mosaic custom call in a lowered module."""
    if "tpu_custom_call" not in lowered_text:
        return set()
    return set(re.findall(r'kernel_name\s*=\s*"([^"]+)"', lowered_text))


def _scaled_err(got, ref):
    """max |got - ref| / max(1, |ref|) in float32."""
    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    return float(np.max(np.abs(got - ref) / np.maximum(1.0, np.abs(ref))))


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


# --------------------------------------------------------------- train ----
def _train_setup(build_model, batch, seq):
    import paddle_tpu as paddle
    from paddle_tpu import optimizer

    paddle.seed(0)
    model = paddle.amp.decorate(build_model(), level="O2",
                                dtype="bfloat16")
    # 1e-4 as bench.py: Adam's first steps move every weight by ~lr, and
    # without warm-up 3e-4 already makes a 124M model's loss bounce
    opt = optimizer.AdamW(learning_rate=1e-4,
                          parameters=model.parameters())
    ids = np.random.RandomState(0).randint(
        0, model.config.vocab_size, (batch, seq)).astype(np.int32)
    return model, opt, paddle.to_tensor(ids)


def _check_losses(report, phase, losses, vocab):
    first, last = losses[0], losses[-1]
    report.check(phase, "first loss within 0.3 of ln(vocab)",
                 abs(first - math.log(vocab)) <= 0.3,
                 f"{first:.4f} vs ln({vocab}) = {math.log(vocab):.4f}")
    report.check(phase, "loss finite and lower after the steps",
                 all(np.isfinite(losses)) and last < first,
                 " -> ".join(f"{x:.4f}" for x in losses))


def train_phase(report, build_model, *, batch, seq, steps, on_chip):
    """``TrainStep`` + ``AdamW`` on one repeated batch; returns the
    losses (the four-chip phase compares against them)."""
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.ops.pallas import FLASH_MIN_SEQ

    phase = f"train[{batch}x{seq}]"
    model, opt, ids = _train_setup(build_model, batch, seq)
    step = TrainStep(model,
                     lambda logits, labels: model.loss(logits, labels), opt)
    if on_chip:
        kernels = _mosaic_kernels(step.lower(ids, ids).as_text())
        want = {"layernorm_fwd", "layernorm_bwd"}
        flash = {"flash_attention_fwd", "flash_attention_bwd_dq_dkv"}
        if seq >= FLASH_MIN_SEQ:
            want |= flash
        report.check(phase, "lowered step holds the Mosaic custom calls",
                     want <= kernels, f"want {sorted(want)}, "
                     f"found {sorted(kernels)}")
        if seq < FLASH_MIN_SEQ:
            report.check(phase, "flash kernel off below "
                         f"FLASH_MIN_SEQ={FLASH_MIN_SEQ} (by choice)",
                         not (flash & kernels))
    first, compile_s = _timed(lambda: float(step(ids, ids).numpy()))
    rest, run_s = _timed(lambda: [float(step(ids, ids).numpy())
                                  for _ in range(steps - 1)])
    losses = [first] + rest
    print(f"  smoke timing: first step (compile + run) {compile_s:.1f} s, "
          f"{steps - 1} more steps {run_s:.2f} s", flush=True)
    _check_losses(report, phase, losses, model.config.vocab_size)
    dtypes = {str(p.dtype) for p in model.parameters()}
    report.check(phase, "parameters still bfloat16 after the steps",
                 dtypes == {"bfloat16"}, str(sorted(dtypes)))
    return losses


def flash_parity(report, *, shape, on_chip):
    """The flash kernel against ``_xla_attention`` on the same device."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import _xla_attention
    from paddle_tpu.ops.pallas.attention_kernel import flash_attention_pallas

    rng = np.random.RandomState(1)
    q, k, v = (jnp.asarray(rng.randn(*shape), jnp.bfloat16)
               for _ in range(3))
    got = jax.jit(lambda q, k, v: flash_attention_pallas(
        q, k, v, is_causal=True, interpret=not on_chip))(q, k, v)
    ref = jax.jit(lambda q, k, v: _xla_attention(
        q, k, v, is_causal=True))(q, k, v)
    err = _scaled_err(got, ref)
    report.check(f"flash{list(shape)}", "kernel agrees with _xla_attention",
                 np.isfinite(err) and err <= ATTN_TOL_BF16,
                 f"max scaled err {err:.2e} (tolerance {ATTN_TOL_BF16:.0e})")


# --------------------------------------------------------------- serve ----
def ragged_parity(report, *, num_heads, head_dim, block_size, on_chip):
    """The ragged kernel against ``paged_ragged_attention_xla`` on one
    mixed batch: two decode rows (one ending on a page boundary), a
    verify-length row, a prefill chunk at a non-zero offset, dead rows,
    and padding tokens — which must come back exactly zero."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.inference.llm.paged_attention import (
        paged_ragged_attention_xla,
    )
    from paddle_tpu.ops.pallas.ragged_attention_kernel import (
        paged_ragged_attention_pallas,
    )

    bs, pages, t, rmax = block_size, 8, 64, 8
    nb = rmax * pages
    #             decode  decode@page-end  verify  prefill  dead...
    row_qlen = [1, 1, 4, 24, 0, 0, 0, 0]
    row_pos0 = [37, 3 * bs - 1, 50, bs, 0, 0, 0, 0]
    row_start = np.concatenate([[0], np.cumsum(row_qlen)[:-1]])
    live = int(np.sum(row_qlen))
    ctx = np.zeros(t, np.int32)
    rows = np.zeros(t, np.int32)
    for r in range(rmax):
        s, n = int(row_start[r]), row_qlen[r]
        ctx[s:s + n] = row_pos0[r] + np.arange(1, n + 1)
        rows[s:s + n] = r
    rng = np.random.RandomState(2)
    q = jnp.asarray(rng.randn(t, num_heads, head_dim), jnp.bfloat16)
    kp, vp = (jnp.asarray(rng.randn(nb, num_heads, bs, head_dim),
                          jnp.bfloat16) for _ in range(2))
    bt = jnp.asarray(rng.permutation(nb).reshape(rmax, pages), jnp.int32)
    desc = [jnp.asarray(x, jnp.int32)
            for x in (row_start, row_qlen, row_pos0)]
    got = jax.jit(lambda *a: paged_ragged_attention_pallas(
        *a, interpret=not on_chip))(q, kp, vp, bt, *desc)
    ref = jax.jit(paged_ragged_attention_xla)(
        q, kp, vp, bt, jnp.asarray(ctx), jnp.asarray(rows))
    got32 = np.asarray(got, np.float32)
    err = _scaled_err(got32[:live], ref[:live])
    phase = f"ragged[{num_heads}x{head_dim},bs{bs}]"
    report.check(phase, "kernel agrees with paged_ragged_attention_xla",
                 np.isfinite(err) and err <= ATTN_TOL_BF16,
                 f"max scaled err {err:.2e} over {live} live tokens "
                 f"(tolerance {ATTN_TOL_BF16:.0e})")
    report.check(phase, "padding and dead rows exactly zero",
                 not got32[live:].any())


def _post(address, body, first_token=None):
    """POST /v1/completions -> (final_body, streamed_ids).  With
    ``stream`` in the body the response is read event by event and
    ``first_token`` is set at the first delta; otherwise
    ``streamed_ids`` is None."""
    conn = http.client.HTTPConnection(*address, timeout=600)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            raise RuntimeError(f"HTTP {resp.status}: {resp.read()!r}")
        if not body.get("stream"):
            return json.loads(resp.read()), None
        streamed, final = [], None
        for raw in resp:
            line = raw.decode().strip()
            if not line.startswith("data: ") or line == "data: [DONE]":
                continue
            event = json.loads(line[len("data: "):])
            if "delta_ids" in event:
                streamed.extend(event["delta_ids"])
                first_token.set()
            else:
                final = event
        return final, streamed
    finally:
        conn.close()


def _serve_requests(address, prompts, max_new_tokens):
    """Request 0 streams; the rest are posted concurrently once its
    first token has arrived, so their prefill chunks share launches
    with its decode rows.  Returns [(final_body, streamed_ids)]."""
    results = [None] * len(prompts)
    errors = []
    decoding = threading.Event()

    def client(i):
        body = {"prompt_ids": prompts[i], "max_new_tokens": max_new_tokens,
                "logprobs": 1}
        try:
            if i == 0:
                results[i] = _post(address, dict(body, stream=True),
                                   decoding)
            else:
                decoding.wait(timeout=600)
                results[i] = _post(address, body)
        except Exception:       # noqa: BLE001 — re-raised by the caller
            errors.append(f"request {i}: {traceback.format_exc()}")
        finally:
            decoding.set()      # never leave the others waiting

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for th in threads:
        th.start()
    for th in threads:
        th.join(timeout=900)
    if errors or any(th.is_alive() for th in threads):
        raise RuntimeError("; ".join(errors) or "a client thread hung")
    return results


def _dense_logprobs(model, sequences):
    """Teacher-forced log-probability of every next token under a dense
    forward of ``model`` in float32 at the highest matmul precision —
    no paging, no kernel of ours below ``ops.pallas.FLASH_MIN_SEQ``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import functional_call

    width = min(-(-max(len(s) for s in sequences) // 128) * 128,
                model.config.max_position_embeddings)
    ids = np.zeros((len(sequences), width), np.int32)
    for i, s in enumerate(sequences):
        ids[i, :len(s)] = s
    state = {k: v._data for k, v in model.state_dict().items()}

    def fwd(state, ids):
        with jax.default_matmul_precision("highest"):
            logits = functional_call(model, state, Tensor(ids))
        logits = getattr(logits, "_data", logits)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.take_along_axis(lp[:, :-1], ids[:, 1:, None],
                                   axis=-1)[..., 0]

    return np.asarray(jax.jit(fwd)(state, jnp.asarray(ids)))


def serve_phase(report, build_model, *, dtype, max_model_len, token_budget,
                prompt_lens, max_new_tokens, logprob_tol, on_chip,
                tensor_parallel=None):
    """``LLMEngine`` -> ``warmup()`` -> ``HttpLLMServer`` -> concurrent
    completions.  Returns [(output_ids, engine logprob per emitted
    token)] per prompt (the four-chip phase compares against it)."""
    import paddle_tpu as paddle
    from paddle_tpu.inference.llm import HttpLLMServer, LLMEngine
    from paddle_tpu.ops.pallas.ragged_attention_kernel import KERNEL_NAME

    phase = "serve" if tensor_parallel is None else f"serve[tp={tensor_parallel}]"
    paddle.seed(0)
    model = build_model()
    model.eval()
    eng = LLMEngine(model, dtype=dtype, max_model_len=max_model_len,
                    block_size=16, max_batch=8, token_budget=token_budget,
                    tensor_parallel=tensor_parallel)
    free0 = eng.block_manager.num_free_blocks
    if on_chip:
        missing = [tb for _, tb, fn, args in eng.executable_grid()
                   if KERNEL_NAME not in _mosaic_kernels(
                       fn.lower(*args).as_text())]
        report.check(phase, "every lowered ragged bucket holds the Mosaic "
                     "custom call (the XLA fallback is a failure)",
                     not missing, f"buckets without it: {missing}")
    if tensor_parallel:
        devs = eng.kv_cache["k"].sharding.device_set
        report.check(phase, f"KV pool spans {tensor_parallel} devices",
                     len(devs) == tensor_parallel, f"{len(devs)} devices")
        _every_device_holds_bytes(report, phase, tensor_parallel)
    watcher, warm_s = _timed(eng.warmup)
    print(f"  smoke timing: warmup of {len(watcher.compile_ms)} buckets "
          f"(compile + one run each) {warm_s:.1f} s "
          f"{ {k: round(v / 1e3, 1) for k, v in watcher.compile_ms.items()} }",
          flush=True)

    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in rng.randint(0, eng.vocab_size, n)]
               for n in prompt_lens]
    srv = HttpLLMServer(engine=eng).start()
    try:
        results, run_s = _timed(lambda: _serve_requests(
            srv.address, prompts, max_new_tokens))
    finally:
        srv.close()
    print(f"  smoke timing: {len(prompts)} requests, prompts {prompt_lens}, "
          f"{max_new_tokens} new tokens each: {run_s:.2f} s", flush=True)

    done = [body["completions"][0] for body, _ in results]
    reasons = [c["finish_reason"] for c in done]
    report.check(phase, "every request ends with length/stop",
                 all(r in ("length", "stop") for r in reasons), str(reasons))
    report.check(phase, "streamed deltas reassemble to the final output",
                 results[0][1] == done[0]["output_ids"]
                 and len(results[0][1]) == max_new_tokens)
    report.check(phase, "no step fault, nothing quarantined",
                 eng.stats["step_faults"] == 0
                 and eng.stats["quarantined"] == 0,
                 f"step_faults={eng.stats['step_faults']} "
                 f"quarantined={eng.stats['quarantined']}")
    report.check(phase, "prefill chunks and decode rows shared a launch",
                 eng.stats["mixed_steps"] >= 1,
                 f"mixed_steps={eng.stats['mixed_steps']} of "
                 f"{eng.stats['steps']} steps")
    try:
        watcher.assert_no_new_compiles()
        report.check(phase, "no compile after warmup", True)
    except AssertionError as e:
        report.check(phase, "no compile after warmup", False, str(e))
    report.check(phase, "every page returned",
                 eng.block_manager.num_free_blocks == free0,
                 f"{eng.block_manager.num_free_blocks} free of {free0}")

    ref = _dense_logprobs(model, [p + c["output_ids"]
                                  for p, c in zip(prompts, done)])
    got, worst = [], 0.0
    for i, (p, c) in enumerate(zip(prompts, done)):
        lps = [e["logprob"] for e in c["logprobs"]]
        want = ref[i, len(p) - 1:len(p) - 1 + len(lps)]
        worst = max(worst, float(np.max(np.abs(np.asarray(lps) - want))))
        got.append((c["output_ids"], lps))
    report.check(phase, "engine log-probabilities agree with a dense "
                 "teacher-forced forward", worst <= logprob_tol,
                 f"max abs err {worst:.2e} over "
                 f"{sum(len(lps) for _, lps in got)} tokens "
                 f"(tolerance {logprob_tol:.0e})")
    return got


def compare_engines(report, phase, one_chip, four_chips, tol):
    """Log-probabilities of the two engines where they can be compared:
    random-init logits are near ties, so the engines may pick different
    tokens, and a request is compared up to its first differing one."""
    gap, compared = 0.0, 0
    for (ids_a, lp_a), (ids_b, lp_b) in zip(one_chip, four_chips):
        same = 0
        while same < len(ids_a) and ids_a[same] == ids_b[same]:
            same += 1
        compared += same
        gap = max([gap] + [abs(a - b)
                           for a, b in zip(lp_a[:same], lp_b[:same])])
    report.check(phase, "log-probabilities agree with the one-chip engine",
                 compared > 0 and gap <= tol,
                 f"max gap {gap:.2e} over {compared} commonly chosen "
                 f"tokens (tolerance {tol:.0e})")


# ---------------------------------------------------------- four chips ----
def _every_device_holds_bytes(report, phase, n):
    """Called while the sharded state is alive."""
    import jax

    used = [d.memory_stats()["bytes_in_use"] for d in jax.devices()[:n]]
    report.check(phase, "every device holds bytes", all(u > 0 for u in used),
                 f"bytes_in_use {used}")


def spmd_train_phase(report, build_model, *, batch, seq, steps, one_chip):
    """``SpmdTrainStep`` on a dp=2 x mp=2 mesh of the real devices, on
    the batch ``train_phase`` used; ``one_chip`` is its losses."""
    import jax


    from paddle_tpu.distributed.fleet.topology import build_mesh
    from paddle_tpu.parallel import SpmdTrainStep, compiled_collectives

    from paddle_tpu.ops.pallas import GSPMD_REASON, KernelFallbackWarning

    phase = f"train[{batch}x{seq},dp2xmp2]"
    model, opt, ids = _train_setup(build_model, batch, seq)
    mesh = build_mesh(dp=2, mp=2, devices=jax.devices()[:4])
    trainer = SpmdTrainStep(model, opt, mesh)
    # JAX cannot partition a Mosaic kernel under GSPMD: the flash
    # dispatcher wraps its kernels in a shard_map over the mesh and must
    # announce nothing; LayerNorm still gives way to XLA here — and only
    # here — and must say so, for that reason alone
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always", KernelFallbackWarning)
        first, compile_s = _timed(
            lambda: float(trainer.step(ids, ids).numpy()))
    said = [str(w.message) for w in caught
            if issubclass(w.category, KernelFallbackWarning)]
    flash = [m for m in said if m.startswith("flash_attention")]
    report.check(phase, "flash kernels run under the dp x mp mesh: no "
                 "fallback announced", not flash,
                 "; ".join(sorted(set(flash))) or "none announced")
    report.check(phase, "what still gave way to XLA under GSPMD said so",
                 all(GSPMD_REASON in m for m in said),
                 "; ".join(sorted(set(said))) or "no fallback announced")
    rest, run_s = _timed(lambda: [float(trainer.step(ids, ids).numpy())
                                  for _ in range(steps - 1)])
    losses = [first] + rest
    print(f"  smoke timing: first step (compile + run) {compile_s:.1f} s, "
          f"{steps - 1} more steps {run_s:.2f} s", flush=True)
    _check_losses(report, phase, losses, model.config.vocab_size)
    # the fused q|k|v is held by heads over mp: the chip's compiler, like
    # the CPU's in tests/test_gpt_parallel.py, gathers no 3h-wide operand
    # (whole, or a shard's contiguous half of the columns)
    wide = {3 * model.config.hidden_size, 3 * model.config.hidden_size // 2}
    found = compiled_collectives(
        trainer.lower(ids, ids).compile().as_text())
    kinds = collections.Counter(kind for kind, _, _ in found)
    gathered = [shapes for kind, shapes, _ in found if kind == "all-gather"
                and any(wide & set(shape) for shape in shapes)]
    report.check(phase, "the compiled step all-gathers no 3h-wide q|k|v",
                 not gathered,
                 f"collectives by kind {dict(sorted(kinds.items()))}; "
                 f"3h-wide all-gathers {gathered or 'none'}")
    leaves = jax.tree_util.tree_leaves((trainer.params, trainer.opt_state))
    sets = {len(x.sharding.device_set) for x in leaves
            if getattr(x, "ndim", 0) >= 1}
    report.check(phase, "parameter and optimizer-state shards span four "
                 "devices", sets == {4}, f"device_set sizes {sorted(sets)}")
    _every_device_holds_bytes(report, phase, 4)
    if one_chip is not None:
        gap = max(abs(a - b) for a, b in zip(losses, one_chip))
        report.check(phase, "losses agree with the one-chip run",
                     gap <= LOSS_TOL_CHIPS,
                     f"max gap {gap:.2e} (tolerance {LOSS_TOL_CHIPS:.0e})")


def fleet_placement(report, build_model):
    """Where ``Fleet`` puts four replicas' pools — recorded, not judged:
    per-replica placement is ROADMAP R7's work."""
    from paddle_tpu.inference.llm import Fleet

    model = build_model()
    model.eval()
    fleet = Fleet(model, replicas=4, dtype="bfloat16", max_model_len=256,
                  block_size=16, max_batch=2, token_budget=16)
    where = [sorted(d.id for d in r.engine.kv_cache["k"].devices())
             for r in fleet.replicas]
    print(f"  fleet: four replicas' KV pools on device ids {where}",
          flush=True)


# ---------------------------------------------------------------- main ----
def main():
    from paddle_tpu.framework.device import (
        describe_devices,
        enable_compile_cache,
        require_tpu,
    )

    try:
        require_tpu()
    except RuntimeError as e:
        print(f"chip_smoke: {e}", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    import jax
    import jaxlib

    from paddle_tpu.core import native
    from paddle_tpu.models.gpt import gpt_124m
    from paddle_tpu.ops.pallas import KernelFallbackWarning

    # a kernel giving way to its XLA composition is a failure here
    warnings.simplefilter("error", KernelFallbackWarning)
    device = describe_devices()
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = "not importable"
    print(f"device: {device}", flush=True)
    print(f"versions: jax {jax.__version__}, jaxlib {jaxlib.__version__}, "
          f"libtpu {libtpu_version}", flush=True)
    print(f"compile cache: {cache_dir}", flush=True)
    print(f"native runtime: {native.status()}", flush=True)

    def build():
        return gpt_124m(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)

    serve_sizes = dict(dtype="bfloat16", max_model_len=1024,
                       token_budget=256, prompt_lens=[24, 70, 130, 260, 400],
                       max_new_tokens=16, logprob_tol=LOGPROB_TOL_BF16,
                       on_chip=True)
    report = Report()
    t0 = time.perf_counter()
    report.run("flash parity", flash_parity, shape=(2, 1024, 12, 64),
               on_chip=True)
    losses = report.run("train", train_phase, build, batch=8, seq=1024,
                        steps=4, on_chip=True)
    report.run("train", train_phase, build, batch=8, seq=512, steps=4,
               on_chip=True)
    gc.collect()
    report.run("ragged parity", ragged_parity, num_heads=12, head_dim=64,
               block_size=16, on_chip=True)
    logprobs = report.run("serve", serve_phase, build, **serve_sizes)
    gc.collect()

    if device["count"] >= 4:
        report.run("four chips: train", spmd_train_phase, build, batch=8,
                   seq=1024, steps=4, one_chip=losses)
        gc.collect()
        tp = report.run("four chips: serve", serve_phase, build,
                        tensor_parallel=4, **serve_sizes)
        if tp is not None and logprobs is not None:
            compare_engines(report, "serve[tp=4]", logprobs, tp,
                            LOGPROB_TOL_BF16)
        gc.collect()
        report.run("four chips: fleet placement", fleet_placement, build)
    else:
        print(f"== four chips: skipped (n_devices={device['count']})",
              flush=True)

    print(f"smoke wall time {time.perf_counter() - t0:.0f} s; "
          f"{len(report.failed)} failed check(s)"
          + "".join(f"\n  FAILED {f}" for f in report.failed), flush=True)
    ok = not report.failed
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
