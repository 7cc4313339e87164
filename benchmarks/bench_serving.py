"""Serving benchmark: Poisson arrivals into the continuous-batching
LLMEngine (inference/llm/), CPU-runnable.

Requests arrive on a seeded Poisson clock with mixed prompt/output
lengths; the driver admits them against real wall time while stepping
the engine, and timestamps every generated token.  Reported:

- tokens/s        end-to-end generated-token throughput
- p50/p99 ms      inter-token latency (per-request gap between tokens)
- ttft p50 ms     arrival -> first token
- tpot p50/p95    per-REQUEST time-per-output-token (decode pace after
                  the first token)
- e2e p50/p95     per-request end-to-end latency (arrival -> last token)

``vs_baseline`` is throughput relative to the same trace replayed at
max_batch=1 — i.e. the measured win of continuous batching itself over
one-request-at-a-time serving on identical hardware and executables.

``--shared-prefix`` switches to the prefix-caching workload: every
request shares a common system prompt (``--prefix-len`` tokens) ahead
of a short unique suffix, the trace replays once with automatic prefix
caching ON and once OFF (the baseline), and the line reports the
throughput ratio, both TTFT p50s, and the measured cache hit rate —
the adopted prefix pages skip their prefill compute entirely, so both
throughput and time-to-first-token should win.

``--tp N`` replays the trace on a TENSOR-PARALLEL engine (params and
the paged KV pool sharded over N devices; on a CPU-only host the bench
forces N virtual host devices before the backend initializes) and on a
single-device engine, reports the throughput ratio, and asserts the TP
replay is token-exact against the single-device one.  ``--artifact``
additionally writes a MULTICHIP-style JSON file so the round harness
records TP serving alongside the training dryruns.

``--spec K`` replays a REPETITIVE agentic-style trace (templated
prompts, cyclic greedy continuations) with n-gram speculative decoding
on (up to K draft tokens per sequence per step, scored by one jitted
verify launch) and off, asserts the speculative replay is token-exact,
and reports the throughput ratio plus the measured draft acceptance
rate.  Speculation wins exactly where decode is launch-bound: the
verify step retires several tokens for one step's worth of overhead —
on a CPU host that regime is small batch (``--max-batch 1`` is the
single-stream latency case speculative decoding exists for; at large
batch the XLA-CPU step cost grows with rows and the win shrinks).

``--spec draft-model`` / ``--spec tree`` replays a named workload
trace (``--trace``, default agentic) with the MODEL-BASED drafter — a
tiny draft model built from the target's first ``--draft-layers``
blocks, zero-padded to the target's leaf shapes so it rides the SAME
ragged executable family against its own paged pools — against the
plain n-gram drafter at the same K.  GATED: token-exact, zero
post-warmup compiles on both legs, and TPOT p50 no worse than the
n-gram leg (within ``--tpot-tol``).  The row also reports the
host-overhead-fraction with the async lookahead pipeline off vs on
(plain engines, same trace) — the before/after pair PERF.md quotes.

``--replicas N --disaggregate`` serves the fleet SPLIT into
prefill-role and decode-role replicas: every request prefills on a
prefill replica and hands off at the prefill→decode boundary by
migrating its KV pages (host-staged gather/scatter, token-exact, zero
new compiles) to a decode replica.  The row gates on token-exactness
vs a single engine, zero leaked pages on EVERY pool, shared
executables and zero post-warmup compiles, and reports migrated
sequences/bytes plus handoff-latency p50/p95.  ``--migrate-chaos
SEED`` additionally injects a seeded migration-fault schedule (fail
mid-export / mid-import / delay) — handoffs that fault fall back and
retry, and the exactness + leak gates must STILL hold.

Prints ONE JSON line (bench.py convention).  ``--artifact PATH``
additionally writes the row as a JSON artifact in every mode
(MULTICHIP-style under --tp).

Usage: python benchmarks/bench_serving.py [--requests 32 --rate 256
        --max-new 24 --max-batch 8 --no-baseline]
       python benchmarks/bench_serving.py --shared-prefix
        [--requests 64 --prefix-len 256 --max-new 16]
       python benchmarks/bench_serving.py --tp 2
        [--artifact MULTICHIP_serving.json]
       python benchmarks/bench_serving.py --spec 4 --max-batch 1
        [--requests 16 --max-new 48 --artifact BENCH_spec.json]
       python benchmarks/bench_serving.py --spec tree --trace agentic
        [--spec-k 4 --draft-layers 2 --artifact BENCH_model_spec.json]
       python benchmarks/bench_serving.py --replicas 2 --disaggregate
        [--migrate-chaos 7 --artifact BENCH_disagg.json]
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, ".")

import numpy as np


def _force_device_count(n):
    """Make >= n devices visible BEFORE the jax backend initializes.

    Importing jax is fine, touching jax.devices() is not.  Only
    meaningful on CPU-only hosts — on a real multichip platform the
    CPU device count changes nothing.
    """
    import jax

    jax.config.update("jax_num_cpu_devices", int(n))


def _build_engine(max_batch, seed=0, max_model_len=64,
                  prefix_caching=True, token_budget=64, tp=1,
                  speculative=None, faults=None, retry=None,
                  max_queue=None, quantize=None, memory_budget=None,
                  num_blocks=None, lora=None, lookahead=False,
                  kv_tier=None, clock=None):
    import paddle_tpu as paddle
    from paddle_tpu.inference.llm import LLMEngine
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(seed)
    m = gpt_tiny(num_layers=2, max_position_embeddings=max_model_len)
    m.eval()
    return LLMEngine(m, block_size=8, max_batch=max_batch,
                     max_model_len=max_model_len,
                     enable_prefix_caching=prefix_caching,
                     token_budget=token_budget,
                     tensor_parallel=tp if tp > 1 else None,
                     speculative=speculative, faults=faults,
                     retry=retry, max_queue=max_queue,
                     quantize=quantize, memory_budget=memory_budget,
                     num_blocks=num_blocks, lora=lora,
                     lookahead=lookahead, kv_tier=kv_tier,
                     clock=clock)


# The trace constructors moved to paddle_tpu.sim.workloads (same
# RandomState draw order — byte-identical replays, pinned by golden
# tests).  The wrappers import lazily so the bench keeps its property
# of not touching paddle_tpu/jax before --tp forces the device count.
def _trace(n_requests, rate, max_new, seed=0):
    from paddle_tpu.sim.workloads import poisson_trace
    return poisson_trace(n_requests, rate, max_new, seed=seed)


def _shared_prefix_trace(n_requests, rate, max_new, prefix_len, seed=0):
    from paddle_tpu.sim.workloads import shared_prefix_trace
    return shared_prefix_trace(n_requests, rate, max_new, prefix_len,
                               seed=seed)


def _repetitive_trace(n_requests, rate, max_new, seed=0):
    from paddle_tpu.sim.workloads import repetitive_trace
    return repetitive_trace(n_requests, rate, max_new, seed=seed)


def _mixed_trace(n_requests, max_new, seed=0):
    from paddle_tpu.sim.workloads import mixed_trace
    return mixed_trace(n_requests, max_new, seed=seed)


def _fleet_trace(n_requests, rate, max_new, seed=0, tenants=4,
                 prefix_len=16):
    from paddle_tpu.sim.workloads import fleet_trace
    return fleet_trace(n_requests, rate, max_new, seed=seed,
                       tenants=tenants, prefix_len=prefix_len)


def _build_fleet(replicas, args, max_model_len=64, faults=None,
                 disaggregate=False):
    import paddle_tpu as paddle
    from paddle_tpu.inference.llm import Fleet
    from paddle_tpu.models.gpt import gpt_tiny

    paddle.seed(args.seed)
    m = gpt_tiny(num_layers=2, max_position_embeddings=max_model_len)
    m.eval()
    # parallel_step threads the per-replica device steps; on a
    # single-core host the GIL bounds the overlap, so the scaling
    # column reads near 1x there — the token-exactness and failover
    # gates are what tier-1 asserts
    return Fleet(m, replicas=replicas, block_size=8,
                 max_batch=args.max_batch, max_model_len=max_model_len,
                 token_budget=args.token_budget, faults=faults,
                 disaggregate=disaggregate, parallel_step=True,
                 router_load_cap=getattr(args, "router_load_cap", None))


def run(engine, arrivals, prompts, new_tokens, deadline_ms=None,
        faults=None):
    """Replay the trace in real time; returns per-token timing data.

    ``deadline_ms`` attaches a per-request deadline to every admission;
    ``faults`` is a FaultInjector whose "client"-site faults the driver
    applies as abort_request on the oldest live request (the step/alloc
    sites fire inside the engine on their own)."""
    # compile ALL ragged token buckets outside the timed window — with
    # cold buckets the first steps at each new bucket size stall on XLA
    # compiles and the measurement reflects compile time, not serving.
    # The FIRST warmup's per-bucket timings (compile + one dummy run)
    # are stashed so repeated replays on a warm engine/fleet keep
    # reporting the real compile cost, not the cache-hit replay.
    watcher = engine.warmup()
    if not getattr(engine, "_bench_warmup_ms", None):
        engine._bench_warmup_ms = {
            k: round(v, 3) for k, v in
            getattr(watcher, "compile_ms", {}).items()}
    warmup_ms = getattr(engine, "_bench_warmup_ms", {})

    t0 = time.perf_counter()
    pending = list(range(len(prompts)))
    arrival_at = {}                  # request index -> absolute time
    rid_to_idx = {}
    first_token_at = {}              # rid -> time of its first token
    last_token_at = {}               # rid -> time of its previous token
    gen_counts = {}                  # rid -> tokens seen so far
    total_tokens_done = [0]          # tokens of already-finished requests
    outputs = {}                     # request index -> full token ids
    reasons = {}                     # request index -> finish_reason
    ttfts, gaps = [], []
    tpots, e2es = [], []             # per-REQUEST decode pace / latency
    done = 0
    while done < len(prompts):
        now = time.perf_counter() - t0
        while pending and arrivals[pending[0]] <= now:
            i = pending.pop(0)
            rid = engine.add_request(prompts[i],
                                     max_new_tokens=new_tokens[i],
                                     deadline_ms=deadline_ms)
            rid_to_idx[rid] = i
            arrival_at[rid] = arrivals[i]
            gen_counts[rid] = 0
        if faults is not None and \
                faults.scheduled("client", engine._step_index + 1):
            live = sorted(engine._requests)
            if live:
                engine.abort_request(live[0])
        finished = engine.step()
        t_step = time.perf_counter() - t0
        done += len(finished)
        for fo in finished:
            outputs[rid_to_idx[fo.request_id]] = fo.all_ids.tolist()
            reasons[rid_to_idx[fo.request_id]] = fo.finish_reason
        # credit token timestamps at step granularity: each live request
        # grew by at most one token this step
        fin_lens = {fo.request_id: len(fo.output_ids) for fo in finished}
        for rid in list(gen_counts):
            if rid in fin_lens:
                req_len = fin_lens[rid]
            else:
                req = engine._requests.get(rid)
                if req is None:
                    continue                # not yet prefillled or done
                req_len = len(req.output_ids)
            while gen_counts[rid] < req_len:
                gen_counts[rid] += 1
                if gen_counts[rid] == 1:
                    ttfts.append(t_step - arrival_at[rid])
                    first_token_at[rid] = t_step
                else:
                    gaps.append(t_step - last_token_at[rid])
                last_token_at[rid] = t_step
            if rid in fin_lens:
                # per-request summary metrics: time-per-output-token
                # (decode pace after the first token) and end-to-end
                # latency (arrival -> last token)
                n = gen_counts[rid]
                if n >= 2:
                    tpots.append((last_token_at[rid]
                                  - first_token_at.pop(rid)) / (n - 1))
                else:
                    first_token_at.pop(rid, None)
                e2es.append(t_step - arrival_at[rid])
                total_tokens_done[0] += gen_counts.pop(rid)
        if not engine.has_unfinished() and pending:
            time.sleep(min(0.005, arrivals[pending[0]] - now
                           if arrivals[pending[0]] > now else 0))
    wall = time.perf_counter() - t0
    total_tokens = total_tokens_done[0] + sum(gen_counts.values())
    return {
        "wall_s": wall,
        "tokens": total_tokens,
        "tokens_per_s": total_tokens / wall,
        "p50_token_ms": float(np.percentile(gaps, 50) * 1e3) if gaps
        else None,
        "p99_token_ms": float(np.percentile(gaps, 99) * 1e3) if gaps
        else None,
        "ttft_p50_ms": float(np.percentile(ttfts, 50) * 1e3) if ttfts
        else None,
        "ttft_p95_ms": float(np.percentile(ttfts, 95) * 1e3) if ttfts
        else None,
        "tpot_p50_ms": float(np.percentile(tpots, 50) * 1e3) if tpots
        else None,
        "tpot_p95_ms": float(np.percentile(tpots, 95) * 1e3) if tpots
        else None,
        "e2e_p50_ms": float(np.percentile(e2es, 50) * 1e3) if e2es
        else None,
        "e2e_p95_ms": float(np.percentile(e2es, 95) * 1e3) if e2es
        else None,
        "preemptions": engine.lifecycle_stats()["preemptions"],
        "prefix_cache": engine.prefix_cache_stats(),
        "spec": engine.spec_stats(),
        "lifecycle": engine.lifecycle_stats(),
        "warmup_ms": warmup_ms,
        "compile_count": len(warmup_ms),
        "outputs": outputs,
        "reasons": reasons,
    }


def _spec_arg(value):
    """--spec takes an integer K (n-gram drafting) or a model-based
    method name."""
    if value in ("draft-model", "tree"):
        return value
    try:
        return int(value)
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--spec takes an integer K, 'draft-model', or 'tree'; "
            f"got {value!r}")


def main():
    ap = argparse.ArgumentParser()
    # defaults put the engine in the compute-saturated regime: gpt_tiny
    # decodes ~1.3k tok/s at batch 1 on CPU, so slower arrival rates are
    # arrival-limited and both engines tie (vs_baseline ~1.0 tells you
    # the load, not the engine)
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--rate", type=float, default=256.0,
                    help="Poisson arrival rate (req/s)")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--no-baseline", action="store_true",
                    help="skip the max_batch=1 baseline replay")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="shared system-prompt workload; baseline is "
                         "the same engine with prefix caching OFF")
    ap.add_argument("--prefix-len", type=int, default=256,
                    help="shared system prompt length (tokens)")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard the engine over "
                         "this many devices (forced virtual CPU devices "
                         "on a single-chip host)")
    ap.add_argument("--token-budget", type=int, default=64,
                    help="scheduler token budget per step")
    ap.add_argument("--spec", type=_spec_arg, default=0,
                    metavar="K|METHOD",
                    help="speculative decoding.  An integer K replays "
                         "a repetitive trace with up to K n-gram "
                         "draft tokens per sequence vs the same trace "
                         "with speculation off.  'draft-model' or "
                         "'tree' instead replays --trace (default "
                         "agentic) with the model-based drafter vs "
                         "the plain n-gram drafter, GATED on token-"
                         "exactness, zero post-warmup compiles on "
                         "both legs, and TPOT p50 no worse than the "
                         "n-gram row's (within --tpot-tol), plus a "
                         "host-overhead-fraction column measured with "
                         "the async lookahead pipeline off and on")
    ap.add_argument("--spec-k", type=int, default=4, metavar="K",
                    help="(--spec draft-model|tree) max draft tokens "
                         "per sequence per step")
    ap.add_argument("--draft-layers", type=int, default=2, metavar="L",
                    help="(--spec draft-model|tree) leading target "
                         "layers the draft model keeps; at the "
                         "2-layer bench scale the default 2 makes the "
                         "draft an exact copy (acceptance ~1), the "
                         "regime a real deployment reaches with a "
                         "distilled tiny draft")
    ap.add_argument("--tpot-tol", type=float, default=0.10,
                    help="(--spec draft-model|tree) relative headroom "
                         "on the TPOT-p50 gate vs the n-gram leg — "
                         "wall-clock on a shared CPU host is noisy at "
                         "smoke scale; PERF.md rows run large enough "
                         "to hold at the default")
    ap.add_argument("--lookahead", action="store_true",
                    help="serve with the async lookahead pipeline on "
                         "(plan+pack step N+1 under step N's device "
                         "window) in the default throughput row")
    ap.add_argument("--chaos", type=int, default=None, metavar="SEED",
                    help="replay the standard trace under a "
                         "randomized-but-seeded fault schedule "
                         "(transient/raise step faults, forced "
                         "allocator OOMs, client aborts) against a "
                         "fault-free baseline replay; reports "
                         "shed/abort/retry/deadline counts and the "
                         "p95 latency deltas the chaos cost")
    ap.add_argument("--replicas", type=int, default=0, metavar="N",
                    help="serve a Fleet of N engine replicas behind "
                         "the prefix-affinity router on a multi-tenant "
                         "trace; baseline is ONE replica on the same "
                         "trace (tokens/s scaling), and with --kill-at "
                         "or --chaos a failover leg replays the trace "
                         "under replica faults and asserts survivors "
                         "stay token-exact")
    ap.add_argument("--kill-at", type=int, default=None, metavar="STEP",
                    help="(--replicas) kill replica N-1 at this fleet "
                         "step in the failover leg")
    ap.add_argument("--disaggregate", action="store_true",
                    help="(--replicas) split the fleet into prefill-"
                         "role and decode-role replicas; every request "
                         "hands off at the prefill→decode boundary by "
                         "migrating its KV pages, gated token-exact "
                         "with zero leaks and zero new compiles")
    ap.add_argument("--migrate-chaos", type=int, default=None,
                    metavar="SEED",
                    help="(--disaggregate) seeded migration-fault "
                         "schedule (fail mid-export / mid-import / "
                         "delay) injected into the handoff path; the "
                         "token-exact and zero-leak gates must still "
                         "hold")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="(--chaos) per-request deadline_ms attached "
                         "to every admission")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="(--chaos) bounded admission: waiting-queue "
                         "depth past which requests are shed")
    ap.add_argument("--repeats", type=int, default=3,
                    help="(--spec only) replay each engine this many "
                         "times and keep the best run — wall-clock on "
                         "a shared host is too noisy for one-shot "
                         "A/B ratios")
    ap.add_argument("--artifact", default=None,
                    help="also write the bench row as a JSON artifact "
                         "to this path (MULTICHIP-style under --tp)")
    ap.add_argument("--mixed", action="store_true",
                    help="GATED acceptance row for the unified ragged "
                         "attention: replay a trace engineered so "
                         "prefill chunks and decode rows share device "
                         "steps, and fail unless the replay is "
                         "token-exact vs an unmixed serial engine, "
                         "leaks zero pages, compiles nothing after "
                         "warmup, mixed at least one step, and warmed "
                         "strictly fewer executables than the retired "
                         "per-phase grid's golden census (5 at tp=1)")
    ap.add_argument("--sampling-mix", action="store_true",
                    help="GATED acceptance row for the production "
                         "request surface: replay a burst mixing "
                         "greedy, top-p/top-k/penalty sampled, "
                         "grammar-constrained, and n=2 COW-forked "
                         "requests through ONE engine and fail unless "
                         "an armed CompileWatcher sees zero "
                         "post-warmup compiles, zero pages leak, "
                         "every request (fork children included) "
                         "finishes ok, and constrained outputs replay "
                         "legally through their grammar; reports TPOT "
                         "p50/p95 per mode")
    ap.add_argument("--quant", default=None, choices=["int8"],
                    help="GATED acceptance row for quantized serving: "
                         "derive an HBM budget that admits batch B at "
                         "full precision, then demand the int8 engine "
                         "(weight-only int8 GEMM + int8 KV pool) run "
                         "batch 2B under the SAME budget with zero "
                         "preemptions, token-count-exact outputs, zero "
                         "leaks, zero post-warmup compiles, and finite "
                         "perplexity/top-k quality deltas vs the f32 "
                         "engine")
    ap.add_argument("--lora", type=int, default=0, metavar="N",
                    help="GATED acceptance row for multi-LoRA serving: "
                         "replay a Zipf tenant mix over N registered "
                         "adapters (plus base-model traffic) as ONE "
                         "mixed continuous batch, and again through a "
                         "serial adapter-swap baseline that drains "
                         "between tenant groups; rc 1 unless the mixed "
                         "batch is >= 2x tokens/s, token-exact vs the "
                         "serial leg, leaks zero pages, and an armed "
                         "CompileWatcher sees zero post-warmup "
                         "compiles across every adapter load")
    ap.add_argument("--kv-tier", default=None, metavar="BYTES",
                    help="GATED acceptance rows for hierarchical KV: "
                         "replay the rag and thousand_tenant traces "
                         "at UNDERSIZED HBM (a page pool too small "
                         "for the working set) through an engine "
                         "backed by a host-RAM page tier + content-"
                         "addressed prefix store of this total byte "
                         "budget, and fail unless the tiered replay "
                         "is token-exact vs an unconstrained-pool "
                         "reference, leaks zero HBM pages and zero "
                         "host-pool chains, compiles nothing after "
                         "warmup, and beats BOTH the preempt-"
                         "recompute and cold-prefill baselines on "
                         "tokens/s and p95 TTFT")
    ap.add_argument("--kv-tier-blocks", type=int, default=None,
                    metavar="N",
                    help="(--kv-tier) explicit undersized HBM pool "
                         "size (pages) for the constrained legs; "
                         "default derives ~2.5 concurrent sequences' "
                         "worth from the trace shape")
    ap.add_argument("--trace", default=None, metavar="NAME",
                    help="named workload from paddle_tpu.sim.workloads "
                         "(poisson, shared_prefix, repetitive, fleet, "
                         "diurnal, agentic, thousand_tenant, rag, "
                         "hot_tenant).  Alone: a GATED replayability "
                         "row for that trace (byte-identical rebuild, "
                         "token-exact double replay, zero leaked "
                         "pages).  With --replicas: selects the fleet "
                         "trace.  With --sim: the calibration trace")
    ap.add_argument("--sim", action="store_true",
                    help="GATED calibration row for the discrete-event "
                         "simulator: replay --trace (default: fleet) "
                         "through the REAL engine on a virtual clock "
                         "and through SimEngine replicas, and fail "
                         "unless the frozen event logs match exactly, "
                         "outputs are token-exact, and the virtual "
                         "durations agree within the documented band; "
                         "also reports the sim-side router load-cap "
                         "policy A/B (docs/SIMULATOR.md)")
    ap.add_argument("--sim-profile", default="tpu-v4",
                    choices=["tpu-v4", "tpu-v5e", "cpu"],
                    help="(--sim) device profile for the roofline "
                         "step-time model")
    ap.add_argument("--router-load-cap", type=int, default=None,
                    metavar="N",
                    help="(--replicas / --sim) cap warm-affinity "
                         "routing: a replica more than N requests "
                         "above the pool's min load loses its "
                         "affinity credit and traffic spills to the "
                         "least-loaded replica (the sim-discovered "
                         "hot-tenant fix; default off = historical "
                         "routing)")
    ap.add_argument("--lint", action="store_true",
                    help="run the static cost census (graph-lint cost), "
                         "the Pallas kernel verifier (graph-lint "
                         "kernels, K001-K005) AND the concurrency lint "
                         "(graph-lint threads, R001-R005) BEFORE the "
                         "replay and embed all three in the artifact — "
                         "compile count, per-bucket FLOPs/HBM, memory "
                         "model, M001/C001/B001 findings, per-kernel "
                         "tiling/VMEM/bounds/race verdicts, and the "
                         "host loop's lock/epoch-discipline verdict")
    args = ap.parse_args()
    args._census = None

    if args.tp > 1:
        _force_device_count(args.tp)

    import jax

    from paddle_tpu.framework.device import enable_compile_cache

    enable_compile_cache()

    if args.sim:
        return _main_sim(args, jax)
    if args.tp > 1:
        return _main_tp(args, jax)
    if args.replicas > 0:
        # --chaos combines with --replicas as the fleet-chaos seed, so
        # the fleet dispatch must win over the single-engine chaos one
        if args.disaggregate:
            return _main_disagg(args, jax)
        return _main_fleet(args, jax)
    if isinstance(args.spec, str):
        return _main_model_spec(args, jax)
    if args.spec > 0:
        return _main_spec(args, jax)
    if args.shared_prefix:
        return _main_shared_prefix(args, jax)
    if args.chaos is not None:
        return _main_chaos(args, jax)
    if args.mixed:
        return _main_mixed(args, jax)
    if args.sampling_mix:
        return _main_sampling_mix(args, jax)
    if args.quant is not None:
        return _main_quant(args, jax)
    if args.lora > 0:
        return _main_lora(args, jax)
    if args.kv_tier is not None:
        return _main_kv_tier(args, jax)
    if args.trace is not None:
        return _main_trace(args, jax)

    arrivals, prompts, new_tokens = _trace(args.requests, args.rate,
                                           args.max_new, args.seed)
    eng = _build_engine(args.max_batch, args.seed,
                        lookahead=args.lookahead)
    _lint_census(args, eng)
    res = run(eng, arrivals, prompts, new_tokens)

    vs_baseline = None
    if not args.no_baseline:
        base = _build_engine(1, args.seed)
        base_res = run(base, arrivals, prompts, new_tokens)
        vs_baseline = res["tokens_per_s"] / base_res["tokens_per_s"]

    row = {
        "metric": "llm_serving_throughput",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "vs_baseline": (round(vs_baseline, 3)
                        if vs_baseline is not None else None),
        "p50_token_ms": round(res["p50_token_ms"], 2),
        "p99_token_ms": round(res["p99_token_ms"], 2),
        "ttft_p50_ms": round(res["ttft_p50_ms"], 2),
        "tpot_p50_ms": round(res["tpot_p50_ms"], 2),
        "tpot_p95_ms": round(res["tpot_p95_ms"], 2),
        "e2e_p50_ms": round(res["e2e_p50_ms"], 2),
        "e2e_p95_ms": round(res["e2e_p95_ms"], 2),
        "requests": args.requests,
        "preemptions": res["preemptions"],
        "max_batch": args.max_batch,
        "lookahead": bool(args.lookahead),
        "host_overhead_fraction": _hof(res),
        "staged_hits": res["lifecycle"].get("staged_hits", 0),
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": "gpt_tiny 2L block_size=8 max_model_len=64",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=True)


def _hof(res):
    """The run's measured host-overhead fraction (critical-path
    schedule+pack time over total step wall), rounded for the row."""
    v = res["lifecycle"].get("host_overhead_fraction")
    return round(v, 4) if v is not None else None


def _lint_census(args, eng):
    """Static pre-replay census of the engine about to be benched
    (framework.cost).  AOT-only, so it adds no compiles and leaves the
    executable caches exactly as warmup will find them; the summary
    goes to stderr (stdout stays the one bench JSON line)."""
    if not args.lint:
        return None
    from paddle_tpu.framework.cost import run_census

    census = run_census(eng)
    doc = census.to_dict()
    # the kernel verifier sweeps the registry at this engine's real
    # launch shapes — a bench artifact that says "fast" must also say
    # "the kernels it ran are provably launchable on the TPU"
    from paddle_tpu.framework.kernel_lint import lint_registry

    kfs = lint_registry(eng)
    doc["kernel_lint"] = {
        "findings": [{"rule": f.rule, "severity": f.severity,
                      "where": f.where, "message": f.message}
                     for f in kfs],
        "clean": not any(f.severity == "error" for f in kfs),
    }
    # the concurrency lint's verdict rides along too: an artifact that
    # says "fast" must also say "the host loop it measured holds its
    # lock/epoch discipline" (R001-R005 over the serving tree)
    from paddle_tpu.framework.concurrency_lint import check_concurrency

    tfs = check_concurrency()
    doc["threads"] = {
        "findings": [{"rule": f.rule, "severity": f.severity,
                      "category": f.category, "where": f.where,
                      "message": f.message} for f in tfs],
        "clean": not any(f.severity == "error" for f in tfs),
    }
    doc["clean"] = not any(
        f["severity"] == "error" for f in doc["findings"])
    print(f"lint: census {census.compile_count} executable(s), "
          f"{len(census.findings)} finding(s); kernels "
          f"{len(kfs)} finding(s); threads {len(tfs)} finding(s)",
          file=sys.stderr)
    args._census = doc
    return doc


def _write_artifact(args, row, ok):
    if not args.artifact:
        return
    doc = {"ok": bool(ok), "rc": 0 if ok else 1, "bench": row}
    if getattr(args, "_census", None) is not None:
        doc["census"] = args._census
    with open(args.artifact, "w") as f:
        json.dump(doc, f)


def _main_trace(args, jax):
    """GATED replayability row for one named workload trace: rebuilding
    the trace must be byte-identical (same seed, same arrays), two
    replays on fresh engines must be token-exact, and the replay must
    leak zero pages.  This is the contract that makes every scenario
    in paddle_tpu.sim.workloads a reproducible experiment, not a
    random load generator."""
    from paddle_tpu.sim.workloads import build_trace

    t1 = build_trace(args.trace, args.requests, args.rate,
                     args.max_new, seed=args.seed)
    t2 = build_trace(args.trace, args.requests, args.rate,
                     args.max_new, seed=args.seed)
    arrivals, prompts, new_tokens = t1
    replayable = (np.array_equal(arrivals, t2[0])
                  and len(prompts) == len(t2[1])
                  and all(np.array_equal(p, q)
                          for p, q in zip(prompts, t2[1]))
                  and new_tokens == t2[2])

    max_model_len = max(64, max(len(p) for p in prompts)
                        + args.max_new)
    eng = _build_engine(args.max_batch, args.seed,
                        max_model_len=max_model_len,
                        token_budget=args.token_budget)
    _lint_census(args, eng)
    res = run(eng, arrivals, prompts, new_tokens)
    eng2 = _build_engine(args.max_batch, args.seed,
                         max_model_len=max_model_len,
                         token_budget=args.token_budget)
    res2 = run(eng2, arrivals, prompts, new_tokens)
    token_exact = res["outputs"] == res2["outputs"]
    leaked = (eng.num_blocks - eng.block_manager.num_free_blocks) \
        + (eng2.num_blocks - eng2.block_manager.num_free_blocks)

    row = {
        "metric": "llm_serving_trace",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "trace": args.trace,
        "replayable": replayable,
        "token_exact": token_exact,
        "leaked_pages": leaked,
        "requests": args.requests,
        "tokens": res["tokens"],
        "prompt_len_max": max(len(p) for p in prompts),
        "ttft_p50_ms": (round(res["ttft_p50_ms"], 2)
                        if res["ttft_p50_ms"] is not None else None),
        "e2e_p95_ms": (round(res["e2e_p95_ms"], 2)
                       if res["e2e_p95_ms"] is not None else None),
        "preemptions": res["preemptions"],
        "prefix_hit_rate": round(res["prefix_cache"]["hit_rate"], 3),
        "max_batch": args.max_batch,
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = replayable and token_exact and leaked == 0
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            f"trace {args.trace!r} violated its contract: "
            f"replayable={replayable} token_exact={token_exact} "
            f"leaked_pages={leaked}")


def _main_kv_tier(args, jax):
    """GATED acceptance rows for hierarchical KV (--kv-tier BYTES).

    Replays the rag and thousand_tenant traces at UNDERSIZED HBM — a
    page pool sized for ~1-2 concurrent sequences while max_batch
    admits far more, so decode preempts constantly — through four
    engines per trace:

      tiered     undersized pool + host-RAM page tier / prefix store
                 of --kv-tier total bytes (preemption demotes chains,
                 re-admission swaps them back instead of re-prefilling)
      reference  unconstrained pool (the correctness oracle)
      recompute  undersized pool, no tier (preempt-recompute baseline)
      cold       undersized pool, prefix caching off (cold-prefill
                 baseline: every re-admission re-runs the full prompt)

    Every leg is the REAL engine stepped on a VIRTUAL clock priced by
    the roofline StepTimeModel under --sim-profile (the --sim
    calibration harness), with tier traffic charged at the profile's
    host-HBM link rate — the same numbers TierPolicy's break-even
    uses, and fully DETERMINISTIC, where one-shot wall-clock A/B on a
    shared CPU host is noise (wall seconds are still reported,
    ungated).

    The rows pin the engine into the CONTENDED regime the tier exists
    for (the same engineering as --mixed pins prefill/decode
    co-residency): token_budget=16 — barely above max_batch, so a
    re-prefill cannot hide in per-step budget slack and costs whole
    extra steps; the rag trace built at 4x --max-new — rag caps its
    generations at a quarter of the knob, and without multi-page
    decode growth nothing ever preempts; and per-trace pool floors
    (2.6x / 1.0x a max-length chain) sitting exactly where admission
    over-commits.  TierPolicy mode is pinned to "always": at gpt_tiny
    scale the per-chain auto estimate (chain bytes over the link vs
    replay FLOPs through ~100k weights) correctly prefers recompute
    and would disable the tier — what it deliberately ignores is the
    SYSTEMIC cost the gates measure, per-launch host overhead and
    token-budget contention of the replayed prefill.

    Gates (rc 1 on any violation, per trace): the tiered replay is
    token-exact vs the reference; zero HBM pages and zero host-pool
    chains remain after drain (page conservation holds every step —
    the engine self-checks whenever a tier is attached); an armed
    CompileWatcher sees zero post-warmup compiles in the tiered
    replay; the tier actually engaged (chains demoted AND swapped
    back in); and the tiered engine beats BOTH baselines on virtual
    tokens/s and virtual p95 TTFT."""
    from paddle_tpu.framework.cost import StepTimeModel, parse_bytes
    from paddle_tpu.sim.simulator import VirtualClock, run_virtual
    from paddle_tpu.sim.workloads import build_trace

    total = int(parse_bytes(args.kv_tier))
    tier_cfg = {"host_bytes": total - total // 2,
                "store_bytes": total // 2,
                "policy": "always"}
    # virtual steps are microseconds-scale under a TPU profile; the
    # default wall-clock arrival rate would serialize the replay and
    # nothing would ever contend for pages
    vrate = max(args.rate, 20000.0)
    token_budget = 16

    per_trace = {}
    all_ok = True
    speedups = []
    for name, pool_mult in (("rag", 2.6), ("thousand_tenant", 1.0)):
        mn = args.max_new * 4 if name == "rag" else args.max_new
        trace = build_trace(name, args.requests, vrate, mn,
                            seed=args.seed)
        arrivals, prompts, new_tokens = trace
        max_model_len = max(64, max(len(p) for p in prompts)
                            + max(new_tokens))
        max_pages = -(-max_model_len // 8)
        small = args.kv_tier_blocks or max(max_pages,
                                           int(max_pages * pool_mult))

        stm = None

        def leg(**kw):
            nonlocal stm
            clk = VirtualClock()
            eng = _build_engine(args.max_batch, args.seed,
                                max_model_len=max_model_len,
                                token_budget=token_budget,
                                clock=clk, **kw)
            watcher = eng.warmup()
            if stm is None:
                # one roofline trace serves all four legs — the
                # executable grid depends on the bucket ladder, not
                # the pool size
                stm = StepTimeModel.from_engine(
                    eng, profile=args.sim_profile,
                    host_overhead_s=2e-4)
            res = run_virtual(eng, arrivals, prompts, new_tokens,
                              step_time_model=stm, clock=clk)
            res["outputs_by_rid"] = {o.request_id: o.all_ids.tolist()
                                     for o in res["outputs"]}
            res["vtps"] = res["tokens"] / res["virtual_s"]
            res["preemptions"] = \
                eng.lifecycle_stats()["preemptions"]
            return eng, watcher, res

        ref, _, res_ref = leg()           # default pool: one full
                                          # sequence per batch slot
        tiered, watcher, res_t = leg(num_blocks=small,
                                     kv_tier=tier_cfg)
        new_compiles = watcher.new_compiles()
        tiered.check_invariants()
        tier = tiered.tier_stats()
        _, _, res_r = leg(num_blocks=small)
        _, _, res_c = leg(num_blocks=small, prefix_caching=False)

        token_exact = res_t["outputs_by_rid"] == \
            res_ref["outputs_by_rid"]
        leaked = tiered.num_blocks \
            - tiered.block_manager.num_free_blocks
        resident = tier["host_pool"]["chains"]
        engaged = tier["host_pool"]["demoted_chains"] > 0 \
            and tier["host_pool"]["swapped_in_chains"] > 0
        tput_beats = (res_t["vtps"] > res_r["vtps"]
                      and res_t["vtps"] > res_c["vtps"])
        ttft_beats = (
            res_t["ttft_ms"]["p95"] < res_r["ttft_ms"]["p95"]
            and res_t["ttft_ms"]["p95"] < res_c["ttft_ms"]["p95"])
        ok = (token_exact and leaked == 0 and resident == 0
              and not new_compiles and engaged and tput_beats
              and ttft_beats)
        all_ok = all_ok and ok
        speedups.append(res_t["vtps"]
                        / max(res_r["vtps"], res_c["vtps"]))
        per_trace[name] = {
            "ok": ok,
            "num_blocks": small,
            "num_blocks_ref": ref.num_blocks,
            "max_new": mn,
            "token_exact": token_exact,
            "leaked_pages": leaked,
            "host_resident_chains": resident,
            "new_compiles": sorted(new_compiles),
            "tier_engaged": engaged,
            "demoted_chains": tier["host_pool"]["demoted_chains"],
            "swapped_in_chains":
                tier["host_pool"]["swapped_in_chains"],
            "swapped_in_tokens": tier["swapped_in_tokens"],
            "store_promoted_pages":
                tier["prefix_store"]["promoted_pages"],
            "store_adopted_pages":
                tier["prefix_store"]["adopted_pages"],
            "virtual_tokens_per_s": {
                "tiered": round(res_t["vtps"], 1),
                "recompute": round(res_r["vtps"], 1),
                "cold": round(res_c["vtps"], 1),
                "reference": round(res_ref["vtps"], 1)},
            "virtual_ttft_p95_ms": {
                "tiered": round(res_t["ttft_ms"]["p95"], 3),
                "recompute": round(res_r["ttft_ms"]["p95"], 3),
                "cold": round(res_c["ttft_ms"]["p95"], 3)},
            "steps": {
                "tiered": res_t["steps"],
                "recompute": res_r["steps"],
                "cold": res_c["steps"]},
            "preemptions": {
                "tiered": res_t["preemptions"],
                "recompute": res_r["preemptions"],
                "cold": res_c["preemptions"]},
            "wall_s": {
                "tiered": round(res_t["wall_s"], 3),
                "recompute": round(res_r["wall_s"], 3),
                "cold": round(res_c["wall_s"], 3)},
        }

    row = {
        "metric": "llm_serving_kv_tier",
        "value": round(min(speedups), 3),
        "unit": "x virtual tokens/s vs best baseline (min over "
                "traces)",
        "kv_tier_bytes": args.kv_tier,
        "sim_profile": args.sim_profile,
        "traces": per_trace,
        "requests": args.requests,
        "max_new": args.max_new,
        "max_batch": args.max_batch,
        "backend": jax.default_backend(),
        "config": "gpt_tiny 2L block_size=8 undersized-HBM "
                  "rag+thousand_tenant virtual-clock",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=all_ok)
    if not all_ok:
        bad = {k: {kk: vv for kk, vv in v.items()
                   if not isinstance(vv, dict)}
               for k, v in per_trace.items() if not v["ok"]}
        raise SystemExit(
            f"--kv-tier violated its contract on {sorted(bad)}: "
            + json.dumps(bad))


def _main_sim(args, jax):
    """GATED calibration row for the discrete-event simulator.

    Replays --trace (default: fleet) through the REAL engine/fleet
    stepped on a virtual clock, then through SimEngine replicas with a
    ReplayOracle, and fails unless (a) the frozen event-log records —
    fleet AND every per-engine log — compare equal (decisions-exact),
    (b) outputs are token-exact, and (c) the virtual durations agree
    within the documented band.  The row's value is the simulator's
    replay speed in requests per second of wall clock; it also carries
    the sim-side hot-tenant router load-cap A/B (the policy finding
    docs/SIMULATOR.md walks through; confirm on the real engine with
    --replicas N --trace hot_tenant --router-load-cap)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.gpt import gpt_tiny
    from paddle_tpu.sim import (build_trace, calibrate,
                                hot_tenant_trace, simulate)

    paddle.seed(args.seed)
    max_model_len = max(64, 32 + args.max_new)
    m = gpt_tiny(num_layers=2, max_position_embeddings=max_model_len)
    m.eval()
    name = args.trace or "fleet"
    trace = build_trace(name, args.requests, args.rate, args.max_new,
                        seed=args.seed)
    max_model_len = max(max_model_len,
                        max(len(p) for p in trace[1]) + args.max_new)
    ek = dict(block_size=8, max_batch=args.max_batch,
              max_model_len=max_model_len,
              token_budget=args.token_budget)
    replicas = args.replicas if args.replicas > 0 else 2
    band = 0.05                 # documented in docs/SIMULATOR.md
    cal = calibrate(m, trace, replicas=replicas, engine_kwargs=ek,
                    profile=args.sim_profile,
                    fleet_kwargs=dict(
                        router_load_cap=args.router_load_cap))

    # the policy experiment, in sim: hot-tenant skew saturating one
    # replica — warm affinity alone vs the load-capped router
    ptrace = hot_tenant_trace(max(200, args.requests),
                              rate=20000.0, max_new=12, seed=args.seed)
    pek = dict(block_size=8, max_batch=4, max_model_len=64,
               token_budget=32)
    base_res, _ = simulate(m, ptrace, replicas=4, engine_kwargs=pek,
                           profile=args.sim_profile)
    cap_res, _ = simulate(m, ptrace, replicas=4, engine_kwargs=pek,
                          profile=args.sim_profile,
                          fleet_kwargs=dict(router_load_cap=2))

    ok = (cal["decisions_exact"] and cal["tokens_exact"]
          and cal["timing_err"] <= band)
    row = {
        "metric": "llm_serving_sim",
        "value": round(cal["sim"]["requests_per_wall_s"], 1),
        "unit": "sim requests/s of wall clock",
        "trace": name,
        "replicas": replicas,
        "requests": args.requests,
        "decisions_exact": cal["decisions_exact"],
        "tokens_exact": cal["tokens_exact"],
        "timing_err": round(cal["timing_err"], 6),
        "timing_band": band,
        "events": cal["events_real"],
        "profile": args.sim_profile,
        "virtual_s": round(cal["sim"]["virtual_s"], 4),
        "sim_wall_s": round(cal["sim"]["wall_s"], 3),
        "real_wall_s": round(cal["real"]["wall_s"], 3),
        "sim_speedup": round(cal["real"]["wall_s"]
                             / max(cal["sim"]["wall_s"], 1e-9), 1),
        "router_load_cap": args.router_load_cap,
        "policy_hot_tenant": {
            "ttft_p95_ms_affinity": round(
                base_res["ttft_ms"]["p95"], 2),
            "ttft_p95_ms_load_cap_2": round(
                cap_res["ttft_ms"]["p95"], 2),
            "makespan_s_affinity": round(base_res["virtual_s"], 4),
            "makespan_s_load_cap_2": round(cap_res["virtual_s"], 4),
        },
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "sim calibration violated its contract: "
            f"decisions_exact={cal['decisions_exact']} "
            f"tokens_exact={cal['tokens_exact']} "
            f"timing_err={cal['timing_err']:.4f} (band {band})")


def _main_spec(args, jax):
    """Replay a repetitive trace with n-gram speculative decoding on
    and off; assert the speculative replay is token-exact (greedy
    acceptance is longest-prefix-vs-argmax, so this must hold by
    construction) and report the decode-throughput ratio plus the
    measured draft acceptance rate."""
    # prompts stay short; leave head-room for the full generation
    max_model_len = 32 + args.max_new
    arrivals, prompts, new_tokens = _repetitive_trace(
        args.requests, args.rate, args.max_new, args.seed)
    # speculation is a DECODE-throughput optimisation, so measure the
    # saturated regime: a Poisson-paced trace is arrival-limited (both
    # engines finish shortly after the last arrival) and would measure
    # the trace, not the decoder.  Queue everything at t=0 instead.
    arrivals = np.zeros_like(arrivals)
    # wall-clock on a shared CPU host is noisy (spec-vs-base ratios
    # swing +-30% run to run), so replay each engine --repeats times and
    # keep the best run — standard best-of-N; the engine (and its
    # compiled executables) is reused so only the first replay pays
    # warmup.  token-exactness is asserted across EVERY replay pair.
    reps = max(1, args.repeats)

    eng = _build_engine(args.max_batch, args.seed,
                        max_model_len=max_model_len,
                        token_budget=args.token_budget,
                        speculative=args.spec)
    _lint_census(args, eng)
    spec_runs = [run(eng, arrivals, prompts, new_tokens)
                 for _ in range(reps)]
    res = max(spec_runs, key=lambda r: r["tokens_per_s"])

    vs_nonspec = None
    base_tpot = None
    token_exact = True
    if not args.no_baseline:
        base = _build_engine(args.max_batch, args.seed,
                             max_model_len=max_model_len,
                             token_budget=args.token_budget)
        base_runs = [run(base, arrivals, prompts, new_tokens)
                     for _ in range(reps)]
        base_res = max(base_runs, key=lambda r: r["tokens_per_s"])
        vs_nonspec = res["tokens_per_s"] / base_res["tokens_per_s"]
        base_tpot = base_res["tpot_p50_ms"]
        token_exact = all(r["outputs"] == b["outputs"]
                          for r in spec_runs for b in base_runs)

    sp = res["spec"]
    row = {
        "metric": "llm_serving_spec",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "spec_tokens": args.spec,
        "vs_nonspec": (round(vs_nonspec, 3)
                       if vs_nonspec is not None else None),
        "token_exact": token_exact,
        "acceptance_rate": round(sp["acceptance_rate"], 3),
        "draft_tokens": sp["draft_tokens"],
        "accepted_tokens": sp["accepted_tokens"],
        "spec_steps": sp["spec_steps"],
        "tpot_p50_ms": round(res["tpot_p50_ms"], 2),
        "tpot_p95_ms": round(res["tpot_p95_ms"], 2),
        "baseline_tpot_p50_ms": (round(base_tpot, 2)
                                 if base_tpot is not None else None),
        "e2e_p50_ms": round(res["e2e_p50_ms"], 2),
        "e2e_p95_ms": round(res["e2e_p95_ms"], 2),
        "ttft_p50_ms": round(res["ttft_p50_ms"], 2),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "repeats": reps,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=token_exact)
    if not token_exact:
        raise SystemExit("speculative replay diverged from non-spec")


def _main_model_spec(args, jax):
    """--spec draft-model|tree: the model-based speculation acceptance
    row, GATED.

    Replays --trace (default: agentic; diurnal is the other PERF.md
    row) through an engine whose drafter is a tiny draft MODEL — the
    target's first --draft-layers blocks zero-padded to the target's
    leaf shapes, riding the SAME ragged executable family against a
    second set of paged pools — and through the plain n-gram drafter
    at the same K.  The hybrid drafter proposes n-gram hits first
    (they are free), so its acceptance is bounded below by the n-gram
    leg's; the gate demands the row CASH that in: TPOT p50 no worse
    than the n-gram leg's (within --tpot-tol), token-exact outputs,
    and zero post-warmup compiles on BOTH legs (the draft params are
    just another first-operand to the already-warmed executables).

    Two more replays (plain engine, lookahead off/on) measure the
    host-overhead-fraction column: the async pipeline plans and packs
    step N+1 under step N's device window, so the fraction of step
    wall spent on critical-path host planning must DROP with the
    pipeline on — the before/after pair PERF.md quotes."""
    from paddle_tpu.sim.workloads import build_trace

    trace = args.trace or "agentic"
    arrivals, prompts, new_tokens = build_trace(
        trace, args.requests, args.rate, args.max_new, seed=args.seed)
    # saturated decode regime, same rationale as --spec K: speculation
    # and the lookahead pipeline are decode-rate optimisations; a
    # paced trace measures the arrival process instead
    arrivals = np.zeros_like(arrivals)
    max_model_len = max(64, max(len(p) for p in prompts)
                        + args.max_new)
    reps = max(1, args.repeats)
    spec_cfg = {"method": args.spec, "num_tokens": args.spec_k,
                "draft_layers": args.draft_layers}

    model_eng = _build_engine(args.max_batch, args.seed,
                              max_model_len=max_model_len,
                              token_budget=args.token_budget,
                              speculative=spec_cfg)
    _lint_census(args, model_eng)
    model_watch = model_eng.warmup()
    model_runs = [run(model_eng, arrivals, prompts, new_tokens)
                  for _ in range(reps)]
    model_res = min(model_runs,
                    key=lambda r: r["tpot_p50_ms"] or float("inf"))

    ngram_eng = _build_engine(args.max_batch, args.seed,
                              max_model_len=max_model_len,
                              token_budget=args.token_budget,
                              speculative=args.spec_k)
    ngram_watch = ngram_eng.warmup()
    ngram_runs = [run(ngram_eng, arrivals, prompts, new_tokens)
                  for _ in range(reps)]
    ngram_res = min(ngram_runs,
                    key=lambda r: r["tpot_p50_ms"] or float("inf"))

    token_exact = all(m["outputs"] == n["outputs"]
                      for m in model_runs for n in ngram_runs)
    new_compiles = (len(model_watch.new_compiles())
                    + len(ngram_watch.new_compiles()))

    # host-overhead before/after: plain engines (no drafter — the
    # model drafter's device-launching draft phase disables staging),
    # identical trace, pipeline off vs on
    hof = {}
    for leg, look in (("off", False), ("on", True)):
        eng = _build_engine(args.max_batch, args.seed,
                            max_model_len=max_model_len,
                            token_budget=args.token_budget,
                            lookahead=look)
        r = run(eng, arrivals, prompts, new_tokens)
        hof[leg] = {"fraction": _hof(r),
                    "staged_steps": r["lifecycle"].get(
                        "staged_steps", 0),
                    "staged_hits": r["lifecycle"].get(
                        "staged_hits", 0)}

    tpot_model = model_res["tpot_p50_ms"]
    tpot_ngram = ngram_res["tpot_p50_ms"]
    tpot_ok = (tpot_model is not None and tpot_ngram is not None
               and tpot_model <= tpot_ngram * (1.0 + args.tpot_tol))
    ok = token_exact and tpot_ok and new_compiles == 0

    sp = model_res["spec"]
    row = {
        "metric": "llm_serving_model_spec",
        "value": round(model_res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "method": args.spec,
        "trace": trace,
        "spec_tokens": args.spec_k,
        "draft_layers": args.draft_layers,
        "token_exact": token_exact,
        "new_compiles": new_compiles,
        "tpot_p50_ms": round(tpot_model, 2),
        "ngram_tpot_p50_ms": round(tpot_ngram, 2),
        "tpot_vs_ngram": round(tpot_model / tpot_ngram, 3),
        "tpot_ok": tpot_ok,
        "acceptance_rate": round(sp["acceptance_rate"], 3),
        "ngram_acceptance_rate": round(
            ngram_res["spec"]["acceptance_rate"], 3),
        "model_drafts": sp.get("model_drafts", 0),
        "ngram_drafts": sp.get("ngram_drafts", 0),
        "tree_hits": sp.get("tree_hits", 0),
        "spec_steps": sp["spec_steps"],
        "host_overhead_fraction": hof["off"]["fraction"],
        "host_overhead_fraction_lookahead": hof["on"]["fraction"],
        "staged_steps": hof["on"]["staged_steps"],
        "staged_hits": hof["on"]["staged_hits"],
        "e2e_p50_ms": round(model_res["e2e_p50_ms"], 2),
        "ttft_p50_ms": round(model_res["ttft_p50_ms"], 2),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "repeats": reps,
        "warmup_ms": model_res["warmup_ms"],
        "compile_count": model_res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=ok)
    if not token_exact:
        raise SystemExit(
            "model-based speculative replay diverged from n-gram leg")
    if new_compiles:
        raise SystemExit(
            f"{new_compiles} post-warmup compile(s) — the draft "
            f"params must ride the warmed executables")
    if not tpot_ok:
        raise SystemExit(
            f"model-based TPOT p50 {tpot_model:.2f}ms worse than "
            f"n-gram leg {tpot_ngram:.2f}ms (+{args.tpot_tol:.0%} "
            f"tolerance)")


def _main_chaos(args, jax):
    """Replay the standard trace fault-free, then again under a
    randomized-but-seeded fault schedule (transient + hard step faults,
    forced allocator OOMs, client aborts — optionally deadlines and
    bounded admission via --deadline-ms / --max-queue).  Reports the
    failure-path counters and the p95 tail-latency cost of the chaos,
    and asserts every surviving (cleanly finished) request is
    token-exact vs the fault-free replay."""
    import warnings

    from paddle_tpu.inference.llm import FaultInjector

    arrivals, prompts, new_tokens = _trace(args.requests, args.rate,
                                           args.max_new, args.seed)
    base = _build_engine(args.max_batch, args.seed,
                         token_budget=args.token_budget)
    base_res = run(base, arrivals, prompts, new_tokens)

    fi = FaultInjector.random(
        args.chaos, steps=4096, p_step=0.005, p_transient=0.03,
        p_oom=0.02, p_abort=0.01)
    eng = _build_engine(
        args.max_batch, args.seed, token_budget=args.token_budget,
        faults=fi,
        retry={"max_attempts": 3, "base_delay_s": 0.001, "jitter": 0.0},
        max_queue=args.max_queue)
    _lint_census(args, eng)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", RuntimeWarning)   # quarantines
        res = run(eng, arrivals, prompts, new_tokens,
                  deadline_ms=args.deadline_ms, faults=fi)
    eng.scheduler.check_invariants()
    leaked = eng.num_blocks - eng.block_manager.num_free_blocks

    # survivors must be byte-identical to the fault-free replay; chaos
    # casualties (abort/deadline/shed/error) are allowed to differ
    survivors = [i for i, r in res["reasons"].items()
                 if r in ("stop", "length")]
    token_exact = all(res["outputs"][i] == base_res["outputs"][i]
                      for i in survivors)

    ls = res["lifecycle"]
    row = {
        "metric": "llm_serving_chaos",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "chaos_seed": args.chaos,
        "fault_events": len(fi.events),
        "survivors": len(survivors),
        "requests": args.requests,
        "survivor_token_exact": token_exact,
        "leaked_pages": leaked,
        "shed": ls["shed"],
        "aborted": ls["aborted"],
        "deadline_missed": ls["deadline_missed"],
        "retries": ls["retries"],
        "quarantined": ls["quarantined"],
        "step_faults": ls["step_faults"],
        "preemptions": ls["preemptions"],
        "tpot_p95_ms": (round(res["tpot_p95_ms"], 2)
                        if res["tpot_p95_ms"] is not None else None),
        "tpot_p95_delta_ms": (
            round(res["tpot_p95_ms"] - base_res["tpot_p95_ms"], 2)
            if res["tpot_p95_ms"] is not None
            and base_res["tpot_p95_ms"] is not None else None),
        "e2e_p95_ms": (round(res["e2e_p95_ms"], 2)
                       if res["e2e_p95_ms"] is not None else None),
        "e2e_p95_delta_ms": (
            round(res["e2e_p95_ms"] - base_res["e2e_p95_ms"], 2)
            if res["e2e_p95_ms"] is not None
            and base_res["e2e_p95_ms"] is not None else None),
        "deadline_ms": args.deadline_ms,
        "max_queue": args.max_queue,
        "max_batch": args.max_batch,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": "gpt_tiny 2L block_size=8 max_model_len=64",
    }
    print(json.dumps(row))
    ok = token_exact and leaked == 0
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "chaos replay violated its contract: "
            f"token_exact={token_exact} leaked_pages={leaked}")


def _main_tp(args, jax):
    """Replay the trace tensor-parallel and single-device; assert the
    TP engine is token-exact, report the throughput ratio, and emit the
    MULTICHIP-style artifact (same shape the training dryruns record)."""
    n_dev = len(jax.devices())
    if n_dev < args.tp:
        raise SystemExit(
            f"--tp {args.tp} needs {args.tp} devices, found {n_dev}")

    arrivals, prompts, new_tokens = _trace(args.requests, args.rate,
                                           args.max_new, args.seed)
    eng = _build_engine(args.max_batch, args.seed,
                        token_budget=args.token_budget, tp=args.tp)
    _lint_census(args, eng)
    res = run(eng, arrivals, prompts, new_tokens)

    base = _build_engine(args.max_batch, args.seed,
                         token_budget=args.token_budget)
    base_res = run(base, arrivals, prompts, new_tokens)
    vs_single = res["tokens_per_s"] / base_res["tokens_per_s"]
    token_exact = res["outputs"] == base_res["outputs"]

    row = {
        "metric": "llm_serving_tp",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "tp": args.tp,
        "vs_single_device": round(vs_single, 3),
        "token_exact": token_exact,
        "p50_token_ms": round(res["p50_token_ms"], 2),
        "ttft_p50_ms": round(res["ttft_p50_ms"], 2),
        "tpot_p50_ms": round(res["tpot_p50_ms"], 2),
        "tpot_p95_ms": round(res["tpot_p95_ms"], 2),
        "e2e_p50_ms": round(res["e2e_p50_ms"], 2),
        "e2e_p95_ms": round(res["e2e_p95_ms"], 2),
        "requests": args.requests,
        "preemptions": res["preemptions"],
        "max_batch": args.max_batch,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "n_devices": n_dev,
        "config": "gpt_tiny 2L block_size=8 max_model_len=64",
    }
    print(json.dumps(row))

    if args.artifact:
        tail = (f"serving_tp({args.tp}): {row['value']} tok/s, "
                f"{row['vs_single_device']}x single-device, "
                f"token_exact={token_exact} "
                f"{'OK' if token_exact else 'MISMATCH'}\n")
        doc = {"n_devices": args.tp, "rc": 0 if token_exact else 1,
               "ok": token_exact, "skipped": False, "tail": tail,
               "bench": row}
        if getattr(args, "_census", None) is not None:
            doc["census"] = args._census
        with open(args.artifact, "w") as f:
            json.dump(doc, f)
    if not token_exact:
        raise SystemExit("TP replay diverged from single-device replay")


def _main_shared_prefix(args, jax):
    # room for prompt (prefix + <=12 suffix) plus the generated tokens
    max_model_len = args.prefix_len + 12 + args.max_new
    arrivals, prompts, new_tokens = _shared_prefix_trace(
        args.requests, args.rate, args.max_new, args.prefix_len,
        args.seed)

    eng = _build_engine(args.max_batch, args.seed,
                        max_model_len=max_model_len)
    _lint_census(args, eng)
    res = run(eng, arrivals, prompts, new_tokens)

    vs_baseline = base_ttft = None
    if not args.no_baseline:
        base = _build_engine(args.max_batch, args.seed,
                             max_model_len=max_model_len,
                             prefix_caching=False)
        base_res = run(base, arrivals, prompts, new_tokens)
        vs_baseline = res["tokens_per_s"] / base_res["tokens_per_s"]
        base_ttft = base_res["ttft_p50_ms"]

    pc = res["prefix_cache"]
    row = {
        "metric": "llm_serving_shared_prefix",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "vs_baseline": (round(vs_baseline, 3)
                        if vs_baseline is not None else None),
        "ttft_p50_ms": round(res["ttft_p50_ms"], 2),
        "baseline_ttft_p50_ms": (round(base_ttft, 2)
                                 if base_ttft is not None else None),
        "p50_token_ms": round(res["p50_token_ms"], 2),
        "tpot_p50_ms": round(res["tpot_p50_ms"], 2),
        "tpot_p95_ms": round(res["tpot_p95_ms"], 2),
        "e2e_p50_ms": round(res["e2e_p50_ms"], 2),
        "e2e_p95_ms": round(res["e2e_p95_ms"], 2),
        "hit_rate": round(pc["hit_rate"], 3),
        "reused_blocks": pc["reused_blocks"],
        "evictions": pc["evictions"],
        "requests": args.requests,
        "prefix_len": args.prefix_len,
        "preemptions": res["preemptions"],
        "max_batch": args.max_batch,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    _write_artifact(args, row, ok=True)


# warmup compile count of the retired per-phase executable grid at
# tp=1 (chunk buckets 8,16 + decode batch buckets 1,2,4 at the golden
# census config) — the --mixed gate requires the unified ragged family
# to warm STRICTLY fewer executables than this
_OLD_GOLDEN_TP1_COMPILES = 5


def _main_mixed(args, jax):
    """--mixed: the unified-ragged-attention acceptance row.

    Replays a trace whose long prompts chunk across several steps while
    earlier short requests decode, so prefill chunks and decode rows
    share single device steps.  GATED, not just measured — the row
    fails (rc 1, artifact ok=false) unless:

    - the mixed replay is token-exact vs a max_batch=1 serial engine
      (one request at a time CANNOT mix, so agreement proves mixing
      never changes a token),
    - the pool ends with zero leaked pages,
    - an armed CompileWatcher sees zero post-warmup compiles, and
    - warmup compiled strictly fewer executables than the retired
      per-phase grid's golden census (5 at tp=1).
    """
    max_model_len = 48 + args.max_new
    prompts, new_tokens = _mixed_trace(args.requests, args.max_new,
                                       args.seed)
    arrivals = np.zeros(len(prompts))

    eng = _build_engine(args.max_batch, args.seed,
                        max_model_len=max_model_len,
                        token_budget=args.token_budget)
    _lint_census(args, eng)
    watcher = eng.warmup()
    eng._bench_warmup_ms = {k: round(v, 3) for k, v in
                            watcher.compile_ms.items()}
    res = run(eng, arrivals, prompts, new_tokens)
    new_compiles = watcher.new_compiles()
    leaked = eng.num_blocks - eng.block_manager.num_free_blocks
    mixed_steps = eng.stats["mixed_steps"]

    token_exact = True
    base_mixed = None
    if not args.no_baseline:
        base = _build_engine(1, args.seed, max_model_len=max_model_len,
                             token_budget=args.token_budget)
        base_res = run(base, arrivals, prompts, new_tokens)
        token_exact = res["outputs"] == base_res["outputs"]
        base_mixed = base.stats["mixed_steps"]

    row = {
        "metric": "llm_serving_mixed",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "token_exact": token_exact,
        "mixed_steps": mixed_steps,
        "baseline_mixed_steps": base_mixed,
        "steps": eng.stats["steps"],
        "chunk_launches": eng.stats["chunk_launches"],
        "new_compiles": len(new_compiles),
        "leaked_pages": leaked,
        "old_golden_compile_count": _OLD_GOLDEN_TP1_COMPILES,
        "p50_token_ms": (round(res["p50_token_ms"], 2)
                         if res["p50_token_ms"] is not None else None),
        "ttft_p50_ms": (round(res["ttft_p50_ms"], 2)
                        if res["ttft_p50_ms"] is not None else None),
        "e2e_p95_ms": (round(res["e2e_p95_ms"], 2)
                       if res["e2e_p95_ms"] is not None else None),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "token_budget": args.token_budget,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (token_exact and leaked == 0 and not new_compiles
          and mixed_steps >= 1
          and res["compile_count"] < _OLD_GOLDEN_TP1_COMPILES)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "mixed replay violated its contract: "
            f"token_exact={token_exact} leaked_pages={leaked} "
            f"new_compiles={len(new_compiles)} "
            f"mixed_steps={mixed_steps} "
            f"compile_count={res['compile_count']} "
            f"(old golden {_OLD_GOLDEN_TP1_COMPILES})")


def _main_sampling_mix(args, jax):
    """--sampling-mix: the production-request-surface acceptance row.

    Replays a burst that mixes all four request modes through ONE
    engine — greedy, top-p/top-k/penalty sampled, grammar-constrained,
    and n=2 COW forks — and GATES on the request surface's contract:

    - an armed CompileWatcher sees ZERO post-warmup compiles (every
      sampling/constraint/fork knob rides batched device operands, so
      the golden census stays one ragged family),
    - the pool ends with zero leaked pages (fork families free their
      COW'd pages refcount-exactly),
    - every request (children included) finishes ok, constrained
      outputs replay legally through their grammar, and each fork
      parent produced exactly its advertised child.

    The row reports TPOT p50/p95 PER MODE, so a regression that slows
    only one mode (say, vocab-channel packing on constrained rows)
    cannot hide inside the aggregate.
    """
    from paddle_tpu.inference.llm.structured import json_array_grammar

    max_model_len = 48 + max(args.max_new, 12)
    _, prompts, new_tokens = _trace(args.requests, args.rate,
                                    args.max_new, args.seed)
    eng = _build_engine(args.max_batch, args.seed,
                        max_model_len=max_model_len,
                        token_budget=args.token_budget)
    _lint_census(args, eng)
    watcher = eng.warmup()

    grammar = json_array_grammar(eng.vocab_size, open_id=10,
                                 close_id=11, comma_id=12,
                                 item_ids=(20, 21, 22), eos_id=1,
                                 max_items=4)
    modes = ("greedy", "top_p", "constrained", "fork")
    mode_of, fork_parents = {}, []
    for i, p in enumerate(prompts):
        mode = modes[i % len(modes)]
        kw = {"max_new_tokens": new_tokens[i]}
        if mode == "top_p":
            kw.update(temperature=0.8, top_p=0.9, top_k=40,
                      repetition_penalty=1.1, seed=100 + i)
        elif mode == "constrained":
            kw.update(grammar=grammar, eos_token_id=1,
                      max_new_tokens=max(new_tokens[i], 12))
        elif mode == "fork":
            kw.update(temperature=0.7, seed=1000 + i, n=2)
        rid = eng.add_request(p, **kw)
        mode_of[rid] = mode
        if mode == "fork":
            fork_parents.append(rid)

    # drive to completion directly (not through run()) so every token
    # timestamp carries its request's mode tag
    t0 = time.perf_counter()
    first, last, counts, outs = {}, {}, {}, {}
    while eng.has_unfinished():
        finished = eng.step()
        now = time.perf_counter() - t0
        grown = {}
        for fo in finished:
            outs[fo.request_id] = fo
            grown[fo.request_id] = len(fo.output_ids)
        for rid, req in eng._requests.items():
            grown.setdefault(rid, len(req.output_ids))
        for rid, n in grown.items():
            # fork children ("<parent>.<k>") inherit the fork tag
            mode_of.setdefault(rid, "fork")
            if n > counts.get(rid, 0):
                counts[rid] = n
                first.setdefault(rid, now)
                last[rid] = now
    elapsed = time.perf_counter() - t0

    tpots = {m: [] for m in modes}
    for rid, fo in outs.items():
        n = len(fo.output_ids)
        if n >= 2 and rid in first:
            tpots[mode_of[rid]].append(
                1e3 * (last[rid] - first[rid]) / (n - 1))
    per_mode = {
        m: {"requests": sum(1 for r in outs if mode_of[r] == m),
            "tpot_p50_ms": (round(float(np.percentile(v, 50)), 2)
                            if v else None),
            "tpot_p95_ms": (round(float(np.percentile(v, 95)), 2)
                            if v else None)}
        for m, v in tpots.items()}

    new_compiles = watcher.new_compiles()
    leaked = eng.num_blocks - eng.block_manager.num_free_blocks
    all_ok = bool(outs) and all(fo.ok for fo in outs.values())

    def _legal(fo):
        s = grammar.start_state()
        for t in fo.output_ids:
            s = grammar.advance(s, int(t))
            if s is None:
                return False
        return True

    constrained_ok = all(
        _legal(fo) for rid, fo in outs.items()
        if mode_of[rid] == "constrained")
    forks_ok = all(f"{rid}.1" in outs for rid in fork_parents)

    total_tokens = sum(len(fo.output_ids) for fo in outs.values())
    row = {
        "metric": "llm_serving_sampling_mix",
        "value": round(total_tokens / max(elapsed, 1e-9), 2),
        "unit": "tokens/s",
        "per_mode": per_mode,
        "new_compiles": len(new_compiles),
        "leaked_pages": leaked,
        "all_ok": all_ok,
        "constrained_ok": constrained_ok,
        "forks_ok": forks_ok,
        "requests": args.requests,
        "fork_children": sum(1 for r in outs if "." in str(r)),
        "max_batch": args.max_batch,
        "compile_count": len(watcher.compile_ms),
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (not new_compiles and leaked == 0 and all_ok
          and constrained_ok and forks_ok)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "sampling mix violated its contract: "
            f"new_compiles={len(new_compiles)} leaked_pages={leaked} "
            f"all_ok={all_ok} constrained_ok={constrained_ok} "
            f"forks_ok={forks_ok}")


def _main_quant(args, jax):
    """--quant int8: the quantized-serving acceptance row.

    Builds a declared per-chip HBM budget from the full-precision
    engine's own memory model (weights + 2.5 max-length sequences of
    pages — admissible batch 2), then replays an all-at-t=0 trace of
    2x that batch on both engines:

    - the FULL-PRECISION leg gets exactly the pages that budget can
      hold beside its f32 weights, so running 2x the admissible batch
      forces preemptions (the pool is smaller than the trace's peak
      working set);
    - the INT8 leg (weight-only int8 GEMM + int8 KV pool) runs under
      the SAME budget via ``memory_budget=`` — the engine derives its
      admissible batch from the quantized residency model, which must
      come out >= 2x the f32 one, and the defaulted pool then holds
      the whole trace: the gate demands ZERO preemptions.

    GATED, not just measured — rc 1 unless: baseline preempts and the
    quantized leg doesn't; the quantized admissible max_batch >= 2x
    the f32 one; every request on both legs finishes by length with
    exactly prompt + max_new tokens (int8 KV is approximate, so the
    gate is token-COUNT-exact, not token-exact); zero leaked pages on
    both legs; an armed CompileWatcher sees zero post-warmup compiles;
    and the quality harness (perplexity + top-k agreement vs the f32
    engine, inference/llm/quality.py) returns finite numbers, which
    the row documents."""
    import math

    from paddle_tpu.inference.llm.quality import quality_report

    max_model_len = 64
    prompt_len, max_new = 8, 40
    rng = np.random.RandomState(args.seed)

    # full-precision probe: the budget is phrased in ITS residency
    # model so the experiment is self-calibrating, not magic numbers
    probe = _build_engine(2, args.seed, max_model_len=max_model_len,
                          token_budget=args.token_budget)
    mm = probe.memory_model()
    budget = mm["weights_bytes"] + int(2.5 * mm["seq_bytes"])
    base_batch = (budget - mm["weights_bytes"]) // mm["seq_bytes"]
    n_req = 2 * base_batch
    prompts = [rng.randint(0, 128, (prompt_len,)).astype(np.int32)
               for _ in range(n_req)]
    new_tokens = [max_new] * n_req
    arrivals = np.zeros(n_req)

    # f32 leg: all the pages the budget can hold beside f32 weights,
    # asked to run 2x the batch the budget admits -> must preempt
    base_pool = (budget - mm["weights_bytes"]) // mm["page_bytes"]
    base = _build_engine(n_req, args.seed, max_model_len=max_model_len,
                         token_budget=args.token_budget,
                         num_blocks=base_pool)
    base_res = run(base, arrivals, prompts, new_tokens)
    base_leaked = base.num_blocks - base.block_manager.num_free_blocks

    # int8 leg: SAME budget, declared -> the engine derives its own
    # admissible batch from the quantized residency model
    eng = _build_engine(n_req, args.seed, max_model_len=max_model_len,
                        token_budget=args.token_budget,
                        quantize=args.quant, memory_budget=budget)
    _lint_census(args, eng)
    watcher = eng.warmup()
    eng._bench_warmup_ms = {k: round(v, 3) for k, v in
                            watcher.compile_ms.items()}
    res = run(eng, arrivals, prompts, new_tokens)
    new_compiles = watcher.new_compiles()
    leaked = eng.num_blocks - eng.block_manager.num_free_blocks
    qmm = eng.memory_model()
    admissible_q = qmm["derived_max_batch"]

    def _count_exact(r):
        return all(
            r["reasons"][i] == "length"
            and len(r["outputs"][i]) == prompt_len + new_tokens[i]
            for i in range(n_req))

    count_exact = _count_exact(res) and _count_exact(base_res)
    quality = quality_report(probe, eng, [p.tolist() for p in prompts],
                             max_new_tokens=16)
    quality_finite = all(
        math.isfinite(quality[k]) for k in
        ("perplexity_ref", "perplexity_test", "perplexity_delta",
         "top1_agreement", "topk_agreement", "greedy_agreement"))

    row = {
        "metric": "llm_serving_quant",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "quant": args.quant,
        "memory_budget_bytes": budget,
        "base_max_batch": int(base_batch),
        "quant_max_batch": int(eng.max_batch),
        "quant_admissible_max_batch": int(admissible_q),
        "base_preemptions": base_res["preemptions"],
        "preemptions": res["preemptions"],
        "base_page_bytes": mm["page_bytes"],
        "quant_page_bytes": qmm["page_bytes"],
        "base_weights_bytes": mm["weights_bytes"],
        "quant_weights_bytes": qmm["weights_bytes"],
        "token_count_exact": count_exact,
        "leaked_pages": leaked,
        "base_leaked_pages": base_leaked,
        "new_compiles": len(new_compiles),
        "vs_baseline": round(res["tokens_per_s"]
                             / base_res["tokens_per_s"], 3),
        "perplexity_ref": round(quality["perplexity_ref"], 4),
        "perplexity_test": round(quality["perplexity_test"], 4),
        "perplexity_delta": round(quality["perplexity_delta"], 4),
        "top1_agreement": round(quality["top1_agreement"], 4),
        "topk_agreement": round(quality["topk_agreement"], 4),
        "greedy_agreement": round(quality["greedy_agreement"], 4),
        "requests": n_req,
        "max_new": max_new,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (base_res["preemptions"] > 0
          and res["preemptions"] == 0
          and eng.max_batch == n_req
          and admissible_q >= 2 * base_batch
          and count_exact
          and leaked == 0 and base_leaked == 0
          and not new_compiles
          and quality_finite)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "quant replay violated its contract: "
            f"base_preemptions={base_res['preemptions']} "
            f"preemptions={res['preemptions']} "
            f"quant_max_batch={eng.max_batch} (need {n_req}) "
            f"admissible={admissible_q} (need >= {2 * base_batch}) "
            f"token_count_exact={count_exact} "
            f"leaked={leaked}/{base_leaked} "
            f"new_compiles={len(new_compiles)} "
            f"quality_finite={quality_finite}")


def _main_lora(args, jax):
    """--lora N: the multi-LoRA serving acceptance row.

    Builds the thousand_tenant_lora_trace Zipf tenant mix over N
    registered adapters plus base-model traffic, then replays it twice
    on identically-registered engines:

    - the MIXED leg submits everything up front and lets continuous
      batching run tenants of different adapters side by side in the
      one ragged executable (per-row slot gather, slot 0 = base);
    - the SERIAL adapter-swap baseline models a one-adapter-at-a-time
      server: requests are grouped into maximal consecutive runs of
      the same adapter (trace order) and each group is fully drained
      before the next is admitted — the swap barrier that multi-LoRA
      batching removes.

    GATED, not just measured — rc 1 unless: the mixed leg is >= 2x
    the serial leg's tokens/s; the two legs are TOKEN-EXACT per
    request (batching across tenants must never change tokens); every
    adapter was actually loaded into a pool slot; armed CompileWatchers
    see zero post-warmup compiles on BOTH legs (adapter slot loads are
    host-staged device_put swaps, never recompiles); and both engines
    leak zero pages."""
    from paddle_tpu.sim.workloads import thousand_tenant_lora_trace

    n_adapters = args.lora
    max_model_len = max(64, 32 + args.max_new)
    _, prompts, new_tokens, adapter_ids = thousand_tenant_lora_trace(
        args.requests, args.rate, args.max_new, seed=args.seed,
        adapters=n_adapters + 1)
    n_req = len(prompts)

    # one weight set per adapter, shared by both legs — token-exactness
    # across legs only means anything if the adapters are the weights
    lora_cfg = dict(rank=4, max_adapters=n_adapters + 1)

    def _make_engine():
        # fresh RandomState per build -> both legs draw byte-identical
        # adapter weights
        wrng = np.random.RandomState(args.seed + 7)
        eng = _build_engine(args.max_batch, args.seed,
                            max_model_len=max_model_len,
                            token_budget=args.token_budget,
                            lora=lora_cfg)
        for a in range(1, n_adapters + 1):
            weights = {}
            for key in eng.lora.targets:
                L, d_in, d_out = eng._lora_shapes[key]
                r = eng.lora.rank
                weights[key] = (
                    wrng.standard_normal((L, d_in, r)).astype(
                        np.float32) * 0.3,
                    wrng.standard_normal((L, r, d_out)).astype(
                        np.float32) * 0.3)
            eng.add_adapter(f"adapter-{a}", weights)
        return eng

    adapters_a = _make_engine()
    adapters_b = _make_engine()

    def _replay(eng, groups):
        watcher = eng.warmup()
        eng._bench_warmup_ms = {k: round(v, 3) for k, v in
                                watcher.compile_ms.items()}
        outputs, reasons = {}, {}
        tokens = 0
        t0 = time.perf_counter()
        for group in groups:
            rid_to_idx = {}
            for i in group:
                rid = eng.add_request(prompts[i],
                                      max_new_tokens=new_tokens[i],
                                      adapter_id=adapter_ids[i])
                rid_to_idx[rid] = i
            while eng.has_unfinished():
                for fo in eng.step():
                    outputs[rid_to_idx[fo.request_id]] = \
                        fo.all_ids.tolist()
                    reasons[rid_to_idx[fo.request_id]] = \
                        fo.finish_reason
                    tokens += len(fo.output_ids)
        wall = time.perf_counter() - t0
        leaked = eng.num_blocks - eng.block_manager.num_free_blocks
        return {"outputs": outputs, "reasons": reasons,
                "tokens": tokens, "wall_s": wall,
                "tokens_per_s": tokens / wall,
                "new_compiles": watcher.new_compiles(),
                "leaked": leaked,
                "warmup_ms": eng._bench_warmup_ms}

    # serial baseline: maximal consecutive same-adapter runs, each
    # drained to empty before the next — the adapter-swap barrier
    serial_groups = []
    for i in range(n_req):
        if serial_groups and \
                adapter_ids[serial_groups[-1][-1]] == adapter_ids[i]:
            serial_groups[-1].append(i)
        else:
            serial_groups.append([i])

    _lint_census(args, adapters_a)
    mixed = _replay(adapters_a, [list(range(n_req))])
    serial = _replay(adapters_b, serial_groups)

    token_exact = mixed["outputs"] == serial["outputs"]
    all_length = all(r == "length" for r in mixed["reasons"].values())
    stats = adapters_a.lora_stats()
    speedup = mixed["tokens_per_s"] / serial["tokens_per_s"]

    row = {
        "metric": "llm_serving_lora",
        "value": round(mixed["tokens_per_s"], 2),
        "unit": "tokens/s",
        "adapters": n_adapters,
        "serial_tokens_per_s": round(serial["tokens_per_s"], 2),
        "vs_serial_swap": round(speedup, 3),
        "serial_groups": len(serial_groups),
        "token_exact": token_exact,
        "all_length": all_length,
        "adapter_loads": stats["loads"],
        "adapter_evictions": stats["evictions"],
        "adapter_hits": stats["hits"],
        "adapters_resident": stats["resident"],
        "new_compiles": len(mixed["new_compiles"]),
        "serial_new_compiles": len(serial["new_compiles"]),
        "leaked_pages": mixed["leaked"],
        "serial_leaked_pages": serial["leaked"],
        "requests": n_req,
        "max_new": args.max_new,
        "warmup_ms": mixed["warmup_ms"],
        "compile_count": len(mixed["warmup_ms"]),
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 rank=4 "
                  f"max_adapters={n_adapters + 1} "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (speedup >= 2.0
          and token_exact
          and all_length
          and stats["loads"] >= n_adapters
          and not mixed["new_compiles"]
          and not serial["new_compiles"]
          and mixed["leaked"] == 0 and serial["leaked"] == 0)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "multi-LoRA replay violated its contract: "
            f"vs_serial_swap={speedup:.3f} (need >= 2.0) "
            f"token_exact={token_exact} all_length={all_length} "
            f"adapter_loads={stats['loads']} (need >= {n_adapters}) "
            f"new_compiles={len(mixed['new_compiles'])}"
            f"/{len(serial['new_compiles'])} "
            f"leaked={mixed['leaked']}/{serial['leaked']}")


def _main_fleet(args, jax):
    """Replay a multi-tenant trace on a Fleet of N replicas and on one
    replica; assert the fleet is token-exact vs the single engine
    (routing must never change tokens), that every replica shares ONE
    executable signature set (per-replica static census — replicated
    serving must not multiply compiles), and that armed CompileWatchers
    see zero post-warmup compiles.  With --kill-at / --chaos a failover
    leg replays the same trace under replica faults: surviving requests
    must be token-exact vs the fault-free fleet replay and the live
    replicas must leak zero pages."""
    import warnings

    from paddle_tpu.framework.cost import run_census
    from paddle_tpu.inference.llm import Fault, FaultInjector

    max_model_len = max(64, 32 + args.max_new)
    if args.trace is not None:
        # a named workload replaces the default multi-tenant trace —
        # e.g. --trace hot_tenant for the router load-cap A/B
        from paddle_tpu.sim.workloads import build_trace
        arrivals, prompts, new_tokens = build_trace(
            args.trace, args.requests, args.rate, args.max_new,
            seed=args.seed)
        max_model_len = max(max_model_len,
                            max(len(p) for p in prompts)
                            + args.max_new)
    else:
        arrivals, prompts, new_tokens = _fleet_trace(
            args.requests, args.rate, args.max_new, args.seed)
    # replication is a THROUGHPUT optimisation: measure the saturated
    # regime (everything queued at t=0), or a Poisson-paced trace is
    # arrival-limited and fleet-vs-one measures the trace
    arrivals = np.zeros_like(arrivals)
    reps = max(1, args.repeats)

    fleet = _build_fleet(args.replicas, args, max_model_len)
    _lint_census(args, fleet.replicas[0].engine)
    # one executable signature set across the fleet, by static census —
    # the replicas literally share replica 0's jitted callables, and
    # this asserts the census sees the same grid through each of them
    sigs = {tuple(sorted(e["label"]
                         for e in run_census(r.engine).entries))
            for r in fleet.replicas}
    executables_shared = (len(sigs) == 1 and len(
        {id(r.engine._ragged) for r in fleet.replicas}) == 1)
    watcher = fleet.warmup()
    # replica 0 paid the compiles; stash its timings so run() reports
    # the real warmup cost, not the shared-cache replay
    fleet._bench_warmup_ms = {
        k: round(v, 3) for k, v in
        fleet.replicas[0].engine.warmup_compile_ms.items()}
    fleet_runs = [run(fleet, arrivals, prompts, new_tokens)
                  for _ in range(reps)]
    res = max(fleet_runs, key=lambda r: r["tokens_per_s"])
    new_compiles = watcher.new_compiles()

    scaling = None
    token_exact = True
    if not args.no_baseline:
        base = _build_engine(args.max_batch, args.seed,
                             max_model_len=max_model_len,
                             token_budget=args.token_budget)
        base_runs = [run(base, arrivals, prompts, new_tokens)
                     for _ in range(reps)]
        base_res = max(base_runs, key=lambda r: r["tokens_per_s"])
        scaling = res["tokens_per_s"] / base_res["tokens_per_s"]
        token_exact = all(r["outputs"] == b["outputs"]
                          for r in fleet_runs for b in base_runs)

    # failover leg: same trace, fresh fleet, seeded replica faults
    failover = None
    leaked = 0
    fail_ok = True
    if args.kill_at is not None or args.chaos is not None:
        if args.kill_at is not None:
            fi = FaultInjector(schedule=[
                Fault("replica", "kill", step=args.kill_at,
                      victim=args.replicas - 1)])
        else:
            fi = FaultInjector.random_fleet(
                args.chaos, steps=4096, replicas=args.replicas,
                p_kill=0.004, p_heartbeat=0.01, p_drain=0.002)
        chaos_fleet = _build_fleet(args.replicas, args, max_model_len,
                                   faults=fi)
        chaos_fleet.warmup()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", RuntimeWarning)
            fres = run(chaos_fleet, arrivals, prompts, new_tokens)
        chaos_fleet.check_invariants()
        leaked = sum(r.engine.num_blocks
                     - r.engine.block_manager.num_free_blocks
                     for r in chaos_fleet.replicas if r.live)
        survivors = [i for i, r in fres["reasons"].items()
                     if r in ("stop", "length")]
        surv_exact = all(fres["outputs"][i] == res["outputs"][i]
                         for i in survivors)
        fail_ok = surv_exact and leaked == 0
        ls = fres["lifecycle"]
        failover = {
            "fault_events": len(fi.events),
            "survivors": len(survivors),
            "survivor_token_exact": surv_exact,
            "leaked_pages": leaked,
            "killed": ls["killed"],
            "drains": ls["drains"],
            "requeued": ls["requeued"],
            "shed": ls["shed"],
            "lost": ls["lost"],
            "replicas_live": ls["replicas_live"],
            "e2e_p95_delta_ms": (
                round(fres["e2e_p95_ms"] - res["e2e_p95_ms"], 2)
                if fres["e2e_p95_ms"] is not None
                and res["e2e_p95_ms"] is not None else None),
        }

    ls = res["lifecycle"]
    row = {
        "metric": "llm_serving_fleet",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "replicas": args.replicas,
        "scaling_vs_1": (round(scaling, 3)
                         if scaling is not None else None),
        "token_exact": token_exact,
        "executables_shared": executables_shared,
        "new_compiles": len(new_compiles),
        "routed": ls["routed"],
        "affinity_hit_rate": round(ls["affinity_hit_rate"], 3),
        "prefix_hit_rate": round(res["prefix_cache"]["hit_rate"], 3),
        "requeued": ls["requeued"],
        "shed": ls["shed"],
        "failover": failover,
        "tpot_p50_ms": (round(res["tpot_p50_ms"], 2)
                        if res["tpot_p50_ms"] is not None else None),
        "e2e_p50_ms": (round(res["e2e_p50_ms"], 2)
                       if res["e2e_p50_ms"] is not None else None),
        "e2e_p95_ms": (round(res["e2e_p95_ms"], 2)
                       if res["e2e_p95_ms"] is not None else None),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "repeats": reps,
        "kill_at": args.kill_at,
        "chaos_seed": args.chaos,
        "trace": args.trace or "fleet",
        "router_load_cap": args.router_load_cap,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (token_exact and fail_ok and executables_shared
          and not new_compiles)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "fleet replay violated its contract: "
            f"token_exact={token_exact} failover_ok={fail_ok} "
            f"executables_shared={executables_shared} "
            f"new_compiles={len(new_compiles)}")


def _main_disagg(args, jax):
    """Replay the multi-tenant trace on a DISAGGREGATED fleet (prefill-
    role + decode-role replicas; every sequence migrates its KV pages
    at the prefill→decode boundary) and on one unified engine.  Gates:
    the disaggregated replay is token-exact (migration must never
    change a token), EVERY replica's pool ends with zero leaked pages,
    the replicas share one executable signature set, and an armed
    CompileWatcher sees zero post-warmup compiles (the migration path
    is host-staged — nothing on it may trace).  ``--migrate-chaos``
    injects a seeded migration-fault schedule into the same replay;
    faulted handoffs fall back (decode in place, retry next step) and
    every gate must still hold."""
    from paddle_tpu.framework.cost import run_census
    from paddle_tpu.inference.llm import FaultInjector

    if args.replicas < 2:
        raise SystemExit("--disaggregate needs --replicas >= 2")
    max_model_len = max(64, 32 + args.max_new)
    arrivals, prompts, new_tokens = _fleet_trace(
        args.requests, args.rate, args.max_new, args.seed)
    arrivals = np.zeros_like(arrivals)

    fi = None
    if args.migrate_chaos is not None:
        # dense schedule: short replays still see several fired faults
        # (a scheduled fault only fires when a handoff is attempted at
        # that step — consume-once semantics)
        fi = FaultInjector.random_fleet(
            args.migrate_chaos, steps=4096, replicas=args.replicas,
            p_migration=0.25)
    fleet = _build_fleet(args.replicas, args, max_model_len, faults=fi,
                         disaggregate=True)
    _lint_census(args, fleet.replicas[0].engine)
    sigs = {tuple(sorted(e["label"]
                         for e in run_census(r.engine).entries))
            for r in fleet.replicas}
    executables_shared = (len(sigs) == 1 and len(
        {id(r.engine._ragged) for r in fleet.replicas}) == 1)
    watcher = fleet.warmup()
    fleet._bench_warmup_ms = {
        k: round(v, 3) for k, v in
        fleet.replicas[0].engine.warmup_compile_ms.items()}
    res = run(fleet, arrivals, prompts, new_tokens)
    new_compiles = watcher.new_compiles()
    fleet.check_invariants()
    leaked = sum(r.engine.num_blocks
                 - r.engine.block_manager.num_free_blocks
                 for r in fleet.replicas)

    token_exact = True
    scaling = None
    if not args.no_baseline:
        base = _build_engine(args.max_batch, args.seed,
                             max_model_len=max_model_len,
                             token_budget=args.token_budget)
        base_res = run(base, arrivals, prompts, new_tokens)
        scaling = res["tokens_per_s"] / base_res["tokens_per_s"]
        token_exact = res["outputs"] == base_res["outputs"]

    mms = fleet.migration_ms
    ls = res["lifecycle"]
    row = {
        "metric": "llm_serving_disagg",
        "value": round(res["tokens_per_s"], 2),
        "unit": "tokens/s",
        "replicas": args.replicas,
        "roles": {str(k): v for k, v in fleet.roles().items()},
        "scaling_vs_1": (round(scaling, 3)
                         if scaling is not None else None),
        "token_exact": token_exact,
        "executables_shared": executables_shared,
        "new_compiles": len(new_compiles),
        "leaked_pages": leaked,
        "migrated": ls["migrated"],
        "migrated_bytes": ls["migrated_bytes"],
        "migration_failed": ls["migration_failed"],
        "handoff_p50_ms": (round(float(np.percentile(mms, 50)), 3)
                           if mms else None),
        "handoff_p95_ms": (round(float(np.percentile(mms, 95)), 3)
                           if mms else None),
        "migrate_chaos_seed": args.migrate_chaos,
        "migration_fault_events": (len(fi.events)
                                   if fi is not None else 0),
        "tpot_p50_ms": (round(res["tpot_p50_ms"], 2)
                        if res["tpot_p50_ms"] is not None else None),
        "e2e_p50_ms": (round(res["e2e_p50_ms"], 2)
                       if res["e2e_p50_ms"] is not None else None),
        "e2e_p95_ms": (round(res["e2e_p95_ms"], 2)
                       if res["e2e_p95_ms"] is not None else None),
        "requests": args.requests,
        "max_batch": args.max_batch,
        "warmup_ms": res["warmup_ms"],
        "compile_count": res["compile_count"],
        "backend": jax.default_backend(),
        "config": f"gpt_tiny 2L block_size=8 "
                  f"max_model_len={max_model_len}",
    }
    print(json.dumps(row))
    ok = (token_exact and leaked == 0 and executables_shared
          and not new_compiles)
    _write_artifact(args, row, ok=ok)
    if not ok:
        raise SystemExit(
            "disaggregated replay violated its contract: "
            f"token_exact={token_exact} leaked_pages={leaked} "
            f"executables_shared={executables_shared} "
            f"new_compiles={len(new_compiles)}")


if __name__ == "__main__":
    main()
