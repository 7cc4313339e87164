"""Long-context attention benchmark: flash kernel vs ring/Ulysses
context parallelism over a sequence-sharded mesh.

Round-4 priority 5 (ROADMAP): measure ring attention on real ICI at 32k+
tokens.  On CPU this runs tiny shapes as a smoke/regression harness; on
a TPU slice pass --seq 32768 --devices 4 (the sp axis rides ICI).

Prints one JSON line per (mode, seq) with tokens/s:
    python benchmarks/bench_longcontext.py --seq 2048 8192 --devices 8
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, nargs="+", default=[1024, 4096])
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--batch", type=int, default=1)
    ap.add_argument("--devices", type=int, default=0,
                    help="sp degree (0 = all visible devices)")
    ap.add_argument("--iters", type=int, default=5)
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (virtual devices)")
    args = ap.parse_args()

    if args.cpu or os.environ.get("JAX_PLATFORMS") == "cpu":
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # APPEND to any user flags (setdefault would silently drop
            # the device count and shrink the mesh)
            os.environ["XLA_FLAGS"] = (
                flags + " --xla_force_host_platform_device_count="
                f"{max(args.devices, 4)}").strip()
        os.environ["JAX_PLATFORMS"] = "cpu"

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from paddle_tpu.distributed.fleet.meta_parallel.sequence_parallel \
        import context_parallel_attention
    from paddle_tpu.framework.device import enable_compile_cache
    from paddle_tpu.ops import pallas

    enable_compile_cache()

    n_dev = args.devices or len(jax.devices())
    if n_dev > len(jax.devices()):
        print(f"# only {len(jax.devices())} devices available "
              f"(requested {n_dev})", file=sys.stderr)
        n_dev = len(jax.devices())
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("sp",))

    def measure(fn, *xs):
        out = fn(*xs)
        jax.block_until_ready(out)
        best = float("inf")
        for _ in range(args.iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*xs))
            best = min(best, time.perf_counter() - t0)
        return best

    rng = np.random.RandomState(0)
    for seq in args.seq:
        shape = (args.batch, seq, args.heads, args.head_dim)
        q, k, v = (jnp.asarray(rng.rand(*shape).astype(np.float32) * 0.1)
                   for _ in range(3))

        # single-device flash kernel (the non-parallel baseline)
        flash = jax.jit(lambda q, k, v: pallas.flash_attention(
            q, k, v, is_causal=True))
        t_flash = measure(flash, q, k, v)

        results = {"seq": seq, "devices": n_dev,
                   "flash_tokens_per_s": round(args.batch * seq / t_flash)}

        for mode in ("ring", "ulysses"):
            # ring only needs the SEQUENCE divisible by the sp degree;
            # Ulysses additionally all-to-alls over heads
            if seq % n_dev or (mode == "ulysses"
                               and args.heads % n_dev):
                print(f"# skip {mode} at seq={seq}: "
                      f"seq/heads not divisible by {n_dev} devices",
                      file=sys.stderr)
                continue
            sharded = NamedSharding(mesh, P(None, "sp", None, None))
            qs, ks, vs = (jax.device_put(x, sharded) for x in (q, k, v))

            def cp(qq, kk, vv, _mode=mode):
                return context_parallel_attention(
                    qq, kk, vv, mesh, axis="sp", mode=_mode,
                    is_causal=True)

            cpj = jax.jit(cp)
            t_cp = measure(cpj, qs, ks, vs)
            results[f"{mode}_tokens_per_s"] = round(
                args.batch * seq / t_cp)
            # parity spot-check at the smallest size only (cheap)
            if seq == min(args.seq):
                ref = np.asarray(flash(q, k, v))
                got = np.asarray(cpj(qs, ks, vs))
                err = float(np.max(np.abs(ref - got)))
                results[f"{mode}_max_err"] = err

        print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
