"""HotRowCache host-overhead measurement at 1e3..1e5 unique keys.

The module docstring (distributed/ps/heter.py) claims host hashing is
never the bottleneck for 1e3-1e5-key batches; this measures it —
steady-state hit-path pull+push wall time, plus the host key->slot
lookup share isolated (the per-pull dict walk is O(unique keys)).

Usage: JAX_PLATFORMS=cpu python benchmarks/bench_heter_cache.py
Emits one JSON line per size.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    from paddle_tpu.distributed.ps import SparseTable
    from paddle_tpu.distributed.ps.heter import HotRowCache
    from paddle_tpu.framework.device import enable_compile_cache

    enable_compile_cache()

    for n_keys in (1_000, 10_000, 100_000):
        dim = 16
        remote = SparseTable(dim=dim, optimizer="sgd", learning_rate=0.1)
        cache = HotRowCache(remote, capacity=1 << 17, optimizer="sgd",
                            learning_rate=0.1)
        rng = np.random.RandomState(0)
        keys = rng.choice(n_keys * 10, n_keys, replace=False).astype(
            np.int64)
        grads = rng.randn(n_keys, dim).astype(np.float32)

        cache.pull(keys)                       # admit (miss path, RPC)
        cache.push(keys, grads)                # compile the update
        iters = 5
        t0 = time.perf_counter()
        for _ in range(iters):
            out = cache.pull(keys)
            cache.push(keys, grads)
        np.asarray(out._value if hasattr(out, "_value") else out)
        dt = (time.perf_counter() - t0) / iters

        # isolate the host key->slot lookup share
        uniq = np.unique(keys)
        t0 = time.perf_counter()
        for _ in range(iters):
            np.fromiter((cache._slot_of.get(k, -1)
                         for k in uniq.tolist()), np.int64, len(uniq))
        lk = (time.perf_counter() - t0) / iters

        print(json.dumps({
            "unique_keys": n_keys,
            "pull_push_ms": round(dt * 1e3, 2),
            "keys_per_sec": round(n_keys / dt, 0),
            "host_lookup_ms": round(lk * 1e3, 2),
            "host_lookup_share": round(lk / dt, 3),
            "hit_rate": round(cache.stats()["hit_rate"], 4),
        }), flush=True)


if __name__ == "__main__":
    main()
