"""Eager dispatch microbenchmark (VERDICT round-1 item #7).

Measures small-op eager dispatch rate (op/s) with the jit-dispatch cache on
vs off, on the grad path (stop_gradient=False inputs) where the uncached
path pays a fresh ``jax.vjp`` trace per call — the structural overhead the
reference's generated C++ dispatch pipeline exists to avoid (SURVEY §3.1).

Prints one JSON line per configuration.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def rate(x, y, n=300):
    for _ in range(5):
        _ = x + y
    t0 = time.perf_counter()
    for _ in range(n):
        _ = x + y
    return n / (time.perf_counter() - t0)


def bwd_rate(x, y, n=100):
    for _ in range(3):
        (x * y).sum().backward()
    t0 = time.perf_counter()
    for _ in range(n):
        (x * y).sum().backward()
    return n / (time.perf_counter() - t0)


def main():
    import paddle_tpu as paddle
    from paddle_tpu.framework.device import enable_compile_cache
    from paddle_tpu.ops import enable_dispatch_cache

    enable_compile_cache()

    x = paddle.to_tensor(np.random.rand(16).astype(np.float32),
                         stop_gradient=False)
    y = paddle.to_tensor(np.random.rand(16).astype(np.float32),
                         stop_gradient=False)

    results = {}
    for cached in (True, False):
        enable_dispatch_cache(cached)
        tag = "cached" if cached else "uncached"
        results[f"add_grad_path_{tag}"] = round(rate(x, y), 1)
        results[f"fwd_bwd_{tag}"] = round(bwd_rate(x, y), 1)
    enable_dispatch_cache(True)

    for metric in ("add_grad_path", "fwd_bwd"):
        speedup = results[f"{metric}_cached"] / max(
            1e-9, results[f"{metric}_uncached"])
        print(json.dumps({
            "metric": f"eager_dispatch_{metric}_ops_per_sec",
            "value": results[f"{metric}_cached"],
            "unit": "op/s",
            "vs_baseline": round(speedup, 2),
        }))


if __name__ == "__main__":
    main()
