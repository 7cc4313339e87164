"""A kernel's share of its roofline, in %: the least time the chip could
take for the calls of the window (the larger of operations / peak FLOP/s
and bytes / peak bytes/s, both from ``chipbench/kernel_costs/<cost>.py``)
over the kernel's own time in the trace.  Returns nothing where the
kernel did not run."""

import importlib

from .. import trace


def read(env, pattern, cost):
    ev = env.traced["devices"][min(env.traced["devices"])]
    seconds = trace.kernel_seconds(ev, pattern)
    if seconds <= 0:
        return None
    mod = importlib.import_module(f"chipbench.kernel_costs.{cost}")
    flops, nbytes = mod.window_cost(env)
    by_compute = flops / env.peaks["bf16_flops"]
    by_memory = nbytes / env.peaks["hbm_bytes_per_s"]
    least = max(by_compute, by_memory)
    bound = "compute" if by_compute >= by_memory else "memory"
    env.ctx.note(f"roofline {pattern}: {flops:.4g} FLOP, {nbytes:.4g} B, "
                 f"least {least:.4f} s ({bound}-bound), kernel "
                 f"{seconds:.4f} s")
    return 100.0 * least / seconds
