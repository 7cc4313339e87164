"""Per-layer metrics: one small reader each, found by the ``reader`` a
``chipbench/metrics/<metric>.json`` file names.

A reader is ``read(env, **args) -> number | None``.  ``env`` carries the
runner's result (whole steps, memory peaks), the cell's files, the
chip count, the peaks of the attached device and -- loaded once -- the
reduced trace.  A reader that finds nothing to read returns ``None`` and
the metric is left out of the line.
"""

import importlib
import json
import os

from .. import stats, trace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def start_trace(ctx):
    """Profiler on, Python call tracing off (the trace stays small and the
    host stays quick); ``chipbench::`` spans are TraceMe events."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(ctx.trace_dir, profiler_options=opts)


def peaks_for(kind):
    with open(os.path.join(BENCH, "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if kind not in table:
        raise KeyError(f"no peaks known for device_kind {kind!r} (known: "
                       f"{sorted(table)}); add it to peaks.json with its "
                       f"source")
    return table[kind]


class Env:
    def __init__(self, ctx, res, chips):
        import jax

        self.ctx, self.res, self.chips = ctx, res, chips
        self.config, self.traffic = ctx.config, ctx.traffic
        self.steps = stats.whole_steps(res["steps"], ctx.seconds)
        self.end_to_end = res["end_to_end"]
        self.device_kind = jax.devices()[0].device_kind
        self._trace = None

    @property
    def peaks(self):
        return peaks_for(self.device_kind)

    @property
    def traced(self):
        """{"devices": {chip: events clipped to the window}, "spans",
        "window": (lo, hi)} of this run's trace."""
        if self._trace is None:
            raw = trace.load(trace.find_xplane(self.ctx.trace_dir))
            lo, hi = trace.window_of(raw["spans"])
            for line in ("devices", "async"):
                raw[line] = {c: trace.clip(ev, lo, hi)
                             for c, ev in raw[line].items()
                             if c < self.chips}
            raw["window"] = (lo, hi)
            self._trace = raw
        return self._trace


def read_all(ctx, res, names, chips):
    """({metric: value}, breakdown, {"busy_s", "window_s"})."""
    env = Env(ctx, res, chips)
    values = {}
    for name in names:
        with open(os.path.join(BENCH, "metrics", name + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        value = reader.read(env, **spec.get("args", {}))
        if value is not None:
            values[name] = float(value)
    tr = env.traced
    lo, hi = tr["window"]
    if not tr["devices"]:
        raise RuntimeError("the trace holds no TPU device plane")
    busy = [trace.busy_seconds(ev) for ev in tr["devices"].values()]
    first = tr["devices"][min(tr["devices"])]
    breakdown = {
        "device_ops": trace.top(trace.self_times(first)),
        "idle_gaps": trace.top(trace.idle_gaps(first, tr["spans"], lo, hi)),
    }
    return values, breakdown, {"busy_s": sum(busy) / len(busy),
                               "window_s": hi - lo}
