"""Run ONE cell of the benchmark once.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

A new process that needs a TPU with as many chips as the cell asks for
(exit code 2 and nothing on stdout otherwise), loads, warms up, measures
for ``--seconds`` and prints as its LAST stdout line one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` and, with
``--trace 1``, ``breakdown``.  With ``--trace 0`` the metrics are the
cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics.

Everything particular to a configuration, a traffic mix or a per-layer
metric is found by NAME: ``BENCHMARK.json`` -> ``configs/<name>.json``
(which names its ``runner`` and ``reference``), ``traffic/<name>.json``
(which names its ``kind``), ``metrics/<name>.json`` (which names its
``reader``).  This file imports nothing model-specific.

``--rehearse`` is not a cell: it takes the cells of
``chipbench/rehearsal/BENCHMARK.json`` (tiny sizes), runs on whatever
device JAX has, names that device in the line and always prints
``"correct": false`` -- a CPU run proves nothing about the chip.
``--control <precision>[,<precision>]`` also puts the reference in that
lower precision in the program's place and prints its numbers beside
the limits (the builder's tool for setting them; the line then says
``"correct": false``).
"""

import argparse
import gc
import importlib
import json
import os
import sys
import time

_T0 = time.perf_counter()       # set-up is counted from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path):
    with open(path) as f:
        return json.load(f)


def load_cell(workload, rehearse=False):
    """(bench, cell, config, traffic) for a workload name."""
    bench_dir = os.path.join(HERE, "rehearsal") if rehearse else ROOT
    bench = _load(os.path.join(bench_dir, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = _load(os.path.join(ROOT, cfg_entry["file"]))
    sub = "rehearsal/traffic" if rehearse else "traffic"
    traffic = _load(os.path.join(HERE, sub, cell["traffic"] + ".json"))
    return bench, cell, config, traffic


def cell_metrics(bench, cell, section):
    """Names of the ``section`` metrics this cell reports."""
    return [m["name"] for m in bench[section]
            if "workloads" not in m or cell["name"] in m["workloads"]]


class Context:
    """What a runner is handed.  ``clock()`` is seconds since the
    process started; ``note()`` prints a line (stdout, before the
    result line); ``check()`` records one compared number beside its
    limit."""

    def __init__(self, args, cell, config, traffic, out_dir):
        self.cell, self.config, self.traffic = cell, config, traffic
        self.seed, self.seconds = args.seed, float(args.seconds)
        self.trace = bool(args.trace)
        self.control = args.control
        self.out_dir = out_dir
        self.trace_dir = os.path.join(out_dir, "trace")
        self.checks = []
        self.excluded_s = 0.0       # reference time, not set-up
        self._kept = {}

    @staticmethod
    def clock():
        return time.perf_counter() - _T0

    @staticmethod
    def note(text):
        print(text, flush=True)

    def check(self, name, value, limit, ok=None, detail=""):
        """``value`` must not exceed ``limit`` (or pass ``ok`` yourself)."""
        ok = bool(value <= limit) if ok is None else bool(ok)
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": ok})
        self.note(f"check {'ok  ' if ok else 'FAIL'} {name}: "
                  f"{value!r} (limit {limit!r})"
                  + (f" -- {detail}" if detail else ""))
        return ok

    def keep_steps(self, steps, series, **more):
        """The run's per-step series, written as soon as the window has
        closed (a later failure must not lose it): ``steps.json`` holds
        ``[start_s, end_s, work]`` of every step."""
        self._kept.update(more)     # the last write keeps what earlier ones gave
        with open(os.path.join(self.out_dir, "steps.json"), "w") as f:
            json.dump({"cell": self.cell["name"], "seed": self.seed,
                       "seconds": self.seconds, "steps": steps,
                       "series": series, "checks": self.checks,
                       **self._kept}, f)

    def limit(self, name):
        return self.config["limits"][name]

    def reference(self):
        return importlib.import_module(
            f"chipbench.reference.{self.config['reference']}")

    def traffic_kind(self):
        return importlib.import_module(
            f"chipbench.traffic_kinds.{self.traffic['kind']}")


def _device_record(jax, chips):
    devs = jax.devices()[:chips]
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(jax.devices()), "memory_peak_bytes": max(peaks)}


def run_cell(args):
    """Everything but the exit code: returns the result dict."""
    bench, cell, config, traffic = load_cell(args.workload, args.rehearse)
    import jax

    chips = int(cell["chips"])
    if not args.rehearse:
        from paddle_tpu.framework.device import require_tpu
        try:
            require_tpu()
        except RuntimeError as e:
            print(f"chipbench: {e}", file=sys.stderr)
            return None
        if jax.device_count() < chips:
            print(f"chipbench: cell {cell['name']} needs {chips} chips, JAX "
                  f"found {jax.device_count()}", file=sys.stderr)
            return None
    from paddle_tpu.framework.device import enable_compile_cache
    cache_dir = enable_compile_cache()
    # the small programs of set-up are worth caching too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    out_dir = os.path.join(ROOT, "chipbench_out", cell["name"],
                           f"seed{args.seed}-trace{int(args.trace)}")
    os.makedirs(out_dir, exist_ok=True)
    ctx = Context(args, cell, config, traffic, out_dir)
    ctx.note(f"cell {cell['name']}: config {cell['config']}, traffic "
             f"{cell['traffic']}, seed {args.seed}, {args.seconds} s, "
             f"trace {int(args.trace)}; compile cache {cache_dir}")
    runner = importlib.import_module(f"chipbench.runners.{config['runner']}")
    gc.collect()
    res = runner.run(ctx)

    device = _device_record(jax, chips)
    if "memory_peak_bytes" in res:      # read before the reference ran
        device["memory_peak_bytes"] = res["memory_peak_bytes"]
    values = dict(res["end_to_end"])
    values["setup_s"] = res["window_opened_at"]
    breakdown = None
    if ctx.trace:
        from . import readers
        values, breakdown, traced = readers.read_all(
            ctx, res, cell_metrics(bench, cell, "per_layer"), chips)
        device.update(traced)
        names = list(values)
    else:
        names = cell_metrics(bench, cell, "end_to_end")
    units = {m["name"]: m["unit"]
             for m in bench["end_to_end"] + bench["per_layer"]}
    metrics = {n: {"value": values[n], "unit": units[n]}
               for n in names if values.get(n) is not None}
    ctx.keep_steps(res["steps"], res.get("series", {}),
                   offered=res.get("offered"))
    own = [c for c in ctx.checks if not c["name"].startswith("control.")]
    checks_ok = bool(own) and all(c["ok"] for c in own)
    line = {"correct": checks_ok and not args.rehearse and not args.control,
            "attempted": res["attempted"], "failed": res["failed"],
            "metrics": metrics, "device": device}
    if breakdown:
        line["breakdown"] = breakdown
    line["checks_ok"] = checks_ok
    line["reference_s"] = ctx.excluded_s
    return line


def main(argv=None):
    ap = argparse.ArgumentParser(prog="chipbench.run")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", default="", metavar="PRECISION[,...]",
                    help="also read the numbers of the reference in this "
                         "lower precision, e.g. fp8,int8 (builder's tool; "
                         "the line says correct: false)")
    args = ap.parse_args(argv)
    line = run_cell(args)
    if line is None:
        return 2
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
