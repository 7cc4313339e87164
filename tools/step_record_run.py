"""One run of a benchmark cell that also keeps the train step's flight
record (docs/PROFILER.md, "The step's flight record").

    python3 tools/step_record_run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The same process ``python3 -m chipbench.run`` is: it calls
``chipbench.run.main`` with the same arguments, which prints the same last
line on stdout, then writes ``paddle_tpu.profiler.step_log()`` as
``step_log.json`` beside the run's ``steps.json``
(``chipbench_out/<cell>/seed<n>-trace<t>/``) and says on stderr one line for
every record the program flagged ``long``.  The hunt for the stalled step:
untraced, ``--seconds 60`` gives six times the steps of a benchmark run for
one set-up (``chipbench/README.step_record.md``).
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402  (starts set-up's clock)


def long_steps(log):
    """One line for each flagged record of ``log``: the record and the
    one after it, which holds the caller's side of the interval."""
    after = {(r["name"], r["step"] - 1): r for r in log}
    for rec in log:
        if rec["long"]:
            nxt = after.get((rec["name"], rec["step"]), {})
            yield ("long step: " + json.dumps(
                {k: v for k, v in rec.items() if k != "threads"})
                + " then " + json.dumps(
                    {k: v for k, v in nxt.items() if k != "threads"}))


def main(argv=None):
    """``chipbench.run.main`` (its arguments, its last line, its exit
    code), then the dump of the record beside the run's ``steps.json``."""
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", type=int, default=0)
    args, _ = ap.parse_known_args(argv)
    rc = bench.main(argv)
    from paddle_tpu import profiler

    log = profiler.step_log()
    out = os.path.join(ROOT, "chipbench_out", str(args.workload),
                       f"seed{args.seed}-trace{args.trace}")
    if rc == 0 and os.path.isdir(out):
        with open(os.path.join(out, "step_log.json"), "w") as f:
            json.dump(log, f)
        for text in long_steps(log):
            print(text, file=sys.stderr)
        print(f"step record: {len(log)} records, "
              f"{sum(1 for r in log if r['long'])} flagged long, in {out}",
              file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
