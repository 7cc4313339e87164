"""One run of a benchmark cell with ANOTHER choice of what ``remat=True``
keeps of a rematerialised block (``distributed/fleet/recompute.py
KEPT_BY_BLOCK``).

    python3 tools/remat_kept_run.py --keep <tag,tag,...> --workload <name> --seed <n> --seconds <s> --trace <0|1>

The same process ``python3 -m chipbench.run`` is: it sets the constant to
the tags given (``--keep ""`` keeps nothing: ``"full"``), then calls
``chipbench.run.main`` with the other arguments, which prints the same last
line on stdout.  The builder's tool for deciding the constant ONCE, on the
chip, from a few compiled steps of the cell that says ``"remat": true``
(PERF.md section 6, PR 52, has the readings that decided it); no program
searches at run time, which would pay every candidate's compile in every
set-up.  On stderr: the tags the step was built with and its account's
bytes (``compile_account()["remat_kept"]``, ``reserved_bytes``).
"""

import argparse
import importlib
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import run as bench  # noqa: E402  (starts set-up's clock)


def main(argv=None):
    ap = argparse.ArgumentParser(add_help=False)
    ap.add_argument("--keep", required=True)
    args, rest = ap.parse_known_args(argv)
    from paddle_tpu import profiler

    # by its path: the package's attribute of that name is the function
    recompute = importlib.import_module(
        "paddle_tpu.distributed.fleet.recompute")
    recompute.KEPT_BY_BLOCK = tuple(t for t in args.keep.split(",") if t)
    rc = bench.main(rest)
    for rec in profiler.compile_log():
        if rec.get("name") == "train_step" and "remat_kept" in rec:
            print(f"remat_kept {rec['remat_kept']} reserved_bytes "
                  f"{rec.get('reserved_bytes')} temp_bytes "
                  f"{rec.get('temp_bytes')} collectives "
                  f"{rec.get('collectives_async')} async / "
                  f"{rec.get('collectives_sync')} sync",
                  file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
