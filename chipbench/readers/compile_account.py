"""What the program recorded where its step compiled, read from the
process's compile log (``paddle_tpu.profiler.compile_log()``,
docs/PROFILER.md "The compile's account"): one record for every executable
the process built or loaded, the train step's with the compiler's memory
account attached, and one of kind ``init`` for the trainer's construction.
The records outlive the step object, which the runner has freed before any
reader runs.  ``what`` is

``reserved_gb``      the compiler's reservation for the step's program on
                     ONE chip (argument + output - alias + temp + code), of
                     the first ``train_step`` record, in GB;
``temp_gb``          its temporaries alone: what a rematerialisation trade
                     moves;
``step_compile_s``   trace + lower + backend seconds of that record;
``other_compile_s``  the same sum over every OTHER executable that finished
                     before the window opened (set-up's small programs; a
                     step that compiled again before the window);
``cache_misses``     how many executables finished before the window's
                     opening that the persistent cache did not serve (0 is
                     a reading: a warm run);
``init_s``           the seconds of the ``init`` records before the
                     window's opening.

The window's opening is the runner's ``window_opened_at`` (seconds since
the process started, ``ctx.clock()``); the log's clock is
``time.perf_counter()``, so the two are brought together through the
context's clock (``run.py`` runs as ``__main__``: its ``_T0`` is not
importable).

Returns nothing on a program without a compile log (the parent of PR 34),
and nothing where the log holds no such record.
"""

import time

STEP = "train_step"
PARTS = ("trace_s", "lower_s", "backend_s")


def program_log():
    """The program's records, or ``None`` where it keeps none."""
    from paddle_tpu import profiler

    log = getattr(profiler, "compile_log", None)
    return None if log is None else log()


def compile_seconds(record):
    return sum(record[k] for k in PARTS)


def reading(log, what, opened):
    """``what`` of the records ``log``; ``opened`` is the window's opening
    on the log's clock."""
    built = [r for r in log if r["kind"] == "executable"]
    step = next((r for r in built if r.get("name") == STEP), None)
    before = [r for r in built if r["at"] < opened]
    if what == "init_s":
        inits = [r["seconds"] for r in log
                 if r["kind"] == "init" and r["at"] < opened]
        return sum(inits) if inits else None
    if what == "cache_misses":
        return sum(1 for r in before if r["cache"] == "miss")
    if what == "other_compile_s":
        return sum(compile_seconds(r) for r in before if r is not step)
    if step is None:
        return None
    if what == "step_compile_s":
        return compile_seconds(step)
    if what in ("reserved_gb", "temp_gb"):
        size = step.get(what[:-3] + "_bytes")
        return None if size is None else size / 1e9
    raise ValueError(f"compile_account: unknown reading {what!r}")


def describe(log, opened):
    """One line for the run's notes: what set-up built, what the
    persistent cache made of it, and what the step's account cost."""
    before = [r for r in log if r["kind"] == "executable"
              and r["at"] < opened]
    by_cache = {c: sum(1 for r in before if r["cache"] == c)
                for c in ("hit", "miss", "off")}
    longest = sorted(before, key=compile_seconds, reverse=True)[:4]
    took = [f"step {r['step']}: call {r['call_s']:.3f} s, its account "
            f"{r['account_s']:.4f} s" for r in before
            if r.get("name") == STEP]
    return (f"compile log: {len(before)} executables before the window "
            f"({by_cache['hit']} from the persistent cache, "
            f"{by_cache['miss']} missed it, {by_cache['off']} without it); "
            "longest: " + "; ".join(
                f"{r['program']} {compile_seconds(r):.2f} s "
                f"(trace {r['trace_s']:.2f}, lower {r['lower_s']:.2f}, "
                f"backend {r['backend_s']:.2f}, {r['cache']})"
                for r in longest)
            + "; train_step " + ("; ".join(took) or "took no account"))


def read(env, what):
    log = program_log()
    if log is None:
        return None
    # perf_counter now, less the context's seconds since the process began
    opened = env.res["window_opened_at"] + (
        time.perf_counter() - env.ctx.clock())
    if what == "other_compile_s":
        env.ctx.note(describe(log, opened))
    return reading(log, what, opened)
