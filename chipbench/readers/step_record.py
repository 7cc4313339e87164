"""The train step's flight record (``paddle_tpu.profiler.step_log()``,
docs/PROFILER.md "The step's flight record"), read inside the window: one
record for every call of the step, kept with the trace on or off and after
the runner has freed the step object.  Interval n is ``enter[n+1] -
enter[n]``: call n and the caller's time after it (its wait on the loss,
its next feed).  For the LONGEST interval of the window ``what`` is

``max_ms``          its length, in ms;
``in_call``         ``call_s[n]`` less the window's median ``call_s``: the
                    program's own host work held the step;
``caller_on_cpu``   ``between_cpu_s[n+1]`` less its median: the caller was
                    busy (the feed);
``caller_off_cpu``  ``between_s - between_cpu_s`` of n+1 less its median:
                    the caller slept or stood runnable (the wait on the
                    device, or a host with no CPU to give);
``device_busy``     the first chip's busy time inside ``[span(step n).start,
                    span(step n+1).start]`` (the program's ``train_step``
                    spans, found by ``step``) less the median over the
                    window's steps: near 0 where the device idled through
                    the stall, near the whole excess where a device op held
                    it.

All in ms; on a window without a long step the four excesses read within
+-1 ms of 0, and that IS the reading.  ``max_ms`` also notes one line: the
longest step's number, whether the program flagged it, where its time
went, and where the record holds a snapshot of the threads, the five whose
run-delay and whose on-CPU time grew most since the snapshot before.

The window's records are those whose ``enter`` lies between the window's
opening and the end of its last whole step, both brought onto
``time.perf_counter()`` as ``readers/compile_account.py`` does it.

Returns nothing on a program without ``step_log`` (the parent of PR 49),
and nothing where the window holds no two consecutive calls of one object.
"""

import bisect
import contextlib
import itertools
import time

from .. import stats, trace, xplane_meta

STEP = "train_step"


def program_log():
    """The program's records, or ``None`` where it keeps none."""
    from paddle_tpu import profiler

    log = getattr(profiler, "step_log", None)
    return None if log is None else log()


def in_window(log, lo, hi):
    """The pairs ``(record n, record n+1)`` of one object's consecutive
    calls that both entered inside ``[lo, hi]``: the window's intervals."""
    found = [r for r in log if r["name"] == STEP and lo <= r["enter"] <= hi]
    return [(a, b) for a, b in zip(found, found[1:])
            if b["step"] == a["step"] + 1]


def interval(pair):
    return pair[1]["enter"] - pair[0]["enter"]


def off_cpu(record):
    return record["between_s"] - record["between_cpu_s"]


def reading(pairs, what):
    """``what`` of the window's intervals ``pairs``, in ms."""
    if not pairs:
        return None
    held, after = max(pairs, key=interval)
    if what == "max_ms":
        return 1e3 * interval((held, after))
    if what == "in_call":
        return 1e3 * (held["call_s"]
                      - stats.median([a["call_s"] for a, _ in pairs]))
    if what == "caller_on_cpu":
        return 1e3 * (after["between_cpu_s"] - stats.median(
            [b["between_cpu_s"] for _, b in pairs]))
    if what == "caller_off_cpu":
        return 1e3 * (off_cpu(after)
                      - stats.median([off_cpu(b) for _, b in pairs]))
    raise ValueError(f"step_record: unknown reading {what!r}")


def device_busy(events, spans, step):
    """The device's busy ms inside the interval that step ``step`` began,
    less the median over the intervals ``spans`` (the ``train_step``
    spans of the window, ``(name, start, end, step)``) hold; ``None``
    where the spans do not hold that step and the next."""
    starts = {}
    for name, start, _, number in spans:
        # a ``step`` that did not decode as a whole number lays no span
        with contextlib.suppress(TypeError, ValueError):
            if name == STEP:
                starts[int(number)] = start
    busy = trace.union((s, e) for _, s, e in events)
    ends = [e for _, e in busy]

    def inside(lo, hi):
        total = 0.0
        for s, e in itertools.islice(busy, bisect.bisect_right(ends, lo),
                                     None):
            if s >= hi:
                break
            total += min(e, hi) - max(s, lo)
        return total

    each = {n: inside(at, starts[n + 1]) for n, at in starts.items()
            if n + 1 in starts}
    if step not in each:
        return None
    return 1e3 * (each[step] - stats.median(list(each.values())))


def grown(before, now, n=5):
    """The threads whose run-delay and whose on-CPU time grew most between
    two snapshots: ``"comm(tid) +ms"`` lists."""
    was = {t[0]: t for t in before or ()}
    new = (None, None, 0, 0, 0)         # a thread born since: all it ran
    diffs = [(t[3] - was.get(t[0], new)[3], t[2] - was.get(t[0], new)[2],
              f"{t[1]}({t[0]})") for t in now]
    by_delay = sorted(diffs, reverse=True)[:n]
    by_cpu = sorted(diffs, key=lambda d: -d[1])[:n]
    return ("; ".join(f"{who} +{1e-6 * d:.1f} ms" for d, _, who in by_delay),
            "; ".join(f"{who} +{1e-6 * c:.1f} ms" for _, c, who in by_cpu))


def describe(log, pairs):
    """One line for the run's notes on the window's longest interval."""
    held, after = max(pairs, key=interval)
    spans = [interval(p) for p in pairs]

    def ms(v):
        return "none" if v is None else f"{1e3 * v:.3f} ms"

    text = (
        f"step record: {len(pairs)} intervals in the window, the longest "
        f"{ms(max(spans))} at step {held['step']} (median "
        f"{ms(stats.median(spans))}; "
        f"{'flagged long' if held['long'] else 'not flagged'} by the "
        f"program, {sum(1 for a, _ in pairs if a['long'])} flagged in the "
        f"window): call {ms(held['call_s'])} (operands "
        f"{ms(held['operands_s'])}, dispatch {ms(held['dispatch_s'])}, "
        f"sync {ms(held['sync_s'])}; on CPU {ms(held['call_cpu_s'])}, "
        f"run-delay {ms(held['call_run_delay_s'])}), then the caller "
        f"{ms(after['between_s'])} (on CPU {ms(after['between_cpu_s'])}, "
        f"run-delay {ms(after['between_run_delay_s'])}); all threads' CPU "
        f"{ms(after['process_cpu_s'])}, nivcsw {after['nivcsw']}, majflt "
        f"{after['majflt']}, gc {after['gc']}")
    if held["threads"]:
        earlier = [r["threads"] for r in log
                   if r["threads"] and r["enter"] < held["enter"]]
        by_delay, by_cpu = grown(earlier[-1] if earlier else None,
                                 held["threads"])
        text += (f"; threads since the snapshot before "
                 f"({len(held['threads'])} now), run-delay: {by_delay}; "
                 f"on CPU: {by_cpu}")
    return text


def read(env, what):
    began = time.perf_counter()
    log = program_log()
    if log is None or not env.steps:
        return None
    # perf_counter now, less the context's seconds since the process began
    opened = env.res["window_opened_at"] + (began - env.ctx.clock())
    pairs = in_window(log, opened, opened + env.steps[-1][1])
    if not pairs:
        return None
    if what != "device_busy":
        value = reading(pairs, what)
        if what == "max_ms":
            env.ctx.note(describe(log, pairs) + f"; read in "
                         f"{time.perf_counter() - began:.4f} s")
        return value
    if not env.traced["devices"]:
        return None
    lo, hi = env.traced["window"]
    loaded = xplane_meta.load(trace.find_xplane(env.ctx.trace_dir))
    spans = [sp for sp in xplane_meta.spans(loaded, (STEP,))
             if lo <= sp[1] < hi]
    events = env.traced["devices"][min(env.traced["devices"])]
    value = device_busy(events, spans, max(pairs, key=interval)[0]["step"])
    env.ctx.note(f"step record: the device's busy time in the longest "
                 f"interval less the median {value!r} ms; read in "
                 f"{time.perf_counter() - began:.4f} s")
    return value
