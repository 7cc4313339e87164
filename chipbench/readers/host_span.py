"""The program's own host spans (``profiler.RecordEvent`` writes them into
the profiler's trace, on the device trace's clock), read inside the
window.  ``span`` is a name (``train_step::dispatch``); ``what`` is

``p50_ms``            the median duration of the spans of that name;
``count``             how many there are (0 is a reading: the marker
                      ``train_step::compiled`` should not be there);
``idle_ms_per_step``  the device's idle time charged to that span or one
                      of its children (``<span>::...``), over the window's
                      whole steps.  Every gap of the first chip goes to the
                      innermost span covering its middle, of the program's
                      spans and the benchmark's (``chipbench::``) together.
                      Also notes the longest gap: its length, its span, and
                      the five events of the host's threads that overlap it
                      most -- what the runtime did while the device waited.

Returns nothing where the trace holds no span of the program at all (the
span's family: the name up to its first ``::``).
"""

from .. import stats, trace, xplane_meta


def family(span):
    return span.split("::", 1)[0]


def in_window(spans, name, lo, hi):
    return [sp for sp in spans if sp[0] == name and lo <= sp[1] < hi]


def charged(gaps, span):
    """Idle seconds of ``{span name: seconds}`` that went to ``span`` or a
    child of it."""
    return sum(v for k, v in gaps.items()
               if k == span or k.startswith(span + "::"))


def longest_gap(events, lo, hi):
    """(start, end) of the device's longest idle stretch in ``[lo, hi]``."""
    at, best = lo, (lo, lo)
    for s, e in trace.union((s, e) for _, s, e in trace.clip(events, lo, hi)):
        if s - at > best[1] - best[0]:
            best = (at, s)
        at = max(at, e)
    return (at, hi) if hi - at > best[1] - best[0] else best


def overlapping(threads, lo, hi, n=5):
    """The ``n`` host events that cover most of ``[lo, hi]``:
    [(seconds inside, thread, event name)]."""
    hits = [(min(e, hi) - max(s, lo), line, name)
            for line, events in threads.items()
            for name, s, e, _ in events if e > lo and s < hi]
    return sorted(hits, reverse=True)[:n]


def read(env, span, what):
    loaded = xplane_meta.load(trace.find_xplane(env.ctx.trace_dir))
    lo, hi = env.traced["window"]
    own = xplane_meta.spans(loaded, (family(span),))
    if not own:
        return None
    if what == "count":
        return len(in_window(own, span, lo, hi))
    if what == "p50_ms":
        found = in_window(own, span, lo, hi)
        return 1e3 * stats.median([e - s for _, s, e, _ in found]) \
            if found else None
    if what != "idle_ms_per_step":
        raise ValueError(f"host_span: unknown reading {what!r}")
    if not env.steps or not env.traced["devices"]:
        return None
    events = env.traced["devices"][min(env.traced["devices"])]
    every = xplane_meta.spans(loaded, (family(span), trace.SPAN_PREFIX))
    gaps = trace.idle_gaps(events, every, lo, hi)
    g0, g1 = longest_gap(events, lo, hi)
    inner = trace.idle_gaps([("busy", lo, g0), ("busy", g1, hi)], every,
                            lo, hi)
    env.ctx.note(
        f"longest idle gap: {1e3 * (g1 - g0):.3f} ms at "
        f"{g0 - lo:.3f} s into the window, inside "
        f"{next(iter(inner), 'no span')}; host events over it: "
        + "; ".join(f"{line} {name} {1e3 * sec:.3f} ms"
                    for sec, line, name in
                    overlapping(loaded["threads"], g0, g1)))
    return 1e3 * charged(gaps, span) / len(env.steps)
