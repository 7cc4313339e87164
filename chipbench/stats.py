"""The benchmark's arithmetic: how a series of whole steps becomes a
rate, and how a tail is read.  Kept here, under the benchmark's own
path, so that every PR computes each number the same way.

A *step record* is ``(start_s, end_s, work)``: host-clock seconds from
the window's opening, and the work (tokens) the step computed.
"""

import math


def whole_steps(records, seconds):
    """The steps that STARTED inside the window ``[0, seconds)``.  A
    step is never cut: the one running when the clock passes
    ``seconds`` is finished and counted whole, with all of its time."""
    return [r for r in records if 0.0 <= r[0] < seconds]


def rate_over_steps(records):
    """Work of the given whole steps over the time they took, from the
    first one's start to the last one's end -- every gap between steps
    included.  Nothing divides by a nominal window length, so one step
    more or less moves the work and the time together."""
    if not records:
        raise ValueError("no whole step in the window")
    span = records[-1][1] - records[0][0]
    if span <= 0:
        raise ValueError(f"steps span {span} s")
    return sum(r[2] for r in records) / span


def durations(records):
    return [r[1] - r[0] for r in records]


def quantile(values, q):
    """Linear-interpolation quantile (numpy's default), ``0 <= q <= 1``."""
    if not values:
        raise ValueError("quantile of nothing")
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values):
    return quantile(values, 0.5)
