"""Reduction of a profiler trace (``.xplane.pb``) to numbers.

Everything works on plain ``(name, start_s, end_s)`` tuples, so the
arithmetic is tested on made-up intervals and the loader on a small
recorded trace (``chipbench/tests/data``).  Read with nothing but JAX
(``jax.profiler.ProfileData``).

A TPU device plane is named ``/device:TPU:<n>``.  Its ``XLA Ops`` line
holds one event per executed HLO op; container ops (``while``, ``call``,
``conditional``) span their children, so busy time is the UNION of the
intervals and an op's own time is its span minus its children's.
"""

import bisect
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"    # start..done spans of asynchronous ops
SPAN_PREFIX = "chipbench::"
# HLO collectives as they are named in a trace (async pairs included)
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute|collective-broadcast)(-start|-done)?([.\d]*)( |$)")


def short_name(name):
    """An op event is named by its whole HLO instruction
    (``%fusion.4 = bf16[128,64]{1,0:T(8,128)} fusion(...)``): keep the
    instruction's name and its result's type and shape."""
    if " = " not in name:
        return name
    head, rest = name.split(" = ", 1)
    m = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return head.lstrip("%") + (" " + m.group(1) if m else "")


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def load(path):
    """{"devices": {chip: [(name, start_s, end_s)]}, "async": {chip:
    [...]}, "spans": [...]}: the op events of every TPU plane (and the
    spans of its asynchronous ops, collectives among them) and the
    benchmark's own host spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices, asyncs, spans = {}, {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, ASYNC_LINE):
                    into = devices if line.name == OPS_LINE else asyncs
                    into[int(m.group(1))] = [
                        (short_name(e.name), e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns * 1e-9,
                     (e.start_ns + e.duration_ns) * 1e-9)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    spans.sort(key=lambda s: s[1])
    return {"devices": devices, "async": asyncs, "spans": spans}


def clip(events, lo, hi):
    """The parts of ``events`` inside ``[lo, hi]``."""
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def union(intervals):
    """Merged, sorted ``[(start, end)]`` of possibly nested intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(events):
    return sum(e - s for s, e in union((s, e) for _, s, e in events))


def self_times(events):
    """{name: seconds} of each op's OWN time: its span minus the spans of
    the events nested inside it (containers such as ``while`` keep only
    their overhead)."""
    out = {}
    stack = []          # [name, end, child_seconds, start]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, child, start = stack.pop()
            out[name] = out.get(name, 0.0) + (end - start) - child
            if stack:
                stack[-1][2] += end - start
    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return out


def top(table, n=10):
    return [[k, v] for k, v in
            sorted(table.items(), key=lambda kv: -kv[1])[:n]]


def kernel_seconds(events, pattern):
    """Own time of the ops whose name contains ``pattern``."""
    return sum(v for k, v in self_times(events).items() if pattern in k)


def idle_gaps(events, spans, lo, hi):
    """{span name: idle seconds}: every gap of the device inside
    ``[lo, hi]`` is charged to the benchmark's host span that covers its
    middle (the innermost one), or to ``unattributed``."""
    busy = union((s, e) for _, s, e in clip(events, lo, hi))
    gaps, at = [], lo
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if hi > at:
        gaps.append((at, hi))
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [sp[1] for sp in spans]
    out = {}
    for s, e in gaps:
        mid = 0.5 * (s + e)
        name = "unattributed"
        # of properly nested spans the latest-starting one that covers
        # the point is the innermost
        for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
            if spans[i][2] > mid:
                name = spans[i][0]
                break
        out[name] = out.get(name, 0.0) + (e - s)
    return out


def exposed_collective_seconds(events, async_events=()):
    """Seconds in which a collective ran on this device and no other op
    did: collective intervals (synchronous ops and the start..done spans
    of asynchronous ones) minus the union of everything else."""
    leaf = [(n, s, e) for n, s, e in events if not _is_container(n)]
    coll = union((s, e) for n, s, e in leaf + list(async_events)
                 if COLLECTIVE.match(n))
    other = union((s, e) for n, s, e in leaf if not COLLECTIVE.match(n))
    exposed, j = 0.0, 0
    for s, e in coll:
        at = s
        while j < len(other) and other[j][1] <= s:
            j += 1
        k = j
        while k < len(other) and other[k][0] < e:
            if other[k][0] > at:
                exposed += other[k][0] - at
            at = max(at, other[k][1])
            k += 1
        if e > at:
            exposed += e - at
    return exposed


def _is_container(name):
    return bool(re.match(r"^(while|call|conditional)([.\d]*)( |$)", name))


def window_of(spans, name=SPAN_PREFIX + "window"):
    """(start, end) of the benchmark's window span in the trace's clock."""
    for n, s, e in spans:
        if n == name:
            return s, e
    raise ValueError(f"trace holds no {name!r} span")
