"""Device time a step by the program's own names, in ms: the own time
(``trace.self_times``: a container keeps only its overhead) of the first
chip's ops inside the window whose ``op_name`` (the ``tf_op`` stat) the
arguments select, over the window's whole steps.

``scope``  path segments (``["attn"]``), bare or wrapped by a
           transformation (``jvp(attn)``).  An op belongs to ONE part: the
           one that lists the first of its segments that any part lists
           (the parts are the ``scope`` lists of every metric file that
           names this reader), so the parts never overlap.  ``[]`` selects
           the ops no part claims; ``None`` does not look at the scope.
``phase``  a mark anywhere in the name: ``transpose(`` (the backward pass),
           ``rematted_computation`` (the recomputed forward).
``collectives``  HLO collectives (``trace.COLLECTIVE``) are counted only
           when this is true, and then only they: whatever scope the
           compiler left on them, they are never a part, so the parts are
           compute and add up, with the collectives, to the busy time.

Returns nothing where nothing matched (a program without the scope).
"""

import functools
import glob
import json
import os

from .. import trace, xplane_meta

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def claimed_segments(metrics_dir=METRICS):
    """Every segment some part lists, from the metric files."""
    out = set()
    for path in sorted(glob.glob(os.path.join(metrics_dir, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec.get("reader") == "scope_device_ms":
            out.update(spec.get("args", {}).get("scope") or ())
    return out


def own_seconds(ops):
    """{(short name, tf_op): own seconds} of ``(short, start, end, tf_op)``
    events: two programs' ``fusion.16`` stay apart."""
    keyed = [((short, tf_op), s, e) for short, s, e, tf_op in ops]
    return trace.self_times(keyed)


def part_of(tf_op, claimed):
    """The segment that decides an op's part, or ``None``."""
    for seg in xplane_meta.segments(tf_op):
        if seg in claimed:
            return seg
    return None


def selected_seconds(own, claimed, scope=None, phase=None,
                     collectives=False):
    total = 0.0
    for (short, tf_op), seconds in own.items():
        if bool(trace.COLLECTIVE.match(short)) != bool(collectives):
            continue
        if phase is not None and phase not in tf_op:
            continue
        if scope is not None:
            part = part_of(tf_op, claimed)
            if (part not in scope) if scope else (part is not None):
                continue
        total += seconds
    return total


def window_ops(path, lo, hi, chips):
    """The first chip's ops inside ``[lo, hi]``, with their ``tf_op``."""
    ops = xplane_meta.load(path)["ops"]
    mine = [c for c in ops if c < chips]
    if not mine:
        return []
    return [(n, max(s, lo), min(e, hi), tf)
            for n, s, e, tf in ops[min(mine)] if e > lo and s < hi]


@functools.lru_cache(maxsize=2)
def window_own(path, lo, hi, chips):
    """``own_seconds`` of the window: one reduction for all the parts."""
    return own_seconds(window_ops(path, lo, hi, chips))


def read(env, scope=None, phase=None, collectives=False):
    if not env.steps:
        return None
    own = window_own(trace.find_xplane(env.ctx.trace_dir),
                     *env.traced["window"], env.chips)
    seconds = selected_seconds(own, claimed_segments(), scope, phase,
                               collectives)
    return 1e3 * seconds / len(env.steps) if seconds > 0 else None
