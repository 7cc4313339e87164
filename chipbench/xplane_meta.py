"""What ``jax.profiler.ProfileData`` does not hand out of an
``.xplane.pb``: the metadata of each device op (the ``tf_op`` stat holds
the JAX ``op_name`` with the program's ``jax.named_scope`` path, ``source``
the file and line that issued it, ``hlo_category`` the compiler's class)
and the host spans of any prefix, every host thread's events beside them.

The file is a protobuf (``tsl/profiler/protobuf/xplane.proto``); the few
message types are walked by hand below, with nothing but the wire format:

    XSpace         1 planes*
    XPlane         2 name  3 lines*  4 event_metadata{id: XEventMetadata}
                   5 stat_metadata{id: XStatMetadata}
    XLine          2 name  3 timestamp_ns  4 events*
    XEvent         1 metadata_id  2 offset_ps  3 duration_ps  4 stats*
    XEventMetadata 1 id  2 name  5 stats*
    XStatMetadata  1 id  2 name
    XStat          1 metadata_id  2 double  3 uint64  4 int64  5 str
                   7 ref (the id of a stat_metadata whose NAME is the string)

An op is identified by its event, not by its instruction's name: names
repeat across programs (``fusion.16`` is in ``jit__threefry_split`` and in
the step), so ops are handed out as ``(short name, start_s, end_s, tf_op)``
on the clock ``chipbench.trace.load`` uses.
"""

import functools
import struct

from . import trace

OP_STATS = ("tf_op", "source", "hlo_category")
HOST_PLANE = "/host:CPU"


def _fields(buf, lo, hi):
    """(field number, wire type, value) of one message: a varint as an int,
    a length-delimited field as its ``(lo, hi)`` inside ``buf``, a fixed
    one as its bytes."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield number, wire, value


def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _text(buf, span):
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v):
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(buf, spans, stat_names):
    """{stat name: value} of XStat messages."""
    out = {}
    for span in spans:
        name = value = None
        for number, _, v in _fields(buf, *span):
            if number == 1:
                name = stat_names.get(v)
            elif number == 2:
                value = struct.unpack("<d", v)[0]
            elif number == 3:
                value = v
            elif number == 4:
                value = _signed(v)
            elif number == 5:
                value = _text(buf, v)
            elif number == 7:
                value = stat_names.get(v, "")
        if name is not None:
            out[name] = value
    return out


def _map_entries(buf, spans):
    """The value message's span of each map entry."""
    for span in spans:
        for number, _, v in _fields(buf, *span):
            if number == 2:
                yield v


def _plane(buf, span):
    """{"name", "lines": [spans], "events": {id: (name, stats)}}."""
    name, lines, event_md, stat_md = "", [], [], []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            lines.append(v)
        elif number == 4:
            event_md.append(v)
        elif number == 5:
            stat_md.append(v)
    stat_names = {}
    for md in _map_entries(buf, stat_md):
        sid, sname = 0, ""
        for number, _, v in _fields(buf, *md):
            if number == 1:
                sid = v
            elif number == 2:
                sname = _text(buf, v)
        stat_names[sid] = sname
    events = {}
    for md in _map_entries(buf, event_md):
        eid, ename, stats = 0, "", []
        for number, _, v in _fields(buf, *md):
            if number == 1:
                eid = v
            elif number == 2:
                ename = _text(buf, v)
            elif number == 5:
                stats.append(v)
        events[eid] = (ename, _stats(buf, stats, stat_names))
    return {"name": name, "lines": lines, "events": events,
            "stat_names": stat_names}


def _line(buf, span, stat_names, with_stats, only=None):
    """(line name, [(metadata id, start_s, end_s, stats)]); the events of
    a line not named ``only`` are left undecoded."""
    name, t0_ns, events = "", 0, []
    for number, _, v in _fields(buf, *span):
        if number == 2:
            name = _text(buf, v)
        elif number == 3:
            t0_ns = v
        elif number == 4:
            events.append(v)
    out = []
    if only is not None and name != only:
        return name, out
    for ev in events:
        mid = offset_ps = duration_ps = 0
        stats = []
        for number, _, v in _fields(buf, *ev):
            if number == 1:
                mid = v
            elif number == 2:
                offset_ps = v
            elif number == 3:
                duration_ps = v
            elif number == 4 and with_stats:
                stats.append(v)
        start = (t0_ns + offset_ps * 1e-3) * 1e-9
        out.append((mid, start, start + duration_ps * 1e-12,
                    _stats(buf, stats, stat_names) if stats else {}))
    return name, out


def _op_name(tf_op):
    """The ``tf_op`` stat is ``<op_name>:<op type>``; the type is empty for
    a JAX program."""
    return tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op


@functools.lru_cache(maxsize=2)
def load(path):
    """{"ops": {chip: [(short name, start_s, end_s, tf_op)]},
        "meta": {chip: {short name: {"tf_op", "source", "hlo_category"}}},
        "threads": {host line: [(name, start_s, end_s, step)]}}

    ``ops`` are the events of each TPU plane's ``XLA Ops`` line; ``meta``
    the same metadata by instruction name (the last program to use a name
    wins: use ``ops`` to tell programs apart); ``threads`` every event of
    the host plane, by thread, with the ``step`` a span was given
    (``RecordEvent(..., step=n)``) or ``None``."""
    with open(path, "rb") as f:
        buf = f.read()
    ops, meta, threads = {}, {}, {}
    for number, _, span in _fields(buf, 0, len(buf)):
        if number != 1:
            continue
        plane = _plane(buf, span)
        m = trace.DEVICE_PLANE.match(plane["name"])
        if m:
            chip = int(m.group(1))
            table = {mid: (trace.short_name(name),
                           {**{k: stats.get(k, "") for k in OP_STATS},
                            "tf_op": _op_name(stats.get("tf_op", ""))})
                     for mid, (name, stats) in plane["events"].items()}
            meta[chip] = dict(table.values())
            for line in plane["lines"]:
                name, events = _line(buf, line, plane["stat_names"], False,
                                     only=trace.OPS_LINE)
                if name == trace.OPS_LINE:
                    ops[chip] = [(table[mid][0], s, e, table[mid][1]["tf_op"])
                                 for mid, s, e, _ in events if mid in table]
        elif plane["name"] == HOST_PLANE:
            for line in plane["lines"]:
                name, events = _line(buf, line, plane["stat_names"], True)
                threads[name] = [
                    (plane["events"].get(mid, ("", {}))[0], s, e,
                     stats.get("step"))
                    for mid, s, e, stats in events]
    return {"ops": ops, "meta": meta, "threads": threads}


def spans(loaded, prefixes):
    """[(name, start_s, end_s, step)] of the host events named under any
    of ``prefixes``, by start."""
    prefixes = tuple(prefixes)
    out = [ev for events in loaded["threads"].values() for ev in events
           if ev[0].startswith(prefixes)]
    return sorted(out, key=lambda ev: ev[1])


def segments(tf_op):
    """The path segments of an ``op_name`` with the transformations'
    wrappers taken off, for every op a fusion joined with ``;``:
    ``jit(step)/transpose(jvp(h))/checkpoint/mlp/dot_general`` ->
    ``["step", "h", "checkpoint", "mlp", "dot_general"]``."""
    out = []
    for name in tf_op.split(";"):
        for seg in name.strip().split("/"):
            while seg.endswith(")") and "(" in seg:
                seg = seg[seg.index("(") + 1:-1]
            out.append(seg)
    return out
