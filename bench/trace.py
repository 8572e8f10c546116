"""Reduction of a profiler trace to device busy time, kernel time, device
time by named scope, the host spans and idle gaps attributed to what the
host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
planes' op line (``XLA Ops``: one event per device operation, the Pallas
kernels among them), the name-scope path each op carries (its ``tf_op``
stat: the ``jax.named_scope`` stack of the code that made it), and the host
spans whose names start with ``bench.`` (the benchmark's own
``jax.profiler.TraceAnnotation``) or with a prefix the cell's runner
declares in its ``SPANS``. The rest of the host plane (the Python tracer,
the runtime's events) is left out. Times are in seconds on the profiler's
common clock. The reduction itself works on plain (name, start, end)
tuples, a device op's with its scope path as a fourth element, so it is
checked on small hand-made traces.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NO_SPAN = "(no host span)"
NO_SCOPE = "(no scope)"
#: the device op's stat that holds its name-scope path and primitive, as
#: ``jit(f)/outer/inner/primitive:``
SCOPE_STAT = "tf_op"


def load(trace_dir, prefixes=()):
    """(device ops per device plane, host spans) of the newest trace under
    ``trace_dir``: ``{plane: [(name, start_s, end_s, scope)]}``, where
    ``scope`` is the op's own named-scope path (``scope_path``), and
    ``[(name, start_s, end_s)]`` of the ``bench.`` spans and those starting
    with any of ``prefixes``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    keep = (SPAN_PREFIX,) + tuple(prefixes)
    data = ProfileData.from_file(files[-1])
    with open(files[-1], "rb") as f:
        tf_ops = op_tf_ops(f.read())
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    events = list(line.events)
                    meta = tf_ops.get(plane.name, [])
                    if [n for n, _ in meta] != [e.name for e in events]:
                        raise ValueError(f"{plane.name}: the op metadata "
                                         "read does not match the events")
                    ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, scope_path(t))
                        for e, (_, t) in zip(events, meta)]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events if e.name.startswith(keep)]
    return ops, spans


def scope_path(tf_op):
    """The named scopes of a ``tf_op`` stat, outermost first, without the
    primitive: ``jit(f)/a/b/dot_general:`` -> ``("jit(f)", "a", "b")``. A
    fused op may carry several op names joined by ``;``: the union of
    their scopes, each once."""
    out = []
    for op_name in tf_op.rsplit(":", 1)[0].split(";"):
        for p in op_name.split("/")[:-1]:
            if p and p not in out:
                out.append(p)
    return tuple(out)


# -- the XSpace protobuf, as far as the scopes need it ----------------------------
#
# ``ProfileData`` gives an event's own stats (its offsets) but not its
# metadata's, where the scope path is. The few fields read here follow
# tsl/profiler/protobuf/xplane.proto: XSpace.planes = 1; XPlane.name = 2,
# lines = 3, event_metadata = 4 (map<int64, XEventMetadata>),
# stat_metadata = 5 (map<int64, XStatMetadata>); XLine.name = 2, events =
# 4; XEvent.metadata_id = 1; XEventMetadata.id = 1, name = 2, stats = 5;
# XStatMetadata.id = 1, name = 2; XStat.metadata_id = 1, str_value = 5,
# ref_value = 7 (the id of a stat metadata whose name is the string).

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo=0, hi=None):
    """(field number, value) of each field of the message in buf[lo:hi]; a
    length-delimited value is its (start, end) in ``buf``."""
    hi = len(buf) if hi is None else hi
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire in (1, 5):
            n = 8 if wire == 1 else 4
            v, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield key >> 3, v


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_values(buf, entries):
    """The value (field 2) of each map entry in ``entries``."""
    for lo, hi in entries:
        for f, v in _fields(buf, lo, hi):
            if f == 2:
                yield v


def op_tf_ops(raw):
    """``{device plane: [(op name, tf_op), ...]}`` of a serialized XSpace:
    for each event on a device plane's ``XLA Ops`` line, in the line's
    order, the name and ``tf_op`` stat ("" without one) of the event
    metadata the event points to. Two programs may hold ops of one name
    (the same HLO line); each event keeps its own metadata's scope."""
    buf = memoryview(raw)
    out = {}
    for f, plane in _fields(buf):
        if f != 1:
            continue
        name, lines, events, stats = "", [], [], []
        for g, v in _fields(buf, *plane):
            if g == 2:
                name = _text(buf, v)
            elif g == 3:
                lines.append(v)
            elif g == 4:
                events.append(v)
            elif g == 5:
                stats.append(v)
        if not name.startswith(DEVICE_PREFIX):
            continue
        stat_names = {}
        for lo, hi in _map_values(buf, stats):
            d = dict(_fields(buf, lo, hi))
            stat_names[d.get(1, 0)] = _text(buf, d[2]) if 2 in d else ""
        want = {i for i, n in stat_names.items() if n == SCOPE_STAT}
        meta = {}
        for lo, hi in _map_values(buf, events):
            mid, op, tf_op = 0, "", ""
            for g, v in _fields(buf, lo, hi):
                if g == 1:
                    mid = v
                elif g == 2:
                    op = _text(buf, v)
                elif g == 5:
                    s = dict(_fields(buf, *v))
                    if s.get(1) in want:
                        tf_op = _text(buf, s[5]) if 5 in s else \
                            stat_names.get(s.get(7), "")
            meta[mid] = (op, tf_op)
        for line in lines:
            fields = list(_fields(buf, *line))
            if any(g == 2 and _text(buf, v) == OPS_LINE for g, v in fields):
                out[name] = [
                    meta.get(dict(_fields(buf, *v)).get(1, 0), ("", ""))
                    for g, v in fields if g == 4]
    return out


# -- the reduction ---------------------------------------------------------------

def merge(intervals):
    """Union of (start, end) intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(events, lo, hi):
    """Events cut to the window [lo, hi]; those outside it dropped."""
    return [(n, max(s, lo), min(e, hi), *rest) for n, s, e, *rest in events
            if e > lo and s < hi]


def busy_seconds(events):
    return sum(e - s for s, e in merge((s, e) for _, s, e, *_ in events))


def time_by_name(events):
    """Summed duration per op name, longest first."""
    acc = collections.defaultdict(float)
    for n, s, e, *_ in events:
        acc[n] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def time_by_scope(events):
    """Summed duration per scope-path component, longest first: an op
    ``(name, start, end, scope)`` counts once under each named scope it
    sits in (nested scopes both count), and under ``NO_SCOPE`` when it
    carries none."""
    acc = collections.defaultdict(float)
    for _, s, e, *scope in events:
        for c in (scope and scope[0]) or (NO_SCOPE,):
            acc[c] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def idle_gaps(events, spans, lo, hi, min_gap=0.0):
    """Device idle time in [lo, hi], by the host span that was open in the
    middle of each gap (the innermost, i.e. latest-starting, one), longest
    total first. Gaps shorter than ``min_gap`` seconds are left out."""
    busy = merge((s, e) for _, s, e, *_ in clip(events, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    order = sorted((s, n, e) for n, s, e in spans)
    acc = collections.defaultdict(float)
    heap, k = [], 0      # spans started by the gap's middle, latest on top
    for s, e in zip(edges[0::2], edges[1::2]):
        if e - s <= min_gap:
            continue
        mid = 0.5 * (s + e)
        while k < len(order) and order[k][0] <= mid:
            heapq.heappush(heap, (-k, order[k][2], order[k][1]))
            k += 1
        while heap and heap[0][1] <= mid:     # ended: ended for later gaps too
            heapq.heappop(heap)
        acc[heap[0][2] if heap else NO_SPAN] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def short_name(op):
    """A device op's HLO text cut to its instruction name without numeric
    suffixes, and its kind (the custom call's target for a custom call):
    ``%copy.245 = f32[...] copy(...)`` -> ``copy (copy)``."""
    m = re.match(r"%([\w.\-]+) = .*? ([\w\-]+)\(", op)
    if not m:
        return op[:80]
    target = re.search(r'custom_call_target="([^"]+)"', op)
    name = re.sub(r"\.\d+", "", m.group(1))
    return f"{name} ({target.group(1) if target else m.group(2)})"


def grouped(op_seconds):
    """Summed seconds per ``short_name``, longest first."""
    acc = collections.defaultdict(float)
    for n, sec in op_seconds.items():
        acc[short_name(n)] += sec
    return sorted(acc.items(), key=lambda kv: -kv[1])


def reduce(ops_by_plane, spans, lo, hi):
    """Busy seconds (averaged over devices), the traced window's length,
    summed time per op name and per named scope (over devices), the host
    spans and the idle gaps by host span, all within [lo, hi]."""
    planes = list(ops_by_plane.values())
    if not planes:
        raise ValueError("the trace holds no device op line")
    clipped = [clip(evs, lo, hi) for evs in planes]
    busy = sum(busy_seconds(c) for c in clipped) / len(clipped)
    allops = [ev for c in clipped for ev in c]
    return {
        "busy_s": busy,
        "window_s": hi - lo,
        "devices": len(planes),
        "op_seconds": dict(time_by_name(allops)),
        "scope_seconds": dict(time_by_scope(allops)),
        "spans": sorted(clip(spans, lo, hi), key=lambda sp: sp[1]),
        "idle_gaps": idle_gaps(clipped[0], spans, lo, hi),
    }
