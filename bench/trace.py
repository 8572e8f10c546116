"""Reduction of a profiler trace to device busy time, kernel time and idle
gaps attributed to what the host was doing.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` writes: the device
planes' op line (``XLA Ops``: one event per device operation, the Pallas
kernels among them) and the host spans the benchmark records with
``jax.profiler.TraceAnnotation`` (names starting ``bench.``). Times are in
seconds on the profiler's common clock. The reduction itself works on plain
(name, start, end) tuples, so it is checked on small hand-made traces.
"""
from __future__ import annotations

import collections
import glob
import os
import re

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
NO_SPAN = "(no host span)"


def load(trace_dir):
    """(device ops per device plane, host spans) of the newest trace under
    ``trace_dir``: ``{plane: [(name, start_s, end_s)]}``, ``[(name, start_s,
    end_s)]``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[plane.name] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                spans += [(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return ops, spans


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
    return [(n, max(s, lo), min(e, hi)) for n, s, e in events
            if e > lo and s < hi]


def busy_seconds(events):
    return sum(e - s for s, e in merge((s, e) for _, s, e in events))


def time_by_name(events):
    """Summed duration per op name, longest first."""
    acc = collections.defaultdict(float)
    for n, s, e in events:
        acc[n] += e - s
    return sorted(acc.items(), key=lambda kv: -kv[1])


def idle_gaps(events, spans, lo, hi, min_gap=0.0):
    """Device idle time in [lo, hi], by the host span that was open in the
    middle of each gap (the innermost, i.e. latest-starting, one), longest
    total first. Gaps shorter than ``min_gap`` seconds are left out."""
    busy = merge((s, e) for _, s, e in clip(events, lo, hi))
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    acc = collections.defaultdict(float)
    for s, e in zip(edges[0::2], edges[1::2]):
        if e - s <= min_gap:
            continue
        mid = 0.5 * (s + e)
        open_ = [(ss, n) for n, ss, ee in spans if ss <= mid < ee]
        acc[max(open_)[1] if open_ else NO_SPAN] += e - s
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
    summed time per op name and idle gaps by host span, all within
    [lo, hi]."""
    planes = list(ops_by_plane.values())
    if not planes:
        raise ValueError("the trace holds no device op line")
    clipped = [clip(evs, lo, hi) for evs in planes]
    busy = sum(busy_seconds(c) for c in clipped) / len(clipped)
    allops = [ev for c in clipped for ev in c]
    return {
        "busy_s": busy,
        "window_s": hi - lo,
        "op_seconds": dict(time_by_name(allops)),
        "idle_gaps": idle_gaps(clipped[0], spans, lo, hi),
    }
