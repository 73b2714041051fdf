"""Reduction of a profiler trace to device busy time, the operations that
took most time, and the idle gaps labelled by what the host was doing.

A driver records the window with ``begin`` and ``end``; ``end`` returns
the raw trace, ``(ops, spans)``, which the harness reduces here and hands,
raw and reduced, to every per-layer metric's reader.

Device operations are the events of the ``XLA Ops`` line of every
``/device:`` plane.  Host spans are the benchmark's own
``jax.profiler.TraceAnnotation`` events (names starting with ``SPAN``) on
the host plane.  The window is the host span named ``WINDOW``.  An
operation is named by its HLO instruction name (``%fusion.12``); a layer
scan shows as its ``%while`` operation, which contains its body's
operations.
"""
from __future__ import annotations

import collections
import glob
import os
import shutil
from typing import Dict, List, NamedTuple, Sequence, Tuple

SPAN = "chipbench."
WINDOW = SPAN + "window"
OP_LINE = "XLA Ops"
TOP = 10


class Interval(NamedTuple):
    name: str
    start: float   # ns
    end: float     # ns


def start(trace_dir: str) -> None:
    """Starts the profiler into ``trace_dir``: device operations and the
    host's annotations (level 1), without the runtime's own host events or
    a trace of every Python call (they slow the host, bloat the trace and
    are read by nothing here)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def begin(trace_dir: str) -> None:
    """Starts a fresh trace into ``trace_dir``."""
    shutil.rmtree(trace_dir, ignore_errors=True)
    start(trace_dir)


def end(trace_dir: str):
    """Stops the trace; returns ``load``'s raw trace and removes the
    files."""
    import sys
    import time
    import jax
    t0 = time.perf_counter()
    jax.profiler.stop_trace()
    t1 = time.perf_counter()
    raw = load(trace_dir)
    print(f"chipbench: trace stopped in {t1 - t0:.2f}s, read in "
          f"{time.perf_counter() - t1:.2f}s: "
          f"{sum(map(len, raw[0].values()))} device ops, "
          f"{len(raw[1])} spans", file=sys.stderr, flush=True)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return raw


def load(trace_dir: str) -> Tuple[Dict[str, List[Interval]], List[Interval]]:
    """(device plane name -> its operations, host spans) from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops.setdefault(plane.name, []).extend(
                        Interval(e.name, e.start_ns,
                                 e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(Interval(e.name, e.start_ns,
                                      e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN))
    return ops, spans


def union(intervals: Sequence[Interval], lo: float, hi: float):
    """Disjoint (start, end) pairs covering ``intervals`` clipped to
    [lo, hi], in order."""
    out: List[List[float]] = []
    for iv in sorted(intervals, key=lambda i: i.start):
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy, lo: float, hi: float):
    """The complement of the disjoint ``busy`` pairs within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, spans: Sequence[Interval]) -> str:
    """The innermost host span (other than the window) open at the gap's
    midpoint; ``host:other`` where none is."""
    mid = (gap[0] + gap[1]) / 2
    open_ = [sp for sp in spans
             if sp.name != WINDOW and sp.start <= mid <= sp.end]
    if not open_:
        return "host:other"
    return min(open_, key=lambda sp: sp.end - sp.start).name


def reduce(ops: Dict[str, List[Interval]], spans: Sequence[Interval]):
    """Busy and window seconds (busy averaged over the device planes), the
    operations that took most time on the first device, and its idle time
    summed by the host span it fell in.  Returns None without a window or
    without device operations."""
    windows = [s for s in spans if s.name == WINDOW]
    if not windows or not any(ops.values()):
        return None
    lo, hi = windows[0].start, windows[0].end
    planes = sorted(p for p in ops if ops[p])
    busy_by_plane = {p: union(ops[p], lo, hi) for p in planes}
    busy_s = sum(sum(e - s for s, e in b) for b in busy_by_plane.values())
    busy_s /= len(planes) * 1e9
    first = planes[0]
    per_op = collections.Counter()
    for iv in ops[first]:
        s, e = max(iv.start, lo), min(iv.end, hi)
        if e > s:
            per_op[iv.name.split(" = ")[0]] += (e - s) / 1e9
    idle = collections.Counter()
    for g in gaps(busy_by_plane[first], lo, hi):
        idle[label(g, spans)] += (g[1] - g[0]) / 1e9
    return {"busy_s": busy_s, "window_s": (hi - lo) / 1e9,
            "device_ops": [[n, s] for n, s in per_op.most_common(TOP)],
            "idle_gaps": [[n, s] for n, s in idle.most_common(TOP)]}
