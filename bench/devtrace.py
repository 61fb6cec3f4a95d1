"""Reduction of a profiler trace to the device's busy time, its idle
gaps, and the operations that took the most time.

Device planes are those named ``/device:<PLATFORM>:<n>``; their operations
are the events of the ``XLA Ops`` line.  Busy time is the union of those
intervals inside the traced window, averaged over the devices used; the
breakdown sums the innermost operations by their short HLO name.
The window is the span of the benchmark's own ``bench.window`` host
annotation.  An idle gap is named by the host event that overlaps it
most closely: the innermost one that covers at least half of it, leaving
out the benchmark's own annotations.
"""
from __future__ import annotations

import collections
import glob
import os

OPS_LINE = "XLA Ops"
WINDOW = "bench.window"
OWN_PREFIX = "bench."
TOP = 10


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` inside ``[lo, hi]``."""
    return sum(e - s for s, e in merge(clip(intervals, lo, hi)))


def gaps(intervals, lo, hi):
    """The idle intervals of ``[lo, hi]`` not covered by ``intervals``."""
    out, t = [], lo
    for s, e in merge(clip(intervals, lo, hi)):
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def name_gap(gap, host_events):
    """Name of the innermost host event that covers at least half of
    ``gap``, else of the one that overlaps it most (None if none)."""
    length = gap[1] - gap[0]
    best, best_key = None, None
    for name, s, e in host_events:
        overlap = min(e, gap[1]) - max(s, gap[0])
        if overlap <= 0:
            continue
        key = (0, e - s) if overlap >= 0.5 * length else (1, -overlap)
        if best_key is None or key < best_key:
            best, best_key = name, key
    return best


def leaves(ops):
    """The operations that hold no other operation: a ``while`` or a
    ``call`` on the ops line spans the operations of its body."""
    ops = sorted(ops, key=lambda op: (op[1], -op[2]))
    return [op for op, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[1] >= op[2] or nxt[2] > op[2]]


def short_name(name: str) -> str:
    """``%fusion.41 = pred[...] fusion(...)`` -> ``%fusion.41``."""
    return name.split(" = ", 1)[0]


def reduce(device_ops: dict, host_events, lo, hi) -> dict:
    """``device_ops``: {device: [(name, start, end)]} in seconds;
    ``host_events``: [(name, start, end)].  Returns busy and window
    seconds and the breakdown."""
    per_device, by_name = [], collections.Counter()
    merged_all = []
    for ops in device_ops.values():
        ivs = [(s, e) for _, s, e in ops]
        per_device.append(busy(ivs, lo, hi))
        merged_all += ivs
        for name, s, e in leaves(ops):
            s, e = max(s, lo), min(e, hi)
            if e > s:
                by_name[short_name(name)] += e - s
    busy_s = sum(per_device) / len(per_device) if per_device else 0.0
    host = [(n, s, e) for n, s, e in host_events
            if not n.startswith(OWN_PREFIX)]
    idle = sorted(gaps(merged_all, lo, hi), key=lambda g: g[0] - g[1])[:TOP]
    return {
        "busy_s": busy_s, "window_s": hi - lo,
        "breakdown": {
            "device_ops": [[n, t] for n, t in by_name.most_common(TOP)],
            "idle_gaps": [[name_gap(g, host) or "no host event",
                           g[1] - g[0]] for g in idle]},
    }


def read(log_dir: str) -> dict:
    """Reduce the ``.xplane.pb`` that ``jax.profiler`` wrote under
    ``log_dir``; also returns the plane and line names seen."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise RuntimeError(f"no trace under {log_dir}")
    data = ProfileData.from_file(max(files, key=os.path.getmtime))
    device_ops, host_events, seen = {}, [], []
    window = None
    for plane in data.planes:
        lines = list(plane.lines)
        seen.append(f"{plane.name}: {[ln.name for ln in lines]}")
        is_device = plane.name.startswith("/device:") \
            and not plane.name.startswith("/device:CUSTOM")
        for line in lines:
            events = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                      for ev in line.events]
            if is_device:
                if line.name == OPS_LINE:
                    device_ops.setdefault(plane.name, []).extend(events)
            elif plane.name.startswith("/host:"):
                for ev in events:
                    if ev[0] == WINDOW:
                        window = (ev[1], ev[2])
                host_events += events
    if window is None:
        raise RuntimeError("the trace holds no bench.window annotation")
    out = reduce(device_ops, host_events, *window)
    out["planes"] = seen
    return out
