"""From a profiler trace to busy time, idle share, module time and gaps.

``load(dir)`` reads the newest ``*.xplane.pb`` under a trace directory
with ``jax.profiler.ProfileData`` and flattens it into ``Ev`` records.
On a TPU the device planes are ``/device:TPU:<n>``; their ``XLA Ops``
line holds one event per executed operation and their ``XLA Modules``
line one per executed program (``jit_<function>(<id>)``). Host planes
hold the benchmark's own ``TraceAnnotation`` spans (``bench/...``).
Every reduction below works on a ``[lo, hi)`` window in the trace's
nanosecond clock, which the host spans and device events share.
"""
from __future__ import annotations

import glob
import os
from typing import NamedTuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
SPAN_PREFIX = "bench/"
NAME_CHARS = 160     # an op's event name is its whole HLO instruction


class Ev(NamedTuple):
    plane: str
    line: str
    name: str
    start: float      # ns
    end: float        # ns


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> list:
    """Flatten an ``.xplane.pb`` (or the newest one under a directory)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_xplane(path)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                s = float(e.start_ns)
                out.append(Ev(plane.name, line.name, e.name, s,
                              s + float(e.duration_ns)))
    return out


def device_planes(evs) -> list:
    return sorted({e.plane for e in evs if e.plane.startswith(DEVICE_PREFIX)
                   and e.line == OPS_LINE})


def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def union(intervals) -> list:
    """Merge intervals into disjoint sorted ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def device_busy(evs, plane: str, lo: float, hi: float) -> list:
    """Disjoint intervals in [lo, hi) in which an op ran on ``plane``."""
    return union(_clip(((e.start, e.end) for e in evs
                        if e.plane == plane and e.line == OPS_LINE), lo, hi))


def busy_seconds(evs, lo: float, hi: float) -> float:
    """Busy time in [lo, hi), averaged over the device planes."""
    planes = device_planes(evs)
    if not planes:
        return 0.0
    total = sum(sum(e - s for s, e in device_busy(evs, p, lo, hi))
                for p in planes)
    return total / len(planes) * 1e-9


def span(evs, name: str):
    """(start, end) of the first host span called ``name``, or None."""
    for e in evs:
        if e.name == name and not e.plane.startswith(DEVICE_PREFIX):
            return e.start, e.end
    return None


def idle_share(evs, span_name: str = "bench/window"):
    """Percent of the span in which no device ran an op; None without a
    device plane or without the span."""
    window = span(evs, span_name)
    if window is None or not device_planes(evs):
        return None
    busy = busy_seconds(evs, *window)
    return 100.0 * (1.0 - busy / ((window[1] - window[0]) * 1e-9))


def module_seconds(evs, prefix: str, lo: float, hi: float) -> float:
    """Device time of programs whose name starts with ``prefix``, summed
    over the device planes and divided by their number."""
    planes = device_planes(evs)
    if not planes:
        return 0.0
    tot = sum(e - s for s, e in _clip(
        ((e.start, e.end) for e in evs
         if e.plane in planes and e.line == MODULES_LINE
         and e.name.startswith(prefix)), lo, hi))
    return tot / len(planes) * 1e-9


def top_ops(evs, lo: float, hi: float, n: int = 10) -> list:
    """The ``n`` device operations that took most time, [name, seconds],
    each name cut to its first ``NAME_CHARS`` characters."""
    planes = device_planes(evs)
    acc: dict = {}
    for e in evs:
        if e.plane in planes and e.line == OPS_LINE:
            for s, t in _clip([(e.start, e.end)], lo, hi):
                acc[e.name] = acc.get(e.name, 0.0) + (t - s) * 1e-9
    k = max(len(planes), 1)
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name[:NAME_CHARS], sec / k] for name, sec in ranked]


def idle_gaps(evs, lo: float, hi: float, n: int = 10) -> list:
    """Device idle time in [lo, hi) by what the host was doing.

    Each gap between busy intervals (on the first device) is named after
    the innermost event that covers its midpoint on the host thread that
    carries the ``bench/`` spans; gaps with the same name are summed.
    Returns the ``n`` largest as [name, seconds].
    """
    planes = device_planes(evs)
    if not planes:
        return []
    busy = device_busy(evs, planes[0], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    # the host thread that carries the benchmark's own spans
    threads = {(e.plane, e.line) for e in evs
               if e.name.startswith(SPAN_PREFIX)}
    host = sorted((e for e in evs if (e.plane, e.line) in threads
                   and e.end > e.start and e.end > lo and e.start < hi),
                  key=lambda h: h.start)
    acc: dict = {}
    active, i = [], 0
    # sweep the gaps' midpoints in order, keeping the host events open there
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = 0.5 * (s + e)
        while i < len(host) and host[i].start <= mid:
            active.append(host[i])
            i += 1
        active = [h for h in active if h.end > mid]
        name = (min(active, key=lambda h: h.end - h.start).name
                if active else "(no host event)")
        acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def summarize(evs, per_line: int = 5) -> str:
    """Planes, lines, event counts and a few event names: for reading a
    trace by hand before writing a reduction against it."""
    lines: dict = {}
    for e in evs:
        lines.setdefault((e.plane, e.line), []).append(e)
    out = []
    for (plane, line), es in sorted(lines.items()):
        names = sorted({e.name for e in es})
        out.append(f"{plane} | {line} | {len(es)} events | "
                   f"{len(names)} names: {names[:per_line]}")
    return "\n".join(out)


if __name__ == "__main__":
    import sys
    print(summarize(load(sys.argv[1])))
