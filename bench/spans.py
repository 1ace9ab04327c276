"""The serving engine's host spans against the device's busy time.

The sync engine marks each layer of a call with a host span on the
profiler's clock: ``serve/price`` (scheduling), ``serve/decode`` (one
exit group) and ``serve/pull`` (one blocking device->host read). The
reductions below read them inside the ``bench/window`` span, with
``bench/trace.py``'s ``span``, ``union`` and ``device_busy``.
"""
from __future__ import annotations

from bench import trace as tr

WINDOW = "bench/window"


def host_spans(evs, name: str, lo: float, hi: float) -> list:
    """(start, end) of every host span called ``name`` that starts in
    [lo, hi), in start order, uncut."""
    return sorted((e.start, e.end) for e in evs
                  if e.name == name and lo <= e.start < hi
                  and not e.plane.startswith(tr.DEVICE_PREFIX))


def overlap(a, b) -> float:
    """Length of the intersection of two disjoint sorted interval lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_inside(evs, name: str):
    """Percent of the window in which the host is inside a ``name`` span
    and no op runs on the device, averaged over the device planes as
    ``trace.idle_share`` averages busy time; None without the window, a
    device plane or the span."""
    window = tr.span(evs, WINDOW)
    planes = tr.device_planes(evs)
    if window is None or not planes:
        return None
    lo, hi = window
    spans = host_spans(evs, name, lo, hi)
    if not spans:
        return None
    inside = tr.union((s, min(e, hi)) for s, e in spans)
    length = sum(e - s for s, e in inside)
    idle = sum(length - overlap(inside, tr.device_busy(evs, p, lo, hi))
               for p in planes) / len(planes)
    return 100.0 * idle / (hi - lo)
