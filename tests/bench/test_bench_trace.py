"""The trace reduction on hand-made events and on a recorded chip trace."""
from pathlib import Path

import pytest

from bench import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "short_trace.json.gz"


def ev(plane, line, name, s, e):
    return tr.Ev(plane, line, name, float(s), float(e))


EVENTS = [
    ev(HOST, "python", "bench/window", 0, 100),
    ev(HOST, "python", "bench/serve_slot", 5, 60),
    ev(HOST, "python", "bench/wait", 60, 100),
    ev(DEV, tr.OPS_LINE, "fusion.1", 10, 20),
    ev(DEV, tr.OPS_LINE, "fusion.2", 15, 30),     # overlaps fusion.1
    ev(DEV, tr.OPS_LINE, "fusion.1", 40, 50),
    ev(DEV, tr.OPS_LINE, "copy.3", 95, 110),      # runs past the window
    ev(DEV, tr.MODULES_LINE, "jit_serve_step(7)", 10, 30),
    ev(DEV, tr.MODULES_LINE, "jit_other(8)", 40, 50),
]


def test_union_merges_overlaps():
    assert tr.union([(3, 5), (0, 2), (1, 4), (7, 8)]) == [(0, 5), (7, 8)]


def test_busy_and_idle():
    w = tr.span(EVENTS, "bench/window")
    assert w == (0.0, 100.0)
    # busy: [10,30) + [40,50) + [95,100) = 35 ns
    assert tr.busy_seconds(EVENTS, *w) == pytest.approx(35e-9)


def test_module_time_by_prefix():
    assert tr.module_seconds(EVENTS, "jit_serve_step", 0, 100) == \
        pytest.approx(20e-9)
    assert tr.module_seconds(EVENTS, "jit_", 0, 100) == pytest.approx(30e-9)


def test_top_ops_sum_per_name_inside_the_window():
    top = dict(tr.top_ops(EVENTS, 0, 100))
    assert top["fusion.1"] == pytest.approx(20e-9)
    assert top["fusion.2"] == pytest.approx(15e-9)
    assert top["copy.3"] == pytest.approx(5e-9)


def test_idle_gaps_named_by_innermost_host_span():
    gaps = dict(tr.idle_gaps(EVENTS, 0, 100))
    # gaps [0,10) and [30,40) have their midpoints inside serve_slot,
    # [50,95) inside wait; the window span covers all three but is wider
    assert gaps["bench/serve_slot"] == pytest.approx((10 + 10) * 1e-9)
    assert gaps["bench/wait"] == pytest.approx(45e-9)
    assert sum(gaps.values()) == pytest.approx(65e-9)


def test_no_device_reads_nothing():
    host_only = [e for e in EVENTS if e.plane == HOST]
    assert tr.busy_seconds(host_only, 0, 100) == 0.0
    assert tr.idle_gaps(host_only, 0, 100) == []
    assert tr.top_ops(host_only, 0, 100) == []


def recorded():
    import gzip
    import json
    with gzip.open(RECORDED, "rt") as f:
        return [tr.Ev(*row) for row in json.load(f)]


def test_recorded_chip_trace_reduces_consistently():
    """A quarter second of a traced ``qwen05b_edge.short`` window on one
    v5e (cut by ``record_trace.py``)."""
    evs = recorded()
    assert tr.device_planes(evs) == ["/device:TPU:0"]
    lo, hi = tr.span(evs, "bench/window")
    window = (hi - lo) * 1e-9
    busy = tr.busy_seconds(evs, lo, hi)
    assert 0 < busy < window
    assert tr.idle_share(evs) == pytest.approx(100 * (1 - busy / window))
    # the decode programs run inside the busy time
    decode = tr.module_seconds(evs, "jit_serve_step", lo, hi)
    assert 0 < decode <= busy * 1.001
    top = tr.top_ops(evs, lo, hi)
    assert 0 < len(top) <= 10
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = tr.idle_gaps(evs, lo, hi, n=1000)
    assert sum(s for _, s in gaps) == pytest.approx(window - busy, rel=1e-6)
    names = dict(gaps)
    assert "bench/wait" in names or "bench/serve_slot" in names
