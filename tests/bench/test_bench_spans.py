"""The four readers of the serving engine's host spans, on hand-made
events and on a recorded chip trace."""
import gzip
import json
from pathlib import Path

import pytest

from bench import manifest
from bench import spans as sp
from bench import trace as tr

DEV = "/device:TPU:0"
HOST = "/host:CPU"
RECORDED = Path(__file__).parent / "data" / "short_trace_spans.json.gz"
NAMES = ("sched_ms.serve", "idle_sched.serve", "idle_decode.serve",
         "host_syncs.serve")
READERS = {n: manifest.load_module(manifest.BENCH_DIR / "metrics"
                                   / f"{n}.py") for n in NAMES}


def ev(plane, line, name, s, e):
    return tr.Ev(plane, line, name, float(s), float(e))


def host(name, s, e):
    return ev(HOST, "python", name, s, e)


def busy(s, e):
    return ev(DEV, tr.OPS_LINE, "fusion", s, e)


# Two calls in a window of 200 ns. Call 1: price [10, 40) with two
# nested pulls, decode [40, 90) with one; call 2: price [110, 130),
# decode [130, 170) and [170, 190), one pull in each. A price span
# before the window is not read.
EVENTS = [
    host("bench/window", 0, 200),
    host("serve/price", -30, -10),
    host("bench/serve_slot", 5, 95),
    host("serve/price", 10, 40),
    host("serve/pull", 20, 25),
    host("serve/pull", 30, 35),
    host("serve/decode", 40, 90),
    host("serve/pull", 85, 90),
    host("bench/serve_slot", 105, 195),
    host("serve/price", 110, 130),
    host("serve/pull", 125, 130),
    host("serve/decode", 130, 170),
    host("serve/pull", 165, 170),
    host("serve/decode", 170, 190),
    host("serve/pull", 185, 190),
    busy(15, 30),        # price 1: idle [10,15) + [30,40) = 15
    busy(45, 60),
    busy(55, 80),        # decode 1: idle [40,45) + [80,90) = 15
    busy(120, 125),      # price 2: idle [110,120) + [125,130) = 15
    busy(140, 175),      # decodes 2: idle [130,140) + [175,190) = 25
    busy(198, 230),      # past the window's end
]


def read(name, evs):
    return READERS[name].read({"events": evs})


def test_overlap_of_disjoint_lists():
    assert sp.overlap([(0, 10), (20, 30)], [(5, 25)]) == 10
    assert sp.overlap([(0, 10)], [(10, 20)]) == 0
    assert sp.overlap([], [(0, 1)]) == 0


def test_idle_inside_is_exact_intersection():
    assert read("idle_sched.serve", EVENTS) == pytest.approx(
        100 * (15 + 15) / 200)
    assert read("idle_decode.serve", EVENTS) == pytest.approx(
        100 * (15 + 25) / 200)


def test_idle_split_within_idle_share():
    total = tr.idle_share(EVENTS)
    split = (read("idle_sched.serve", EVENTS)
             + read("idle_decode.serve", EVENTS))
    assert split <= total + 1e-9
    # the rest is idle time between calls and outside the two layers
    assert total == pytest.approx(100 * (1 - 92 / 200))


def test_sched_ms_is_mean_price_span_in_window():
    assert read("sched_ms.serve", EVENTS) == pytest.approx(
        (30 + 20) / 2 * 1e-6)


def test_host_syncs_count_nested_pulls_per_call():
    # three pulls in call 1 (two inside price), three in call 2
    assert read("host_syncs.serve", EVENTS) == pytest.approx(6 / 2)


def test_span_past_window_end_is_cut():
    evs = EVENTS + [host("serve/decode", 195, 260)]
    # [195, 198) idle inside the window; the rest lies outside it
    assert read("idle_decode.serve", evs) == pytest.approx(
        100 * (15 + 25 + 3) / 200)


@pytest.mark.parametrize("name", NAMES)
def test_missing_span_reads_none(name):
    # a program without the engine's spans, as the parent of this change
    evs = [e for e in EVENTS if not e.name.startswith("serve/")]
    assert read(name, evs) is None
    assert read(name, [e for e in EVENTS if e.name != "bench/window"]) \
        is None


@pytest.mark.parametrize("name", ["idle_sched.serve", "idle_decode.serve"])
def test_no_device_plane_reads_none(name):
    assert read(name, [e for e in EVENTS if e.plane == HOST]) is None


def test_host_readers_need_no_device():
    evs = [e for e in EVENTS if e.plane == HOST]
    assert read("sched_ms.serve", evs) == read("sched_ms.serve", EVENTS)
    assert read("host_syncs.serve", evs) == 3.0


def test_readers_are_listed_for_both_serve_cells():
    entries = {m["name"]: m for m in manifest.load_manifest()["per_layer"]}
    for name in NAMES:
        m = entries[name]
        assert m["moves"] == "serve_tokens_per_s"
        assert m["source"] == "program_span"
        assert m["workloads"] == ["qwen05b_edge.long", "qwen05b_edge.short"]


def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return [tr.Ev(*row) for row in json.load(f)]


def test_recorded_chip_trace_reads_every_span_metric():
    """A quarter second of a traced ``qwen05b_edge.short`` window on one
    v5e with the engine's spans (cut by ``record_trace.py``)."""
    evs = recorded()
    values = {n: read(n, evs) for n in NAMES}
    assert all(v is not None and v > 0 for v in values.values()), values
    assert (values["idle_sched.serve"] + values["idle_decode.serve"]
            <= tr.idle_share(evs) + 1e-9)
    # six reads in the scheduling phase, one per exit group
    assert 7 <= values["host_syncs.serve"] <= 10
