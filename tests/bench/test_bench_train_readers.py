"""The training cell's three trace readers on hand-made events."""
import pytest

from bench import manifest
from bench import trace as tr
from bench.shapes import grle

DEV = "/device:TPU:0"
HOST = "/host:CPU"
NAMES = ("idle_share.train", "mfu.train", "gcn_roofline.train")
READERS = {n: manifest.load_module(manifest.BENCH_DIR / "metrics"
                                   / f"{n}.py") for n in NAMES}
PEAKS = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
MIX = {"fleets": 64, "episode_slots": 200}


def ev(plane, line, name, s, e):
    return tr.Ev(plane, line, name, float(s), float(e))


def op(name, s, e):
    return ev(DEV, tr.OPS_LINE, name, s, e)


# A window of 3 s holding two episodes: 2.4 s of ops, of which 0.3 s in
# ops named after the kernels; one kernel op runs past the window's end.
EVENTS = [
    ev(HOST, "python", "bench/window", 0, 3e9),
    ev(HOST, "python", "bench/episode", 0, 1.5e9),
    ev(HOST, "python", "bench/episode", 1.5e9, 3e9),
    op("fusion.12", 0, 1.0e9),
    op("gcn_agg.3", 1.0e9, 1.1e9),
    op("edge_score.1", 1.1e9, 1.2e9),
    op("while.7", 1.5e9, 2.6e9),
    op("gcn_agg.8", 2.9e9, 3.1e9),
]


def ctx(events=EVENTS, episodes=2):
    cfg = manifest.load_json(manifest.BENCH_DIR / "configs"
                             / "grle_paper.json")
    return dict(events=events, episodes=episodes, config=cfg, traffic=MIX,
                peaks=PEAKS)


def read(name, **kw):
    return READERS[name].read(ctx(**kw))


def test_idle_share_is_the_window_without_ops():
    assert read("idle_share.train") == pytest.approx(100 * (1 - 2.4 / 3))


def test_mfu_counts_the_traced_episodes_over_the_window():
    flops = 2 * grle.episode_flops(ctx()["config"], 64, 200)
    assert read("mfu.train") == pytest.approx(100 * flops / (3 * 197e12))


def test_roofline_over_the_kernels_device_time_in_the_window():
    least = sum(max(f / 197e12, b / 819e9) for _, f, b in
                grle.episode_kernel_calls(ctx()["config"], 64, 200))
    assert read("gcn_roofline.train") == pytest.approx(
        100 * 2 * least / 0.3)


def test_roofline_reads_none_without_kernel_ops():
    evs = [e for e in EVENTS if not e.name.startswith(("gcn", "edge"))]
    assert read("gcn_roofline.train", events=evs) is None


@pytest.mark.parametrize("name", NAMES)
def test_reads_none_without_the_window(name):
    evs = [e for e in EVENTS if e.name != "bench/window"]
    assert read(name, events=evs) is None
