"""Observability layer: telemetry registry semantics, loop/scan
equivalence, compile tracking (the packed-sweep 2-compile guard),
structured run logs, and the NaN-free report contract."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import make_agent
from repro.mec import MECConfig, MECEnv
from repro.obs import (
    CompileTracker,
    RunLog,
    hist_add,
    hist_init,
    hist_quantile,
    json_safe,
    read_events,
    rollout_telemetry,
    telemetry_host,
    telemetry_summary,
)
from repro.rollout import RolloutDriver, carry_telemetry


def make_env(m=4, n=2, **kw):
    return MECEnv(MECConfig(n_devices=m, n_servers=n, **kw))


def train_driver(key, *, telemetry=True, n_fleets=2):
    env = make_env()
    agent = make_agent("grle", env, key, buffer_size=32, batch_size=8,
                       train_every=5)
    return RolloutDriver(agent, n_fleets=n_fleets, telemetry=telemetry)


# ------------------------------------------------------------- histograms
class TestHistogram:
    def test_bucket_edges(self):
        """Left-closed bins: a value on an interior edge lands in the bin
        it opens; below-range underflows; the top edge overflows."""
        h = hist_init([0.0, 1.0, 2.0, 3.0])        # 3 bins + under/over
        h = hist_add(h, jnp.asarray([
            -0.5,         # below range            -> counts[0] underflow
            0.0,          # ON the bottom edge     -> counts[1] first bin
            0.5,          # interior               -> counts[1]
            1.0,          # ON an interior edge    -> counts[2] (bin opened)
            2.999,        # inside the last bin    -> counts[3]
            3.0,          # ON the top edge        -> counts[4] overflow
            7.0,          # above range            -> counts[4]
        ]))
        assert np.asarray(h.counts).tolist() == [1, 2, 1, 1, 2]

    def test_weights_mask_values_out(self):
        h = hist_init([0.0, 1.0])
        h = hist_add(h, jnp.asarray([0.5, 0.5, 0.5]),
                     jnp.asarray([1.0, 0.0, 1.0]))
        assert float(h.counts[1]) == 2.0

    def test_counts_stay_float32(self):
        h = hist_add(hist_init([0.0, 1.0]), jnp.asarray([0.5]))
        assert h.counts.dtype == jnp.float32
        assert h.edges.dtype == jnp.float32

    def test_quantile_interpolates_and_handles_empty(self):
        edges = [0.0, 1.0, 2.0]
        assert np.isnan(hist_quantile(edges, [0, 0, 0, 0], 0.5))
        # all mass in [1, 2): the median sits mid-bin
        q = hist_quantile(edges, [0, 0, 10, 0], 0.5)
        assert 1.0 <= q <= 2.0
        # overflow mass reports the top edge, never an extrapolation
        assert hist_quantile(edges, [0, 0, 0, 5], 0.99) == 2.0
        assert hist_quantile(edges, [5, 0, 0, 0], 0.01) == 0.0


# ----------------------------------------------------- rollout telemetry
class TestRolloutTelemetry:
    def test_loop_scan_equivalence(self, key):
        """Every non-loss leaf is bit-identical between modes; the loss
        EMA matches to float32 rounding (same caveat as
        CellMetrics.last_loss — XLA fuses train-step reductions
        differently inside scan)."""
        drv = train_driver(key)
        c_scan, _ = drv.run(key, 30, mode="scan")
        c_loop, _ = drv.run(key, 30, mode="loop")
        a, b = c_scan.telemetry, c_loop.telemetry
        for name in a.counters:
            assert np.array_equal(np.asarray(a.counters[name]),
                                  np.asarray(b.counters[name])), name
        for name in a.hists:
            assert np.array_equal(np.asarray(a.hists[name].counts),
                                  np.asarray(b.hists[name].counts)), name
        np.testing.assert_allclose(np.asarray(a.loss_ema),
                                   np.asarray(b.loss_ema), rtol=1e-5)

    def test_telemetry_does_not_perturb_trajectories(self, key):
        """The registry is observation only: decisions, rewards and the
        learned state are bitwise identical with telemetry on and off."""
        c_on, tr_on = train_driver(key, telemetry=True).run(
            key, 25, mode="scan")
        c_off, tr_off = train_driver(key, telemetry=False).run(
            key, 25, mode="scan")
        assert np.array_equal(np.asarray(tr_on.decisions),
                              np.asarray(tr_off.decisions))
        assert np.array_equal(np.asarray(tr_on.reward),
                              np.asarray(tr_off.reward))
        for pa, pb in zip(
                jax.tree_util.tree_leaves(c_on.agent_state.params),
                jax.tree_util.tree_leaves(c_off.agent_state.params)):
            assert np.array_equal(np.asarray(pa), np.asarray(pb))
        assert c_off.telemetry is None
        assert carry_telemetry(c_off) is None

    def test_counters_agree_with_trace(self, key):
        """The registry re-derives what the trace shows: task/success
        counts exactly, the Eq-9 reward decomposition to f32 sum order,
        and phi*psi summing to the realized reward."""
        drv = train_driver(key)
        carry, trace = drv.run(key, 30, mode="scan")
        c = {k: float(v) for k, v in carry.telemetry.counters.items()}
        active = np.asarray(trace.active) > 0.5
        success = np.asarray(trace.success) & active
        assert c["slots"] == 30
        assert c["tasks"] == active.sum()
        assert c["success"] == success.sum()
        assert c["train_steps"] == (~np.isnan(np.asarray(trace.loss))).sum()
        np.testing.assert_allclose(c["reward"],
                                   np.asarray(trace.reward).sum(),
                                   rtol=1e-5)
        # decision histograms partition the active tasks
        host = telemetry_host(carry.telemetry)
        for name in ("exit", "server", "latency"):
            counts = host["hists"][name]["counts"]
            assert sum(counts) == pytest.approx(c["tasks"])

    def test_summary_shapes_and_ranges(self, key):
        drv = train_driver(key)
        carry, _ = drv.run(key, 30, mode="scan")
        host = carry_telemetry(carry)
        s = host["summary"]
        env = drv.env
        assert len(s["exit_share"]) == env.L
        assert len(s["server_share"]) == env.N
        assert 0.0 <= s["deadline_hit_rate"] <= 1.0
        assert abs(sum(s["exit_share"]) - 1.0) < 1e-3
        assert (s["comm_share"] + s["wait_share"]
                + s["compute_share"]) == pytest.approx(1.0, abs=1e-6)
        # one strict-JSON host dict — the run-log contract
        json.dumps(json_safe(host), allow_nan=False)


# ------------------------------------------------------- compile tracking
class TestCompileTracker:
    def test_counts_fresh_jits(self):
        with CompileTracker() as ct:
            f = jax.jit(lambda x: x * 2 + 1)
            f(jnp.zeros((4,)))
            f(jnp.ones((4,)))          # cache hit
            g = jax.jit(lambda x: x - 3)
            g(jnp.zeros((2,)))
            ct.track("f", f)
            ct.track("g", g)
        assert ct.counts() == {"f": 1, "g": 1}
        ct.assert_counts({"f": 1, "g": 1})
        assert ct.n_backend_compiles >= 2
        assert ct.total_compile_s > 0
        json.dumps(ct.summary(), allow_nan=False)

    def test_assert_counts_raises_on_mismatch(self):
        with CompileTracker() as ct:
            f = jax.jit(lambda x: x + 1)
            f(jnp.zeros((2,)))
            f(jnp.zeros((3,)))         # second shape -> second program
            ct.track("f", f)
        assert ct.counts() == {"f": 2}
        with pytest.raises(AssertionError):
            ct.assert_counts({"f": 1})

    def test_packed_sweep_is_two_compiles(self):
        """The repo's compile-count acceptance invariant, pinned in
        tier-1: a full 4-method grid packs into exactly 2 programs (one
        per actor family), each compiling once — telemetry on."""
        from repro.sweep import SweepSpec, pack_cells
        from repro.sweep.runner import PackProgram

        spec = SweepSpec.from_names("fig5_baseline", "grle,grl,drooe,droo",
                                    2, n_devices=4, n_slots=10,
                                    replay_capacity=16, batch_size=4,
                                    train_every=5)
        packs = pack_cells(spec.expand())
        assert len(packs) == 2
        assert {p.family for p in packs} == {"gcn", "mlp"}
        with CompileTracker() as ct:
            for pack in packs:
                prog = PackProgram(pack, telemetry=True)
                prog.run()
                prog.run()             # warm re-run must reuse the cache
                ct.track(pack.label(), prog._episode)
        ct.assert_counts({pack.label(): 1 for pack in packs})


# ------------------------------------------------------ compile-cache rule
_CACHE_PROBE = ("import jax\n"
                "from repro.obs.compile import use_compile_cache\n"
                "print(use_compile_cache(), "
                "jax.config.jax_compilation_cache_dir)\n")


def _cache_probe(**env_over) -> list:
    import subprocess
    import sys

    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(env_over, JAX_PLATFORMS="cpu",
               PYTHONPATH="src" + os.pathsep + env.get("PYTHONPATH", ""))
    p = subprocess.run([sys.executable, "-c", _CACHE_PROBE], env=env,
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0, p.stderr[-2000:]
    return p.stdout.split()


class TestCompileCache:
    def test_env_var_is_honoured_and_nothing_set_in_code(
            self, tmp_path, monkeypatch):
        from repro.obs import compile as obs_compile

        calls = []
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        monkeypatch.setattr(obs_compile.jax.config, "update",
                            lambda *a: calls.append(a))
        assert obs_compile.use_compile_cache() == str(tmp_path)
        assert calls == []
        # and JAX itself picks the variable up
        assert _cache_probe(JAX_COMPILATION_CACHE_DIR=str(tmp_path)) == \
            [str(tmp_path)] * 2

    def test_default_is_fixed_checkout_path_across_processes(self):
        from repro.obs.compile import DEFAULT_CACHE_DIR

        first, second = _cache_probe(), _cache_probe()
        assert first == second == [DEFAULT_CACHE_DIR] * 2
        assert os.path.basename(DEFAULT_CACHE_DIR) == ".jax_cache"
        assert os.path.isfile(os.path.join(
            os.path.dirname(DEFAULT_CACHE_DIR), "pyproject.toml"))


# ----------------------------------------------------------- chip peaks
class TestChipPeaks:
    def test_v5e_row_is_the_published_one(self):
        from repro.obs.peaks import TPU_V5E, chip_peaks

        v5e = chip_peaks(TPU_V5E)
        assert (v5e.flops_bf16, v5e.hbm_bw) == (197e12, 819e9)

    @pytest.mark.parametrize("kind", ["cpu", "TPU v4", ""])
    def test_unknown_device_kind_is_an_error(self, kind):
        from repro.obs.peaks import chip_peaks

        with pytest.raises(KeyError, match="no published peaks"):
            chip_peaks(kind)


# --------------------------------------------------------- sweep + report
class TestSweepTelemetry:
    def test_rows_carry_strict_json_telemetry(self):
        from repro.sweep import SweepSpec, pack_cells, run_cell
        from repro.sweep.runner import PackProgram

        spec = SweepSpec.from_names("fig5_baseline", "grle", 1,
                                    n_devices=4, n_slots=10,
                                    replay_capacity=16, batch_size=4,
                                    train_every=5)
        (pack,) = pack_cells(spec.expand())
        (row,) = PackProgram(pack, telemetry=True).run()
        tel = row["telemetry"]
        json.dumps(row, allow_nan=False)
        assert tel["summary"]["tasks"] == tel["counters"]["tasks"]
        # packed and per-cell reference agree on the registry counters
        ref = run_cell(spec.expand()[0], telemetry=True)
        for k, v in tel["counters"].items():
            assert ref["telemetry"]["counters"][k] == pytest.approx(
                v, rel=1e-5), k

    def test_report_never_serializes_nan(self, tmp_path):
        from repro.sweep.report import (build_report, format_markdown,
                                        format_telemetry, write_report)

        rows = [
            {"scenario": "fig5_baseline", "method": "grle", "seed": 0,
             "avg_accuracy": 0.8, "ssp": 0.9, "deadline_miss": 0.1,
             "throughput_tps": 5.0, "avg_reward": 0.2,
             "final_loss": float("nan")},   # pre-train NaN must not leak
            {"scenario": "fig5_baseline", "method": "grl", "seed": 0,
             "avg_accuracy": 0.4, "ssp": 0.8, "deadline_miss": 0.2,
             "throughput_tps": 4.0, "avg_reward": 0.1, "final_loss": None},
        ]
        report = build_report(rows)
        stats = report["scenarios"]["fig5_baseline"]["methods"]["grle"]
        assert stats["final_loss"]["mean"] is None
        assert stats["final_loss"]["n"] == 0
        path = write_report(report, str(tmp_path / "report.json"))
        text = open(path).read()
        assert "NaN" not in text
        json.loads(text)                   # strict parse round-trips
        format_markdown(report)            # renders without touching NaN
        assert "no telemetry" in format_telemetry(rows)

    def test_format_telemetry_renders_rows(self, key):
        from repro.sweep.report import format_telemetry

        drv = train_driver(key)
        carry, _ = drv.run(key, 20, mode="scan")
        row = {"scenario": "fig5_baseline", "method": "grle", "seed": 0,
               "telemetry": json_safe(carry_telemetry(carry))}
        table = format_telemetry([row])
        assert "fig5_baseline/grle/s0" in table
        assert "lat_p50" in table


# ------------------------------------------------------------------- logs
class TestRunLog:
    def test_jsonl_roundtrip_and_nan_scrub(self, tmp_path):
        out = str(tmp_path / "run")
        with RunLog(out, manifest={"config_signature": "test"}) as log:
            log.emit("episode", loss=float("nan"),
                     arr=np.asarray([1.0, float("inf")]),
                     scalar=np.float32(2.5))
        events = read_events(log.path)
        assert [e["event"] for e in events] == ["manifest", "episode"]
        assert events[0]["seq"] == 0 and events[1]["seq"] == 1
        ep = events[1]
        assert ep["loss"] is None              # NaN -> null
        assert ep["arr"] == [1.0, None]        # inf -> null
        assert ep["scalar"] == 2.5

    def test_json_safe_handles_jnp(self):
        out = json_safe({"a": jnp.float32(jnp.nan), "b": jnp.arange(3),
                         "c": (1, jnp.inf)})
        assert out == {"a": None, "b": [0, 1, 2], "c": [1, None]}


# ----------------------------------------------------------------- engine
class TestEngineTelemetry:
    @pytest.fixture(scope="class")
    def engine(self):
        from repro.configs import get_arch
        from repro.serve.engine import EdgeServingEngine, Replica

        cfg = get_arch("qwen1_5_0_5b", reduced=True)
        return EdgeServingEngine(cfg, [Replica("a"), Replica("b", 0.5)],
                                 batch_slots=3)

    def test_decode_single_transfer_each_way(self, engine):
        from repro.serve.engine import Request

        reqs = [Request(tokens=np.asarray([3, 5, 7], np.int32),
                        deadline_s=0.05, max_new=3),
                Request(tokens=np.asarray([2, 9], np.int32),
                        deadline_s=0.05, max_new=2)]
        before = dict(engine.transfers)
        outs = engine._decode(reqs, engine.cfg.exit_layers[0])
        assert engine.transfers["decode_h2d"] == before["decode_h2d"] + 1
        assert engine.transfers["decode_d2h"] == before["decode_d2h"] + 1
        assert (engine.transfers["decode_launches"]
                == before["decode_launches"] + 1)
        assert [len(o) for o in outs] == [3, 2]
        assert all(isinstance(t, int) for o in outs for t in o)

    def test_serve_slot_spans_and_pinned_host_reads(self, engine,
                                                    tmp_path):
        """One decoding call under a profiler capture: one ``serve/price``,
        one ``serve/decode`` per exit group, and a ``serve/pull`` per
        blocking read, equal in number to the ``host_pulls`` delta: six
        in the scheduling phase (success, accuracy, active, reward,
        decision, t_total), then one inside each exit group's decode,
        which launches one decode program."""
        import glob

        from jax.profiler import ProfileData

        from repro.obs import trace_capture
        from repro.serve.engine import Request

        reqs = [Request(tokens=np.asarray([3, 5, 7], np.int32),
                        deadline_s=0.05, max_new=2),
                Request(tokens=np.asarray([2, 9], np.int32),
                        deadline_s=0.05, max_new=3),
                Request(tokens=np.asarray([4], np.int32),
                        deadline_s=0.05, max_new=2)]
        engine.serve_slot(reqs, decode=True)      # compile outside
        before = dict(engine.transfers)
        with trace_capture(str(tmp_path)):
            assignments, info = engine.serve_slot(reqs, decode=True)
        pulls = engine.transfers["host_pulls"] - before["host_pulls"]
        groups = len({e for _, e in assignments})
        assert (engine.transfers["decode_launches"]
                - before["decode_launches"]) == groups
        assert [len(t) for t in info["texts"]] == [2, 3, 2]

        path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                         recursive=True)[0]
        spans = sorted(
            (e.start_ns, e.start_ns + e.duration_ns, e.name)
            for p in ProfileData.from_file(path).planes
            if not p.name.startswith("/device:")
            for line in p.lines for e in line.events
            if e.name.startswith("serve/"))
        layers = [(s, t, n) for s, t, n in spans if n != "serve/pull"]
        names = [n for _, _, n in layers]
        assert names == ["serve/price"] + ["serve/decode"] * groups

        def inside(s, t):
            return [n for a, b, n in layers if a <= s and t <= b]

        reads = [inside(s, t) for s, t, n in spans if n == "serve/pull"]
        assert reads == [["serve/price"]] * 6 + [["serve/decode"]] * groups
        assert pulls == len(reads) == 6 + groups

    def test_zero_request_snapshot_is_strict_json(self):
        # a freshly constructed engine has served nothing: every quantile
        # must be None (not NaN) and every rate 0 — no div-by-zero
        from repro.configs import get_arch
        from repro.serve.engine import EdgeServingEngine, Replica

        cfg = get_arch("qwen1_5_0_5b", reduced=True)
        fresh = EdgeServingEngine(cfg, [Replica("a")], batch_slots=2)
        snap = fresh.telemetry_snapshot()
        s = snap["summary"]
        json.dumps(json_safe(snap), allow_nan=False)
        assert s["tasks"] == 0
        assert s["deadline_hit_rate"] == 0.0
        assert s["latency_ring_n"] == 0
        for key in ("latency_p50", "latency_p99", "latency_p50_s",
                    "latency_p99_s", "latency_p50_s_exact",
                    "latency_p99_s_exact"):
            assert s[key] is None, (key, s[key])

    def test_snapshot_summary(self, engine):
        for _ in range(5):
            engine.serve_slot()
        snap = engine.telemetry_snapshot()
        s = snap["summary"]
        assert s["tasks"] == snap["counters"]["tasks"] > 0
        assert 0.0 <= s["deadline_hit_rate"] <= 1.0
        dl = float(engine.env.cfg.deadline_s)
        assert s["latency_p50_s"] == pytest.approx(s["latency_p50"] * dl)
        assert snap["transfers"]["telemetry_pulls"] == 1
        json.dumps(json_safe(snap), allow_nan=False)
        # the exact latency ring saw the same served requests: true order
        # statistics alongside the histogram estimates
        assert s["latency_ring_n"] > 0
        assert np.isfinite(s["latency_p50_s_exact"])
        assert np.isfinite(s["latency_p99_s_exact"])
        assert s["latency_p50_s_exact"] <= s["latency_p99_s_exact"]
