"""A whole serving run on the CPU at a tiny size: correct code passes the
check, and each fault of the timed path makes ``correct`` false.

The look for a chip is skipped (``require_tpu=False``); everything else
is the run as ``bench.run`` makes it: weights from the seed, the engine,
warm-up, the window, and the reference check after it.
"""
import pytest

from bench_tiny import run_tiny, tiny_cell

CELL = "qwen05b_edge.short"


@pytest.mark.parametrize("replay", [False, True],
                         ids=["warm-every-length", "replay-the-window"])
def test_sound_run_is_correct(replay):
    line, out = run_tiny(tiny_cell(CELL, replay=replay))
    assert line["correct"] is True
    assert line["failed"] == 0 and line["attempted"] > 0
    assert list(line)[-1] == "checks"
    gap, limit = line["checks"]["logit_gap"]
    assert gap <= limit
    assert set(line["metrics"]) == {"serve_tokens_per_s", "serve_p95_ms",
                                    "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert out["compiles_in_window"]["compiled"] == 0


def test_scheduling_engine_makes_the_window_decisions():
    """Set-up finds the window's decode shapes with an engine that only
    schedules: it has to pick the same exits as the engine that decodes,
    whatever requests either is given."""
    import numpy as np
    from repro.serve import EdgeServingEngine, Replica, Request
    cell = tiny_cell(CELL)
    cfg = cell["config"]
    arch = cell["driver"].layout(cfg).arch_config(cfg)

    def engine(model):
        return EdgeServingEngine(
            arch, [Replica(n, float(s)) for n, s in cfg["replicas"]],
            batch_slots=cfg["batch_slots"], cache_len=cfg["cache_len"],
            scheduler=cfg["scheduler"], seed=cfg["engine_seed"],
            init_model=model)

    def req(p, a):
        return Request(tokens=np.ones(p, np.int32), deadline_s=1.0,
                       max_new=a)

    full, plan = engine(True), engine(False)
    for i in range(24):
        got, _ = full.serve_slot([req(3, 2), req(8, 6)], decode=i % 3 == 0)
        want, _ = plan.serve_slot([req(5, 1), req(4, 4)])
        assert got == want


def test_group_shapes_follow_the_decode_groups():
    shapes = tiny_cell(CELL)["driver"].group_shapes
    batch = [(3, 2), (8, 1), (4, 6), (2, 2)]
    exits = [("a", 1), ("b", 2), ("a", 1), ("b", 1)]
    assert shapes(batch, exits) == {(3, 4 + 6), (1, 8 + 1)}
    assert shapes(batch[:1], exits[:1]) == {(1, 5)}


def _broken_decode(monkeypatch, alter):
    from repro.serve import engine as eng
    orig = eng.EdgeServingEngine._decode

    def decode(self, requests, exit_layer):
        return alter(self, requests, exit_layer, orig)

    monkeypatch.setattr(eng.EdgeServingEngine, "_decode", decode)


def test_token_altered_where_produced_is_caught(monkeypatch):
    def alter(self, requests, exit_layer, orig):
        out = orig(self, requests, exit_layer)
        for toks in out:
            toks[-1] = (toks[-1] + 1) % self.cfg.vocab
        return out

    _broken_decode(monkeypatch, alter)
    line, _ = run_tiny(tiny_cell(CELL))
    assert line["correct"] is False
    assert line["checks"]["logit_gap"][0] > line["checks"]["logit_gap"][1]


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    def alter(self, requests, exit_layer, orig):
        keep = max(1, len(requests) // 2)
        out = orig(self, requests[:keep], exit_layer)
        # the rows left out get the first row's tokens, cut to length
        first = out[0] + out[0] * 200
        return out + [first[: r.max_new] for r in requests[keep:]]

    _broken_decode(monkeypatch, alter)
    line, _ = run_tiny(tiny_cell(CELL))
    assert line["correct"] is False


def test_cache_left_unchanged_by_the_step_is_caught(monkeypatch):
    from repro.serve import engine as eng
    orig = eng.make_serve_step

    def make(cfg, *, exit_layer=None):
        step = orig(cfg, exit_layer=exit_layer)

        def stale(params, cache, tokens, pos):
            logits, _ = step(params, cache, tokens, pos)
            return logits, cache

        return stale

    monkeypatch.setattr(eng, "make_serve_step", make)
    line, _ = run_tiny(tiny_cell(CELL))
    assert line["correct"] is False


def test_bad_answer_length_is_a_failure(monkeypatch):
    def alter(self, requests, exit_layer, orig):
        return [t[:-1] for t in orig(self, requests, exit_layer)]

    _broken_decode(monkeypatch, alter)
    line, _ = run_tiny(tiny_cell(CELL))
    assert line["correct"] is False and line["failed"] == line["attempted"]
