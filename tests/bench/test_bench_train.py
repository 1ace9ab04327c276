"""The training cell on the CPU at a tiny size: a whole run through
``bench.run.run_cell`` is correct, the program agrees with the plain
reference on decisions, rewards, losses, Adam's first moment and the
weights' change, and the control (the reference with float8 actor
matmul operands) fails the check."""
import pytest

from bench_tiny import (SEED, quick_compiles, run_tiny, tiny_train_cell,
                        train_ctx)


@pytest.fixture(scope="module")
def sound():
    with quick_compiles():
        cell = tiny_train_cell()
        line, out = run_tiny(cell, seed=SEED, seconds=0.3)
        yield cell, line, out


def test_sound_run_is_correct(sound):
    cell, line, out = sound
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {"train_slots_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    mix = cell["traffic"]
    per_episode = mix["fleets"] * mix["episode_slots"]
    assert line["attempted"] > 0 and line["attempted"] % per_episode == 0
    assert line["failed"] == 0
    assert out["compiles_in_window"]["compiled"] == 0
    assert out["checked_tokens"] == per_episode * 4


def test_program_agrees_with_the_reference(sound):
    checks = sound[1]["checks"]
    assert checks["decision_gap"][0] == 0
    assert checks["bad_episodes"][0] == 0
    for name in ("reward_gap", "loss_gap", "moment_gap", "change_gap"):
        assert checks[name][0] <= 1e-5, (name, checks[name])


def test_control_fails_the_check(sound):
    import jax
    cell, line, _ = sound
    # on the run's device, as bench.control reads it: the reference's
    # compiled slot is then the one the sound run's check compiled
    with jax.default_device(jax.devices()[0]):
        got = cell["driver"].control_readings(train_ctx(cell, SEED))
    limits = cell["config"]["limits"]
    assert set(got) == set(limits)
    assert any(got[k] > limits[k] for k in limits), got
