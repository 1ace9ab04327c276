"""Faults of the training job's timed path, each switched on in turn in
the program under a whole tiny run on the CPU: a train step that hands
back its state unchanged, half of the minibatch left out of the loss's
mean, a decision altered where it is made, a train step skipped, a
candidate dropped from the critic's set, a term left out of the reward.
``correct`` comes out false through the number named in ``FAULTS``.
All six share one planted program, compiled once for the module."""
import pytest

import train_faults
from bench_tiny import quick_compiles, run_tiny, tiny_train_cell
from train_faults import FAULTS


@pytest.fixture(scope="module")
def planted():
    cell = tiny_train_cell()
    mp = pytest.MonkeyPatch()
    train_faults.plant(mp)
    cell["driver"].rollout.cache_clear()
    with quick_compiles():
        yield cell
    train_faults.switch(None)
    mp.undo()
    cell["driver"].rollout.cache_clear()


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, planted):
    train_faults.switch(fault)
    try:
        line, _ = run_tiny(planted, seconds=0.1)
    finally:
        train_faults.switch(None)
    assert line["correct"] is False
    value, limit = line["checks"][FAULTS[fault][1]]
    assert value > limit, line["checks"]
