"""Faults of the training job's timed path, each planted in the program
under a whole tiny run on the CPU: a train step that hands back its
state unchanged, half of the minibatch left out of the loss's mean, a
decision altered where it is made, a train step skipped, a candidate
dropped from the critic's set, a term left out of the reward. ``correct``
comes out false through the number named in ``FAULTS``."""
import pytest

from bench_tiny import run_tiny, tiny_train_cell
from train_faults import FAULTS


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_incorrect(fault, monkeypatch):
    plant, catcher = FAULTS[fault]
    plant(monkeypatch)
    line, _ = run_tiny(tiny_train_cell(), seconds=0.1)
    assert line["correct"] is False
    value, limit = line["checks"][catcher]
    assert value > limit, line["checks"]
