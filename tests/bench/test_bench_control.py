"""The correctness check's control at a tiny size on the CPU.

The control is the plain reference with fp8 weights put in the
program's place (``bench.control``). Read at the same positions of the
same requests, it has to fail the configuration's ``logit_gap`` limit
while the program, at the same size, stays under it. The full-size
readings on the chip, from which the limit was set, are in PERF.md.
"""
from bench import control
from bench_tiny import tiny_cell

CELL = "qwen05b_edge.long"


def test_control_fails_the_limit_and_the_program_passes():
    rows = control.main(["--workload", CELL, "--seeds", "7,9",
                         "--seconds", "0.5"],
                        require_tpu=False, resolved=tiny_cell(CELL))
    for row in rows:
        assert row["checked_tokens"] > 0
        assert row["program"] <= row["limit"] < row["control"], row


def test_training_control_row_holds_every_number():
    """A driver with ``control_readings`` of its own (training) gets one
    row per seed: every number of its check read on the program and on
    the control, beside its limit."""
    import types
    from bench_tiny import tiny_train_cell
    cell = tiny_train_cell()
    seen = []
    cell["driver"] = types.SimpleNamespace(
        run=lambda ctx: seen.append(ctx["seed"]) or {
            "checks": {"loss_gap": [1e-7, 0.01], "bad_episodes": [0, 0]},
            "attempted": 30},
        control_readings=lambda ctx: {"loss_gap": 0.5})
    rows = control.main(["--workload", "grle_paper.train64", "--seeds",
                         "3,4", "--seconds", "0.1"],
                        require_tpu=False, resolved=cell)
    assert seen == [3, 4]
    assert rows[0] == {"workload": "grle_paper.train64", "seed": 3,
                       "program": {"loss_gap": 1e-7, "bad_episodes": 0},
                       "control": {"loss_gap": 0.5},
                       "limits": {"loss_gap": 0.01, "bad_episodes": 0},
                       "attempted": 30}
