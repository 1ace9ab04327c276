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
