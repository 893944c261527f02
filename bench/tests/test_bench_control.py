"""The low-precision control, kept at a size a test run can hold: the
plain reference computed in bfloat16, put in the program's place, must come
out not correct under each cell's own limits (on the chip it was read at
the cells' own sizes; see PERF.md)."""
import pytest

import bench_tiny
import harness
from kinds import train

TRAIN = ["gpt2-12l.train-fixed", "gpt2-12l.train-prog"]


@pytest.mark.parametrize("name", TRAIN)
@pytest.mark.parametrize("seed", [7, 2 ** 31 + 5])
def test_training_control_is_not_correct(name, seed):
    cell = bench_tiny.cell(name)
    limits = cell.workload["limits"]
    got = train.readings(cell, seed, 0.5, ["program", "bf16"])
    for mode, correct in (("program", True), ("bf16", False)):
        compared = {k: v for k, v in limits.items() if k in got[mode]}
        assert harness.judge(got[mode], compared)[0] == correct, got[mode]
