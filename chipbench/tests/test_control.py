"""The control, at a size a test run can hold: the reference with its tables
in bfloat16 stands in the program's place and fails the cell's limit, while
the program passes it, on three seeds."""

import pytest

import control
import run

SCALE = {"g500s19-u7-2": 11, "g500s19-u12-2": 9}


@pytest.mark.parametrize("cell", sorted(SCALE))
def test_control_fails_and_program_passes(cell):
    spec = run.load_cell(cell)
    spec["config"]["graph"]["scale"] = SCALE[cell]
    spec["traffic"]["batch_cap"] = 2
    limit = spec["limits"]["rel_gap"]
    for row in control.readings(spec, [1, 2, 3], controls=("bfloat16",),
                                require_tpu=False):
        assert row["program_gap"] <= limit, row
        assert row["bfloat16"] > limit, row
