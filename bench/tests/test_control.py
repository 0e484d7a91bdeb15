"""The check's control, the reference in float32 in the program's
place, fails the committed limits of every cell."""
import pytest

import control
from small import cells, small_cell


@pytest.mark.parametrize("workload", cells())
def test_float32_control_fails_the_limits(workload):
    cell = small_cell(workload)
    limits = cell["limits"]["limits"]
    for seed in (1, 2, 3):
        got = control.readings(cell, seed)
        assert any(got[k] > limits[k] for k in limits), (seed, got)
