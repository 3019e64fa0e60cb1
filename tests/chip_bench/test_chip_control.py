"""The control (the reference in fp8, in the program's place) and the
planted faults each read at least three times what the program reads on
one of the compared numbers, and come out as not correct, at test sizes
on the CPU."""
import pytest

import control
from conftest import CELLS, TEST_LIMITS


@pytest.mark.parametrize("workload", CELLS)
def test_control_and_faults_separate_from_the_program(harness, workload,
                                                      tmp_path):
    prog = harness.run(workload, 3, 0.5, False, test_sizes=True,
                       require_chip=False, limits=TEST_LIMITS,
                       work=str(tmp_path))["checks"]
    got = control.readings(workload, 3, test_sizes=True, limits=TEST_LIMITS)
    for sub in ("control", "half_batch", "altered_token"):
        numbers = got[sub]["numbers"]
        apart = [k for k, v in numbers.items()
                 if v > 0 and v >= 3 * prog[k]["value"]]
        assert apart, (sub, numbers, prog)
        assert got[sub]["correct"] is False, (sub, numbers)
