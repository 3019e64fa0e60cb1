"""The chip benchmark's tests: its harness, references and yardsticks on
the CPU, at each config's ``test_sizes``."""
import importlib.util
import os
import sys

import pytest

CHIP = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    os.pardir, "benchmarks", "chip"))
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

CELLS = ["internlm2-1.8b-2l.ppr", "internlm2-1.8b-2l.dpr"]

# Limits for runs at test sizes on the CPU, set as the chip's are: the
# program's largest reading over 14 seeds (loss 7.9e-5, grad 1.2e-3,
# update 9.7e-3, nll 6.7e-3) below, the control's least over 3 seeds
# (loss 2.2e-4, grad 4.0e-3, nll 0.031; update 3.4e-3, under the
# program's) and the half-batch fault's (update 0.22) above.
TEST_LIMITS = {"loss_gap": 1.5e-4, "grad_gap": 2.5e-3, "update_gap": 0.05,
               "nll_gap": 0.015, "reload_mismatches": 0,
               "failed_iterations": 0}


@pytest.fixture(scope="session")
def harness():
    """``benchmarks/chip/run.py`` as a module (``run`` names another
    module on the test path)."""
    spec = importlib.util.spec_from_file_location(
        "chip_bench_run", os.path.join(CHIP, "run.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
