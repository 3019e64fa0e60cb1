"""The trace reduction, on traces whose numbers were counted by hand."""
import os

import pytest

import devtrace
from conftest import CHIP

DATA = os.path.join(CHIP, "testdata")
PROGRAMS = ("helix_train_step", "helix_eval_nll")


def test_hand_built_trace():
    # One TPU plane; a 10 ms window. Ops at 2-3, 3-5 ms (in the eval
    # program), 6-7.5, 7.5-8 ms (in the train step), and one before the
    # window: busy 5 ms in two merged runs, so gaps of 2 ms (both clients
    # waiting), 1 ms (client 0 waiting) and 2 ms (no span).
    r = devtrace.reduce(os.path.join(DATA, "hand.xplane.pb"), PROGRAMS)
    assert r["window_s"] == pytest.approx(0.010)
    assert r["busy_s"] == pytest.approx(0.005)
    assert r["programs"] == {"helix_eval_nll": [1, pytest.approx(0.003)],
                             "helix_train_step": [1, pytest.approx(0.002)]}
    assert r["device_ops"] == [
        ["jit_helix_eval_nll:dot.2", pytest.approx(0.002)],
        ["jit_helix_train_step:dot.2", pytest.approx(0.0015)],
        ["jit_helix_eval_nll:fusion.1", pytest.approx(0.001)],
        ["jit_helix_train_step:copy.3", pytest.approx(0.0005)]]
    assert sorted(r["idle_gaps"], key=lambda g: (-g[1], g[0])) == [
        ["client0.wait+client1.wait", pytest.approx(0.002)],
        ["outside any client span", pytest.approx(0.002)],
        ["client0.wait", pytest.approx(0.001)]]


def test_trace_without_window_is_refused():
    with pytest.raises(ValueError):
        devtrace.reduce(os.path.join(DATA, "no_window.xplane.pb"))


def test_recorded_v5e_trace():
    # 2.4 s cut from the first traced chip run of internlm2-1.8b-2l.ppr
    # (TPU v5 lite): both clients wait while their two evals run back to
    # back. Busy time was counted once by hand, as 1-us bins over the
    # union of the XLA Ops events: 0.066139 s.
    r = devtrace.reduce(os.path.join(DATA, "v5e_ppr.xplane.pb"), PROGRAMS)
    assert r["window_s"] == pytest.approx(2.4)
    assert r["busy_s"] == pytest.approx(0.066139, abs=2e-6)
    runs, seconds = r["programs"]["helix_eval_nll"]
    assert runs == 2 and seconds == pytest.approx(r["busy_s"], abs=2e-6)
    assert "helix_train_step" not in r["programs"]
    assert len(r["device_ops"]) == 10
    assert all(name.startswith("jit_helix_eval_nll:%")
               for name, _ in r["device_ops"])
    assert r["idle_gaps"][0][0] == "client0.wait+client1.wait"
    assert r["idle_gaps"][0][1] == pytest.approx(2.3208, abs=1e-4)
