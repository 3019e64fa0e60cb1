"""Each cell's workflow and mix, driven through the harness on the CPU."""
import pytest

from conftest import CELLS, TEST_LIMITS


@pytest.mark.parametrize("workload", CELLS)
def test_cell_runs_and_is_correct(harness, workload, tmp_path):
    r = harness.run(workload, 2**33 + 5, 1.0, False, test_sizes=True,
                    require_chip=False, limits=TEST_LIMITS,
                    work=str(tmp_path))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert r["metrics"]["iterations_per_min"]["value"] > 0
    assert r["metrics"]["setup_s"]["value"] > 0
    assert ("iteration_p90_s" in r["metrics"]) == workload.endswith(".ppr")
    assert list(r)[-1] == "checks"


def test_traced_run_reports_per_layer_metrics(harness, tmp_path):
    r = harness.run("internlm2-1.8b-2l.dpr", 7, 1.0, True, test_sizes=True,
                    require_chip=False, limits=TEST_LIMITS,
                    work=str(tmp_path))
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert m["compile.count"]["value"] == 0
    for name in ("server.wait_s", "planner.s", "store.load_s",
                 "store.save_s", "node.compute_s"):
        assert m[name]["value"] >= 0, name
    assert m["node.compute_s"]["value"] > 0
    # No TPU plane on the CPU: the device metrics of the trace are there,
    # and the chip's peak is not, so there is no MFU.
    assert "step.mfu" not in m
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}
