"""``chip_smoke.py`` rehearsed on the CPU: its phases at ``configs.reduced``
sizes (kernels in interpret mode), and its refusal to run without a TPU."""
import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), os.pardir))
import chip_smoke  # noqa: E402

SIZES = chip_smoke.sizes(reduced=True)
KIND = "cpu"


def test_kernel_phases_match_references(capsys):
    chip_smoke.phase_kernels(SIZES, on_tpu=False, device=KIND)
    lines = capsys.readouterr().out.splitlines()
    assert [ln.split(":", 2)[1] for ln in lines] == [
        "flash_attention", "ssd", "rmsnorm"]


def test_iteration_phase_reuses_trained_state(tmp_path):
    found = chip_smoke.phase_iteration(SIZES.lm, KIND,
                                       workdir=str(tmp_path / "w"))
    assert found["cold"]["nodes"] == dict.fromkeys(
        ("tokens", "initState", "train", "evalLoss"), "compute")
    assert found["warm"]["nodes"]["train"] == "load"
    assert found["warm_eval_loss"] == found["eval_loss"]
    assert not (tmp_path / "w").exists()


def test_sharded_phase_on_local_devices(tmp_path):
    found = chip_smoke.phase_sharded(SIZES.lm, KIND,
                                     workdir=str(tmp_path / "w"))
    n = len(jax.devices())
    assert found["mesh"]["batch"] == n
    assert all(p["min_devices"] == n
               for p in found["mesh"]["placement"].values())


def test_main_fails_without_tpu(monkeypatch, capsys):
    monkeypatch.setattr(chip_smoke, "init_compile_cache", lambda: "off")
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert '"ok": true' not in capsys.readouterr().out
