"""The yardsticks: peaks, FLOP counts, seeds and the manifest."""
import json
import os
import re

import numpy as np
import pytest

import cell
import flops
import peaks
import weights
from conftest import CELLS, CHIP
from traffic import Traffic

ROOT = os.path.dirname(os.path.dirname(CHIP))


def test_peaks_of_v5e():
    p = peaks.peak("TPU v5 lite")
    assert (p["bf16_flops"], p["hbm_bytes_per_s"], p["hbm_bytes"]) == (
        197e12, 819e9, 16e9)


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peak("TPU v9 imaginary")


def test_dense_layer_flops_by_hand():
    c = cell.load("internlm2-1.8b-2l.ppr").config
    one = dict(c, num_hidden_layers=1, vocab_size=0)
    d, f, s = 2048, 8192, 2048
    q_o = 2 * (2 * d * d)                 # Wq and Wo: 16 heads x 128
    k_v = 2 * (2 * d * 1024)              # Wk and Wv: 8 heads x 128
    mlp = 3 * 2 * d * f
    attn = 2 * 2 * 16 * 128 * (s + 1) / 2  # causal QK^T and PV
    assert flops.forward_per_token(one, s) == q_o + k_v + mlp + attn


def test_reference_forward_matches_the_program():
    # Weights drawn at std 0.3, so that a wrong rotary base or head
    # order moves the logits by more than their size (14-15 against a
    # largest logit of 11), while the program's bf16 weights and
    # activations move them by 0.37.
    import jax
    import jax.numpy as jnp
    import workflow
    from reference import common
    from repro.models import lm
    c = cell.load("internlm2-1.8b-2l.dpr", test_sizes=True).config
    c = dict(c, initializer_range=0.3)
    model = cell.reference_module(c)
    params = weights.make(model.param_spec(c), weights.key_for(5, 3))
    toks = jnp.asarray(workflow.tokens(c, cell.Sizes(2, 32, 1, 0, 0, 0, {}),
                                       9, 1, 1)[0])
    with jax.default_matmul_precision("highest"):
        got = lm.forward(workflow.arch(c, model.vocab(c)), params,
                         toks).logits.astype(jnp.float32)
        want = model.forward(c, common.f32(params), toks, common.exact)
    assert float(jnp.max(jnp.abs(got - want))) <= 0.1 * float(
        jnp.max(jnp.abs(want)))


def test_state_digest_sees_one_changed_element():
    import jax.numpy as jnp
    import workflow
    w = jnp.linspace(-1, 1, 4096, dtype=jnp.bfloat16).reshape(64, 64)
    state = {"w": w,
             "n": jnp.ones(64, jnp.float32), "step": jnp.asarray(3)}
    base = np.asarray(workflow.state_digest(state))
    assert np.array_equal(base, np.asarray(workflow.state_digest(
        {k: v.copy() for k, v in state.items()})))
    for leaf, changed in (("w", state["w"].at[7, 9].set(0.5)),
                          ("n", state["n"].at[63].add(1e-7)),
                          ("step", state["step"] + 1)):
        other = np.asarray(workflow.state_digest({**state, leaf: changed}))
        assert (other != base).sum() == 1, leaf


def test_reload_check_counts_each_other_checksum():
    import check
    from workflow import Knobs
    out = {"state_digest": [7, 9], "train_losses": [1.0], "nll": [0.0, 0.0],
           "grad_norms": {"a": 1.0}, "update_norms": {"a": 1.0}}
    cold = {"knobs": Knobs(1, 2, 3), "status": "done", "out": out}
    same = dict(cold, knobs=Knobs(1, 2, 4))
    other = dict(same, out=dict(out, state_digest=[7, 10]))
    new_data = dict(other, knobs=Knobs(5, 2, 4))   # trained anew: not reused
    mismatches = lambda window: check.readings(  # noqa: E731
        _NoGaps(), cold, window, len(window), 1)["reload_mismatches"]
    assert mismatches([same, same]) == 0
    assert mismatches([same, other, other, new_data]) == 2


class _NoGaps:
    """A reference that agrees with every answer, so only the reload
    check has anything to count."""

    def trained(self, k):
        return {"losses": [1.0], "grad_norms": {"a": 1.0},
                "update_norms": {"a": 1.0}}

    def eval_nll(self, k):
        return np.zeros(2)


def test_seeds_draw_fixed_distinct_streams():
    big = 2**31 + 12345
    a, b = Traffic({"clients": 2, "edit": ["eval_seed"]}, big), Traffic(
        {"clients": 2, "edit": ["eval_seed"]}, big)
    assert a.base == b.base and a.knobs(1, 4) == b.knobs(1, 4)
    drawn = {a.base.eval_seed} | {a.knobs(c, i).eval_seed
                                  for c in range(2) for i in range(-1, 50)}
    assert len(drawn) == 1 + 2 * 51
    assert a.knobs(0, 3).data_seed == a.base.data_seed
    assert np.array_equal(weights.key_for(big, 1), weights.key_for(big, 1))
    assert not np.array_equal(weights.key_for(big, 1),
                              weights.key_for(big + 2**32, 1))


@pytest.mark.parametrize("workload", CELLS)
def test_reference_spec_is_the_programs_layout(workload):
    import workflow
    c = cell.load(workload)
    model = cell.reference_module(c.config)
    workflow._check_layout(workflow.arch(c.config, model.vocab(c.config)),
                           model.param_spec(c.config))


def test_manifest_names_files_and_readers():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    for cfg in bench["configs"]:
        assert name.match(cfg["name"])
        with open(os.path.join(ROOT, cfg["file"])) as f:
            assert json.load(f)["name"] == cfg["name"]
    for w in bench["workloads"]:
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        c = cell.load(w["name"])
        assert c.chips == w["chips"] and c.limits
    metrics = bench["end_to_end"] + bench["per_layer"]
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"])
    for m in bench["per_layer"]:
        assert os.path.exists(os.path.join(CHIP, "metrics",
                                           m["name"] + ".py"))
