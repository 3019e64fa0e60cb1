"""The mamba2-130m.dpr cell on the CPU, at its config's test sizes: the
cell through the harness, the control and the planted faults against it,
the reference's chunked SSD against the sequential recurrence, the
reference against the program, and the work counts against the
program's own scopes and a count by hand."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import cell
import control
import devtrace
import flops
import weights
import workflow
from reference import common, mamba2
from repro.kernels.ssd.ref import ssd_ref

CELL = "mamba2-130m.dpr"
PROGRAMS = ("helix_train_step", "helix_eval_nll")

# Limits for runs at test sizes on the CPU, set as the chip's are: the
# program's largest reading over 14 seeds (loss 1.3e-4, grad 5.8e-3,
# update 0.20, nll 8.6e-3) below, the control's least over the same
# seeds (grad 0.014, nll 0.037; loss 1.8e-4, too near the program's to
# separate) and the planted faults' (half batch: loss 3.3e-3, grad 0.077;
# altered token: nll 0.10) above. The program's update_gap comes from
# its bf16 conv weights: their init (up to 1/sqrt(4) = 0.5) puts the bf16
# spacing (2^-9) near a step's update, so part of each update rounds
# away (conv_b moves 16 % less than in float32); it separates nothing.
LIMITS = {"loss_gap": 2.5e-4, "grad_gap": 0.01, "update_gap": 0.5,
          "nll_gap": 0.02, "reload_mismatches": 0, "failed_iterations": 0}


def test_cell_runs_and_is_correct(harness, tmp_path):
    r = harness.run(CELL, 2**33 + 5, 1.0, False, test_sizes=True,
                    require_chip=False, limits=LIMITS, work=str(tmp_path))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0
    assert set(r["metrics"]) == {"iterations_per_min", "setup_s"}


def test_control_and_faults_are_not_correct(harness, tmp_path):
    prog = harness.run(CELL, 3, 0.5, False, test_sizes=True,
                       require_chip=False, limits=LIMITS,
                       work=str(tmp_path))["checks"]
    got = control.readings(CELL, 3, test_sizes=True, limits=LIMITS)
    for sub in ("control", "half_batch", "altered_token"):
        numbers = got[sub]["numbers"]
        apart = [k for k, v in numbers.items()
                 if v > 0 and v >= 3 * prog[k]["value"]]
        assert apart, (sub, numbers, prog)
        assert got[sub]["correct"] is False, (sub, numbers)


@pytest.mark.parametrize("chunk", [4, 8, 24, 5, 7, 32])
def test_reference_ssd_is_the_recurrence(chunk):
    # 24 positions: chunks that divide it, that do not, and one longer.
    b, s, h, p, n = 2, 24, 3, 4, 5
    ks = jax.random.split(jax.random.PRNGKey(4), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    B, C = (jax.random.normal(k, (b, s, n)) for k in ks[3:])
    y, last = mamba2.ssd(x, dt, a, B, C, chunk, common.exact)
    # h ← h·exp(dt·A) + dt·B⊗x, y = C·h, one position at a time.
    want_y, want_last = ssd_ref(x, dt, a, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(want_last),
                               rtol=1e-5, atol=1e-5)


def test_reference_forward_matches_the_program():
    # The embedding drawn at std 0.5 makes the logits large (up to 29),
    # so that a conv run backwards or a gate applied after the norm moves
    # them by more than a tenth of that, while the program's bf16 weights
    # and activations move them by 0.14.
    c = cell.load(CELL, test_sizes=True).config
    c = dict(c, initializer_range=0.5)
    params = weights.make(mamba2.param_spec(c), weights.key_for(5, 3))
    toks = jnp.asarray(workflow.tokens(c, cell.Sizes(2, 32, 1, 0, 0, 0, {}),
                                       9, 1, 1)[0])
    from repro.models import lm
    with jax.default_matmul_precision("highest"):
        got = lm.forward(workflow.arch(c, mamba2.vocab(c)), params,
                         toks).logits.astype(jnp.float32)
        want = mamba2.forward(c, common.f32(params), toks, common.exact)
    assert float(jnp.max(jnp.abs(got - want))) <= 0.1 * float(
        jnp.max(jnp.abs(want)))


def test_reference_spec_is_the_programs_layout():
    c = cell.load(CELL).config
    workflow._check_layout(workflow.arch(c, mamba2.vocab(c)),
                           mamba2.param_spec(c))
    assert mamba2.vocab(c) == 50288


@pytest.fixture(scope="module")
def compiled():
    """Both step programs compiled at test sizes: their optimized HLO."""
    c = cell.load(CELL, test_sizes=True)
    pr = workflow.Programs(c.config, c.sizes, mamba2)
    state = jax.eval_shape(pr.init, weights.key_for(1, 2))
    toks = jax.ShapeDtypeStruct((c.sizes.batch, c.sizes.seq_len), jnp.int32)
    return c, {
        "helix_train_step": pr.train.lower(state, {"tokens": toks}),
        "helix_eval_nll": pr.eval_nll.lower(state.params, toks)}


@pytest.mark.parametrize("program", PROGRAMS)
def test_scope_work_names_the_programs_scopes(compiled, program):
    c, lowered = compiled
    work = flops.scope_work(c.config, program, c.sizes.batch,
                            c.sizes.seq_len)
    hlo = lowered[program].compile().as_text()
    known = set(work) | {"attention", "mlp", "optimizer"}
    carried = {devtrace.scope_of(n, known)
               for n in re.findall(r'op_name="([^"]*)"', hlo)} - {None}
    assert carried == set(work)


def test_scope_flops_by_hand():
    c = cell.load(CELL).config
    d, di, h, p, n, v, layers, chunk = 768, 1536, 24, 64, 128, 50288, 24, 256
    b, s = 2, 8192
    proj = 2 * d * (2 * di + 2 * n + h) + 2 * di * d
    scan = (2 * n + 2 * h * p) * (chunk + 1) / 2 + 2 * 2 * h * p * n
    per_token = layers * (proj + scan) + 2 * d * v
    work = flops.scope_work(c, "helix_train_step", b, s)
    assert sum(f for f, _ in work.values()) == flops.train_step(c, b, s)
    assert flops.train_step(c, b, s) == pytest.approx(3 * per_token * b * s,
                                                      rel=1e-12)
    assert flops.eval_pass(c, b, s) == pytest.approx(per_token * b * s,
                                                     rel=1e-12)
    assert work["ssd"][0] == pytest.approx(3 * layers * scan * b * s,
                                           rel=1e-12)
    # 22 bytes per bf16 parameter (read it, its gradient and both float32
    # moments; write it and both moments), 28 per float32 one.
    bf16 = v * d + layers * (d * (2 * di + 2 * n + h) + 5 * (di + 2 * n)
                             + di * d)
    f32 = d + layers * (d + 3 * h + di)
    assert work["optimizer"] == (0.0, 22 * bf16 + 28 * f32)


@pytest.mark.parametrize("name, scope", [("ssd_roofline", "ssd"),
                                         ("ssm_proj_roofline", "ssm_proj")])
def test_roofline_readers_by_hand(harness, name, scope):
    run = {"peak": {"bf16_flops": 100.0, "hbm_bytes_per_s": 10.0},
           "work": {"helix_train_step": {scope: (50.0, 2.0),
                                         "head": (20.0, 1.0)}},
           "trace": {"scopes": {"helix_train_step": {
               "runs": 2, "seconds": {scope: 4.0, "head": 1.0}}}}}
    read = harness.load_readers([name])[name]
    # max(50/100, 2/10) = 0.5 s a run, 2 runs in 4 s.
    assert read(run) == pytest.approx(25.0)
    assert read(dict(run, trace=None)) is None
