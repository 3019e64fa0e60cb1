"""The Mamba-2 mixer's gradient through the chunked SSD is finite where
the decay between two positions of a chunk overflows float32 above the
causal diagonal, and equals the gradient through the sequential
recurrence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.ssd import ref as ssd_ref
from repro.models import ssd as ssd_lib
from repro.models.config import SSMCfg
from repro.models.params import init_params

D_MODEL, BATCH, SEQ = 16, 2, 96
SCFG = SSMCfg(d_state=8, head_dim=8, expand=2, d_conv=4, chunk=64)


def _params():
    defs = ssd_lib.ssm_defs(D_MODEL, SCFG)
    p = init_params(defs, jax.random.PRNGKey(0))
    p = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), p)
    # Heads 0-1: dt near 2 and A = -16, dt·|A| ~ 32 a step, so the
    # log-decay across a 64-long chunk reaches ~2000 and its exp above
    # the diagonal is inf in float32. Heads 2-3: dt near 0.018 and A = -1,
    # a decay that leaves every gradient, A's too, well above rounding.
    return dict(p, a_log=jnp.log(jnp.asarray([16.0, 16.0, 1.0, 1.0])),
                dt_bias=jnp.asarray([2.0, 2.0, -4.0, -4.0]))


def _loss(p, x):
    out, _ = ssd_lib.ssm_block(None, SCFG, p, x)
    return jnp.mean(jnp.square(out))


def _sequential(x, dt, a, B, C, chunk, h0=None):
    return ssd_ref.ssd_ref(x, dt, a, B, C, h0=h0)


@pytest.fixture(scope="module")
def inputs():
    x = jax.random.normal(jax.random.PRNGKey(1), (BATCH, SEQ, D_MODEL),
                          jnp.float32)
    return _params(), x


def test_decay_overflows_above_the_diagonal(inputs):
    p, x = inputs
    nheads = p["a_log"].shape[0]
    dt_raw = (x @ p["in_proj"])[..., -nheads:]
    dt = jax.nn.softplus(dt_raw + p["dt_bias"])[:, :SCFG.chunk]
    log_decay = jnp.sum(dt[:, 1:] * jnp.exp(p["a_log"]), axis=1)  # (b, H)
    assert float(jnp.min(log_decay[:, :2])) > np.log(
        np.finfo(np.float32).max)


def test_chunked_gradient_is_finite_and_matches_the_recurrence(
        inputs, monkeypatch):
    p, x = inputs
    loss, grads = jax.value_and_grad(_loss)(p, x)
    assert np.isfinite(float(loss))
    for name, g in grads.items():
        assert np.all(np.isfinite(np.asarray(g))), name
    monkeypatch.setattr(ssd_lib, "ssd_scan_reference", _sequential)
    want_loss, want = jax.value_and_grad(_loss)(p, x)
    np.testing.assert_allclose(float(loss), float(want_loss), rtol=1e-5)
    for name in grads:
        g, w = np.asarray(grads[name]), np.asarray(want[name])
        np.testing.assert_allclose(g, w, rtol=1e-4,
                                   atol=1e-5 * float(np.max(np.abs(w))),
                                   err_msg=name)
