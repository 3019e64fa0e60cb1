"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
from repro.kernels.ssd import ops as ssd_ops, ref as ssd_ref
from repro.models.ssd import ssd_scan_reference

KEY = jax.random.PRNGKey(0)


# ---------------------------------------------------------------- flash attn
FLASH_CASES = [
    # B, Sq, Sk, H, KV, D, causal, window, qoff
    (2, 128, 128, 4, 2, 64, True, 0, 0),
    (1, 256, 256, 8, 8, 32, True, 0, 0),
    (2, 128, 128, 4, 4, 64, True, 16, 0),
    (1, 64, 128, 4, 2, 64, True, 0, 64),
    (2, 128, 128, 2, 1, 128, False, 0, 0),
    (1, 512, 512, 2, 2, 64, True, 128, 0),
]


@pytest.mark.parametrize("case", FLASH_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(case, dtype):
    B, Sq, Sk, H, KV, D, causal, window, qoff = case
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    out = fa_ops.flash_attention(q, k, v, jnp.int32(qoff),
                                 causal=causal, window=window)
    exp = fa_ref.attention_ref(q, k, v, qoff, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=tol)


def test_flash_fallback_on_odd_shapes():
    q = jax.random.normal(KEY, (1, 15, 2, 64))
    k = jax.random.normal(KEY, (1, 15, 2, 64))
    out = fa_ops.flash_attention(q, k, k, causal=True, window=0)
    exp = fa_ref.attention_ref(q, k, k, 0, causal=True, window=0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp), atol=2e-5)


# -------------------------------------------------------------------- SSD
SSD_CASES = [
    # b, S, H, P, N, chunk
    (2, 64, 3, 16, 32, 16),
    (1, 128, 4, 32, 16, 32),
    (2, 48, 2, 16, 8, 16),      # S not a chunk multiple (padding path)
    (1, 96, 8, 8, 8, 32),
]


@pytest.mark.parametrize("case", SSD_CASES)
def test_ssd_kernel_matches_sequential_oracle(case):
    b, S, H, P, N, chunk = case
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B = jax.random.normal(ks[3], (b, S, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S, N)) * 0.5
    y1, h1 = ssd_ops.ssd(x, dt, a, B, C, chunk=chunk)
    y2, h2 = ssd_ref.ssd_ref(x, dt, a, B, C)
    scale = float(jnp.max(jnp.abs(y2))) + 1e-6
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                               atol=1e-4 * max(float(jnp.max(jnp.abs(h2))), 1))


def test_ssd_xla_chunked_matches_oracle():
    b, S, H, P, N, chunk = 2, 64, 3, 16, 32, 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B = jax.random.normal(ks[3], (b, S, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S, N)) * 0.5
    y1, h1 = ssd_scan_reference(x, dt, a, B, C, chunk)
    y2, h2 = ssd_ref.ssd_ref(x, dt, a, B, C)
    scale = float(jnp.max(jnp.abs(y2))) + 1e-6
    np.testing.assert_allclose(np.asarray(y1, np.float32), np.asarray(y2),
                               atol=1e-4 * scale)


def test_ssd_respects_initial_state():
    b, S, H, P, N, chunk = 1, 32, 2, 8, 8, 16
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, S, H)))
    a = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.5)
    B = jax.random.normal(ks[3], (b, S, N)) * 0.5
    C = jax.random.normal(ks[4], (b, S, N)) * 0.5
    h0 = jax.random.normal(ks[5], (b, H, P, N))
    y1, _ = ssd_ops.ssd(x, dt, a, B, C, chunk=chunk, h0=h0)
    y2, _ = ssd_ref.ssd_ref(x, dt, a, B, C, h0=h0)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-3)


# ------------------------------------------------------------------ rmsnorm
@pytest.mark.parametrize("shape", [(8, 128), (3, 5, 64), (257, 96), (1, 8)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(KEY, shape, dtype)
    w = jax.random.normal(KEY, shape[-1:], jnp.float32)
    out = rn_ops.rmsnorm(x, w)
    exp = rn_ref.rmsnorm_ref(x, w)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), atol=2e-2)


# ----------------------------------------------------------- no fallback
def test_interpret_mode_only_on_cpu(monkeypatch):
    import repro.kernels as kernels
    assert kernels.interpret_mode()          # the tests' CPU backend
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "tpu")
    assert not kernels.interpret_mode()
    monkeypatch.setattr(kernels.jax, "default_backend", lambda: "gpu")
    with pytest.raises(RuntimeError, match="'gpu'"):
        kernels.interpret_mode()


@pytest.mark.parametrize("op", ["flash_attention", "rmsnorm"])
def test_untiled_shape_raises_on_tpu(op, monkeypatch):
    """Where the CPU runs the oracle for an untiled shape, a TPU refuses."""
    mod = fa_ops if op == "flash_attention" else rn_ops
    monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    with pytest.raises(ValueError, match="tile"):
        if op == "flash_attention":
            q = jnp.zeros((1, 13, 2, 64))          # 13: no 8k block
            fa_ops.flash_attention(q, q, q, causal=True)
        else:
            rn_ops.rmsnorm(jnp.zeros((263, 64)), jnp.ones((64,)))
