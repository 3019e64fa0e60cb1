"""Mamba-2 SSD intra-chunk kernel for TPU via Pallas.

The SSD decomposition makes the per-chunk work three MXU matmuls; this
kernel computes, for one (batch, chunk, head) grid cell with VMEM tiles
of chunk length L:

    y_intra = ((C Bᵀ) ∘ causal-decay) (X ∘ dt)            (L×L quadratic part)
    state   = Bᵀ (X ∘ dt ∘ decay-to-end)                  (chunk boundary state)

The cumulative log-decay ``cs = cumsum(dt·a)`` is precomputed outside (a
cheap elementwise pass) so the kernel body is pure matmul + exp — Mosaic
has no cumsum primitive.

Layout: per-head operands are head-major with batch and head squeezed out
of each block, so every block's last two dims satisfy the TPU's (8, 128)
tiling rule: x is an (L, P) tile, dt and cs arrive as (L, 1) columns, and
cs once more as a (1, L) row for the pairwise decay.

The inter-chunk state scan (O(S/L) sequential) and the rank-1 inter-chunk
output correction stay in XLA (ops.py): they are bandwidth-trivial compared
to the quadratic part.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, dt_ref, cs_ref, csr_ref, b_ref, c_ref, y_ref, st_ref, *,
            L: int):
    x = x_ref[...].astype(jnp.float32)               # (L, P)
    dt = dt_ref[...]                                 # (L, 1) f32
    cs = cs_ref[...]                                 # (L, 1) f32 cumulative
    cs_row = csr_ref[...]                            # (1, L) the same cs
    B = b_ref[...].astype(jnp.float32)               # (L, N)
    C = c_ref[...].astype(jnp.float32)               # (L, N)

    # causal decay matrix: exp(cs_i - cs_j) for i >= j else 0
    ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
    decay = jnp.where(ii >= jj, jnp.exp(cs - cs_row), 0.0)   # (L, L)

    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (L, L)
    y = jax.lax.dot_general(cb * decay, x * dt, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)   # (L, P)
    y_ref[...] = y.astype(y_ref.dtype)

    # chunk state: Bᵀ (x ∘ dt ∘ decay-to-end)  → (N, P)
    dte = dt * jnp.exp(cs[L - 1:L, :] - cs)          # (L, 1)
    st_ref[...] = jax.lax.dot_general(
        B, x * dte, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)          # (N, P)


def ssd_chunk_pallas(x: jax.Array, dt: jax.Array, cs: jax.Array,
                     B: jax.Array, C: jax.Array, *, chunk: int,
                     interpret: bool) -> tuple[jax.Array, jax.Array]:
    """Intra-chunk SSD.

    x:  (batch, S, H, P)      dt, cs: (batch, S, H) fp32
    B, C: (batch, S, N)       S % chunk == 0
    Returns (y_intra (batch,S,H,P) fp32, states (batch, nc, H, N, P) fp32).
    """
    bsz, S, H, P = x.shape
    N = B.shape[-1]
    L = chunk
    assert S % L == 0
    nc = S // L

    xt = jnp.swapaxes(x, 1, 2)                       # (b, H, S, P)
    dt_col = jnp.swapaxes(dt, 1, 2)[..., None]       # (b, H, S, 1)
    cs_col = jnp.swapaxes(cs, 1, 2)[..., None]       # (b, H, S, 1)
    cs_row = jnp.swapaxes(cs, 1, 2)[:, :, None, :]   # (b, H, 1, S)

    sqz = pl.Squeezed()
    col = pl.BlockSpec((sqz, sqz, L, 1), lambda bi, ci, hi: (bi, hi, ci, 0))
    bc = pl.BlockSpec((sqz, L, N), lambda bi, ci, hi: (bi, ci, 0))
    kernel = functools.partial(_kernel, L=L)
    y, states = pl.pallas_call(
        kernel,
        grid=(bsz, nc, H),
        in_specs=[
            pl.BlockSpec((sqz, sqz, L, P), lambda bi, ci, hi: (bi, hi, ci, 0)),
            col,
            col,
            pl.BlockSpec((sqz, sqz, 1, L), lambda bi, ci, hi: (bi, hi, 0, ci)),
            bc,
            bc,
        ],
        out_specs=[
            pl.BlockSpec((sqz, sqz, L, P), lambda bi, ci, hi: (bi, hi, ci, 0)),
            pl.BlockSpec((sqz, sqz, sqz, N, P),
                         lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, H, S, P), jnp.float32),
            jax.ShapeDtypeStruct((bsz, nc, H, N, P), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        interpret=interpret,
    )(xt, dt_col, cs_col, cs_row, B, C)
    return jnp.swapaxes(y, 1, 2), states
