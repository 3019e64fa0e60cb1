"""Fused RMSNorm row kernel for TPU via Pallas.

Row-block tiles (br × D) in VMEM; fp32 mean-square reduction; one pass.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu


def _kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps)
                  * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, w: jax.Array, *, eps: float,
                   block_rows: int, interpret: bool) -> jax.Array:
    n, d = x.shape
    assert n % block_rows == 0
    kernel = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kernel,
        grid=(n // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(x, w)
