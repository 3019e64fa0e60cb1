"""Jit'd RMSNorm wrapper (flattens leading dims).

Row counts that don't block run the jnp oracle on the CPU and raise on a
TPU, whose tiling wants blocks of a multiple of 8 rows (or all rows).
"""
from __future__ import annotations

import functools

import jax

from . import ref
from .rmsnorm import rmsnorm_pallas
from .. import interpret_mode


@functools.partial(jax.jit, static_argnames=("eps",))
def rmsnorm(x, w, eps: float = 1e-5):
    shape = x.shape
    n = 1
    for s in shape[:-1]:
        n *= s
    x2 = x.reshape(n, shape[-1])
    br = next((b for b in (256, 128, 64, 32, 16, 8, 4, 2, 1) if n % b == 0))
    interpret = interpret_mode()
    if not interpret and br % 8 and br != n:
        raise ValueError(
            f"rmsnorm: {n} rows do not tile into blocks of 8k rows on the "
            f"TPU")
    if br < 2 and n > 1:
        return ref.rmsnorm_ref(x, w, eps)
    out = rmsnorm_pallas(x2, w, eps=eps, block_rows=br, interpret=interpret)
    return out.reshape(shape)
