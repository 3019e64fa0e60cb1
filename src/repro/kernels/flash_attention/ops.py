"""Jit'd wrapper for the flash attention kernel.

Picks MXU-aligned block sizes. Shapes that don't tile (tiny smoke shapes)
run the jnp oracle on the CPU and raise on a TPU; the kernel runs compiled
on a TPU and interpreted on the CPU (see ``repro.kernels.interpret_mode``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ref
from .flash_attention import flash_attention_pallas
from .. import interpret_mode
from ...models import layers as _layers  # GLOBAL_WINDOW sentinel


def _pick_block(s: int, target: int = 512) -> int:
    for cand in (target, 256, 128, 64, 32, 16, 8):
        if s % cand == 0 and cand <= s:
            return cand
    return 0


@functools.partial(jax.jit, static_argnames=("causal", "window"))
def flash_attention(q, k, v, q_offset=None, *, causal: bool = True,
                    window: int = 0):
    """q: (B, Sq, H, D); k/v: (B, Sk, KV, D); q_offset: () or (B,) int32.

    window: 0 or >= GLOBAL_WINDOW → global attention.
    """
    if window >= _layers.GLOBAL_WINDOW:
        window = 0
    if q_offset is None:
        q_offset = jnp.zeros((), jnp.int32)
    bq = _pick_block(q.shape[1])
    bk = _pick_block(k.shape[1])
    interpret = interpret_mode()
    if bq < 8 or bk < 8 or q.shape[-1] % 8:
        if not interpret:
            raise ValueError(
                f"flash attention: q {q.shape} / k {k.shape} do not tile "
                f"into (8k, head_dim) blocks on the TPU")
        return ref.attention_ref(q, k, v, q_offset, causal=causal,
                                 window=window)
    return flash_attention_pallas(
        q, k, v, q_offset, causal=causal, window=window,
        bq=bq, bk=bk, interpret=interpret)
