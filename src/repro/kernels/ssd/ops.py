"""Jit'd SSD wrapper: Pallas intra-chunk kernel + XLA inter-chunk scan."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .ssd import ssd_chunk_pallas
from .. import interpret_mode


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssd(x, dt, a, B, C, *, chunk: int = 128, h0=None):
    """Full SSD with the quadratic part in Pallas.

    x: (b,S,H,P); dt: (b,S,H) fp32 (post-softplus); a: (H,) fp32 (negative);
    B, C: (b,S,N). Returns (y (b,S,H,P) fp32, h_final (b,H,P,N) fp32).
    """
    bsz, S, H, P = x.shape
    N = B.shape[-1]
    L = chunk
    S_orig = S
    if S % L:
        pad = L - S % L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        S += pad
    nc = S // L

    da = (dt * a).reshape(bsz, nc, L, H)
    cs = jnp.cumsum(da, axis=2).reshape(bsz, S, H)       # within-chunk

    y_intra, states = ssd_chunk_pallas(
        x, dt, cs, B, C, chunk=L, interpret=interpret_mode())

    # inter-chunk scan over boundary states
    seg = jnp.exp(cs.reshape(bsz, nc, L, H)[:, :, -1, :])  # (b,nc,H)
    # kernel returns states as (b,nc,H,N,P): transpose to (b,nc,H,P,N)
    states = jnp.swapaxes(states, -1, -2)

    def scan_fn(h, inp):
        s_c, g_c = inp
        return h * g_c[:, :, None, None] + s_c, h

    if h0 is None:
        h0 = jnp.zeros((bsz, H, P, N), jnp.float32)
    h_final, h_prev = jax.lax.scan(
        scan_fn, h0.astype(jnp.float32),
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(seg, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                   # (b,nc,H,P,N)

    # inter-chunk output: y_inter[t] = exp(cs_t) · C_t · h_prev(chunk(t))
    Cc = C.reshape(bsz, nc, L, N).astype(jnp.float32)
    y_inter = jnp.einsum("bcln,bchpn->bclhp", Cc, h_prev) \
        * jnp.exp(cs.reshape(bsz, nc, L, H))[..., None]
    y = y_intra + y_inter.reshape(bsz, S, H, P)
    return y[:, :S_orig], h_final
