"""Mamba-2 layer via State-Space Duality (SSD, arXiv:2405.21060).

Hardware adaptation: the CUDA reference implements a fused selective
scan, a sequential loop over positions that a TPU runs on its vector
units alone; the SSD *chunked* decomposition does the same work as
matmuls, which the MXU runs:

  * within a chunk of length L: the quadratic "attention-like" form
    Y_intra = ((C Bᵀ) ∘ decay-mask) · (dt ∘ X)              — three matmuls
  * chunk boundary states:  S_c = (B ∘ dt ∘ decay-to-end)ᵀ · X — one matmul
  * across chunks: a cheap associative scan over per-chunk states,
  * inter-chunk contribution: Y_inter = C · S_prev ∘ decay-from-start.

The per-chunk compute is what the Pallas kernel (kernels/ssd) tiles into
VMEM; this module is the composable JAX implementation (also the oracle).

Decode uses the O(1) recurrent form: h ← h·exp(dt·A) + dt·(B ⊗ x).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .config import SSMCfg
from .params import P
from .layers import rmsnorm


def ssm_defs(d_model: int, scfg: SSMCfg) -> dict:
    d_in = scfg.expand * d_model
    nheads = d_in // scfg.head_dim
    ns = scfg.d_state
    # in_proj emits [z (d_in), x (d_in), B (ns), C (ns), dt (nheads)]
    zxbcdt = 2 * d_in + 2 * ns + nheads
    return {
        "in_proj": P((d_model, zxbcdt), ("embed", "ssm_inner")),
        "conv_w": P((scfg.d_conv, d_in + 2 * ns), (None, "ssm_inner")),
        "conv_b": P((d_in + 2 * ns,), ("ssm_inner",), init="zeros"),
        "a_log": P((nheads,), (None,), init="ones", dtype=jnp.float32),
        "dt_bias": P((nheads,), (None,), init="zeros", dtype=jnp.float32),
        "d_skip": P((nheads,), (None,), init="ones", dtype=jnp.float32),
        "norm_w": P((d_in,), ("ssm_inner",), init="ones", dtype=jnp.float32),
        "out_proj": P((d_in, d_model), ("ssm_inner", "embed")),
    }


def _split_proj(scfg: SSMCfg, d_model: int, zxbcdt: jax.Array):
    d_in = scfg.expand * d_model
    ns = scfg.d_state
    nheads = d_in // scfg.head_dim
    z, xbc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in + 2 * ns], axis=-1)
    return z, xbc, dt, d_in, ns, nheads


def _causal_conv(xbc: jax.Array, w: jax.Array, b: jax.Array,
                 state: jax.Array | None = None):
    """Depthwise causal conv, window d_conv. xbc: (B, S, C); w: (K, C).

    Returns (out, new_state) where state is the last K-1 inputs (for decode).
    """
    k = w.shape[0]
    if state is None:
        pad = jnp.zeros((xbc.shape[0], k - 1, xbc.shape[2]), xbc.dtype)
    else:
        pad = state.astype(xbc.dtype)
    xp = jnp.concatenate([pad, xbc], axis=1)                   # (B, S+K-1, C)
    out = sum(xp[:, i:i + xbc.shape[1], :] * w[i] for i in range(k)) + b
    new_state = xp[:, -(k - 1):, :] if k > 1 else None
    return jax.nn.silu(out), new_state


def ssd_scan_reference(x, dt, a, B, C, chunk: int, h0=None):
    """Chunked SSD. Shapes:
      x: (batch, S, H, P)   — P = head_dim
      dt: (batch, S, H)     — positive step sizes (post-softplus)
      a:  (H,)              — negative decay rates (−exp(a_log))
      B, C: (batch, S, N)   — shared across heads (n_groups=1)
      h0: optional initial state (batch, H, P, N)
    Returns (y (batch,S,H,P), h_final (batch,H,P,N)).
    """
    bsz, S, H, Pd = x.shape
    N = B.shape[-1]
    L = chunk
    S_orig = S
    if S % L:
        # Zero-pad to a chunk multiple: dt=0 ⇒ no decay (exp(0)=1) and no
        # state update, so the final state and the first S outputs are exact.
        pad = L - S % L
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B = jnp.pad(B, ((0, 0), (0, pad), (0, 0)))
        C = jnp.pad(C, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    nc = S // L
    xc = x.reshape(bsz, nc, L, H, Pd)
    dtc = dt.reshape(bsz, nc, L, H)
    Bc = B.reshape(bsz, nc, L, N)
    Cc = C.reshape(bsz, nc, L, N)

    da = dtc * a                                   # (b, nc, L, H) negative
    # Within-chunk cumulative sum, as a matmul with a lower-triangular mask:
    # on a TPU jnp.cumsum lowers to a reduce-window quadratic in L, which
    # took 17 ms of a 320 ms mamba2-130m train step on a v5e and which XLA
    # emits without the op's metadata.
    tri = jnp.asarray(np.tril(np.ones((L, L), np.float32)))
    cs = jnp.einsum("ij,bcjh->bcih", tri, da,
                    precision=jax.lax.Precision.HIGHEST)
    seg_end = cs[:, :, -1:, :]                     # total decay per chunk

    # --- intra-chunk (quadratic in L, matmul form) ---------------------------
    # decay(i←j) = exp(cs_i − cs_j) for i ≥ j
    diff = cs[:, :, :, None, :] - cs[:, :, None, :, :]      # (b,nc,L,L,H)
    ii = np.arange(L)
    causal = (ii[:, None] >= ii[None, :])[None, None, :, :, None]
    # Mask before the exp: above the diagonal diff is positive and its exp
    # overflows, and inf times the zero cotangent there is a NaN gradient.
    decay = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)              # (b,nc,L,L)
    w = cb[..., None] * decay * dtc[:, :, None, :, :]       # (b,nc,L,L,H)
    y = jnp.einsum("bcijh,bcjhp->bcihp", w.astype(x.dtype), xc)

    # --- chunk states -----------------------------------------------------------
    decay_to_end = jnp.exp(seg_end - cs)                    # (b,nc,L,H)
    xdt = xc * (dtc * decay_to_end)[..., None].astype(x.dtype)
    states = jnp.einsum("bcln,bclhp->bchpn", Bc, xdt)       # (b,nc,H,P,N)

    # --- inter-chunk scan ---------------------------------------------------------
    seg = jnp.exp(seg_end[:, :, 0, :])                      # (b,nc,H)

    def scan_fn(h, inp):
        s_c, g_c = inp                                      # state, decay
        h_new = h * g_c[:, :, None, None] + s_c
        return h_new, h

    if h0 is None:
        h0 = jnp.zeros(states.shape[:1] + states.shape[2:], jnp.float32)
    h_final, h_prev = jax.lax.scan(
        scan_fn, h0.astype(jnp.float32),
        (jnp.moveaxis(states.astype(jnp.float32), 1, 0),
         jnp.moveaxis(seg, 1, 0)))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                     # (b,nc,H,P,N)

    # --- inter-chunk contribution ---------------------------------------------------
    cdec = jnp.exp(cs)                                      # decay from chunk start
    y_inter = jnp.einsum("bcln,bchpn->bclhp",
                         Cc, h_prev) * cdec[..., None]
    y = y + y_inter.astype(y.dtype)
    return y.reshape(bsz, S, H, Pd)[:, :S_orig], h_final


def ssd_decode_step(x, dt, a, B, C, h):
    """Single-token recurrence. x:(b,H,P) dt:(b,H) B,C:(b,N) h:(b,H,P,N)."""
    g = jnp.exp(dt * a)                                     # (b,H)
    upd = (dt[..., None] * x.astype(jnp.float32))[..., None] \
        * B[:, None, None, :]                               # (b,H,P,N)
    h_new = h * g[..., None, None] + upd
    y = jnp.einsum("bhpn,bn->bhp", h_new, C)
    return y.astype(x.dtype), h_new


def ssm_block(cfg, scfg: SSMCfg, p: dict, x: jax.Array,
              state: tuple | None = None, use_kernel: bool = False):
    """Full Mamba-2 mixer. x: (B, S, D).

    state: None for training/prefill-from-scratch, else
    (conv_state (B, K-1, C), h (B, H, P, N)) for decode (S == 1 uses the
    recurrent path).
    Returns (out (B,S,D), new_state).
    """
    with jax.named_scope("ssm_proj"):
        zxbcdt = x @ p["in_proj"]
    with jax.named_scope("ssd"):
        y, new_state = _mixer(scfg, p, zxbcdt, x.shape[-1], state,
                              use_kernel)
    with jax.named_scope("ssm_proj"):
        return (y.astype(x.dtype) @ p["out_proj"]).astype(x.dtype), new_state


def _mixer(scfg: SSMCfg, p: dict, zxbcdt: jax.Array, d_model: int,
           state: tuple | None, use_kernel: bool):
    """Everything between the two projections: the causal conv, dt, the
    scan, the D skip and the gated norm. Returns (y (B,S,d_in), state)."""
    bsz, S = zxbcdt.shape[:2]
    z, xbc, dt_raw, d_in, ns, nheads = _split_proj(scfg, d_model, zxbcdt)
    a = -jnp.exp(p["a_log"])                                # (H,) negative
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if state is not None and S == 1:
        conv_state, h = state
        # shift conv state, apply conv at last position
        cat = jnp.concatenate([conv_state.astype(xbc.dtype), xbc], axis=1)
        w, b = p["conv_w"], p["conv_b"]
        k = w.shape[0]
        conv_out = sum(cat[:, i + 1 - 1:i + 1 - 1 + 1, :] * w[i]
                       for i in range(k)) + b  # uses last k positions
        conv_out = jax.nn.silu(conv_out)[:, 0]
        new_conv_state = cat[:, -(k - 1):, :]
        xs, B, C = jnp.split(conv_out, [d_in, d_in + ns], axis=-1)
        xh = xs.reshape(bsz, nheads, scfg.head_dim)
        y, h_new = ssd_decode_step(xh, dt[:, 0], a, B, C, h)
        y = y + xh.astype(jnp.float32) * p["d_skip"][:, None]
        y = y.reshape(bsz, 1, d_in)
        new_state = (new_conv_state, h_new)
    else:
        conv_state = state[0] if state is not None else None
        h0 = state[1] if state is not None else None
        conv_out, new_conv_state = _causal_conv(
            xbc, p["conv_w"], p["conv_b"], conv_state)
        xs, B, C = jnp.split(conv_out, [d_in, d_in + ns], axis=-1)
        xh = xs.reshape(bsz, S, nheads, scfg.head_dim)
        if use_kernel:
            from ..kernels.ssd import ops as ssd_ops
            y, h_new = ssd_ops.ssd(xh, dt, a, B, C, chunk=scfg.chunk, h0=h0)
        else:
            y, h_new = ssd_scan_reference(xh, dt, a, B, C, scfg.chunk, h0=h0)
        y = y + (xh.astype(jnp.float32)
                 * p["d_skip"][None, None, :, None]).astype(y.dtype)
        y = y.reshape(bsz, S, d_in)
        new_state = (new_conv_state, h_new)

    y = rmsnorm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                p["norm_w"])
    return y, new_state


def init_ssm_state(cfg, scfg: SSMCfg, batch: int):
    d_in = scfg.expand * cfg.d_model
    nheads = d_in // scfg.head_dim
    conv = jnp.zeros((batch, scfg.d_conv - 1, d_in + 2 * scfg.d_state),
                     jnp.bfloat16)
    h = jnp.zeros((batch, nheads, scfg.head_dim, scfg.d_state), jnp.float32)
    return (conv, h)
