"""Mamba-2 language model (Dao & Gu, "Transformers are SSMs",
arXiv:2405.21060), float32, written from the published description.

Pre-norm blocks with no MLP: h += out_proj(mixer(RMSNorm(h))), then a
final RMSNorm and the LM head tied to the embedding. The mixer
(``Mamba2``, ngroups 1):

    [z, xBC, dt] = in_proj(x)
    xBC = silu(causal depthwise conv of width d_conv, with bias)
    [x, B, C] = xBC;  dt = softplus(dt + dt_bias);  A = -exp(A_log)
    y = SSD(x, dt, A, B, C) + D * x            (per head)
    y = RMSNorm(y * silu(z)) * norm_weight     (norm_before_gate=False)

SSD is the scalar-decay state-space recurrence, per head and position t:
h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_tᵀ, y_t = C_t h_t. Here it is
computed in the paper's chunked ("state space dual") form: the output
within each chunk as a masked, decayed C·Bᵀ applied to dt·x, each
chunk's final state, the states passed from chunk to chunk, and each
state's contribution to the next chunk's outputs. :func:`ssd` takes any
chunk length (the sequence is padded with dt = 0, which neither decays
nor updates the state).

Departure from the published model, shared with the program: weight
decay reaches every leaf of rank two or more as stored (``common``).
Everything here is float32, the residual stream included, as published;
the program keeps its residual stream in bfloat16.

Parameters are stacked over layers under ``blocks`` (leading dim L);
layers run under ``lax.scan`` with each block rematerialized.

Work counts (``scope_work``) follow the program's named scopes:
``embed``, ``norm``, ``ssm_proj`` (in_proj and out_proj), ``ssd`` (the
rest of the mixer), ``head``, ``loss`` and ``optimizer``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from weights import Leaf

from .common import rmsnorm


def dims(c: dict) -> dict:
    d = c["d_model"]
    di = c["expand"] * d
    h, n = di // c["headdim"], c["d_state"]
    return dict(d=d, di=di, h=h, p=c["headdim"], n=n, k=c["d_conv"],
                conv=di + 2 * n, proj=2 * di + 2 * n + h, v=vocab(c),
                layers=c["n_layer"], chunk=c["chunk_size"])


def vocab(c: dict) -> int:
    """Rows of the embedding: the vocabulary padded to a multiple of
    ``pad_vocab_size_multiple``."""
    m = c["pad_vocab_size_multiple"]
    return -(-c["vocab_size"] // m) * m


def param_spec(c: dict) -> dict:
    g = dims(c)
    d, n = g["d"], g["layers"]
    uni = lambda bound, *s: Leaf(s, "bfloat16",  # noqa: E731
                                 ("uniform", -bound, bound))
    vec = lambda *s: Leaf(s, "float32", ("ones",))  # noqa: E731
    conv = 1 / np.sqrt(g["k"])
    return {
        "embed": Leaf((g["v"], d), "bfloat16",
                      ("normal", c["initializer_range"])),
        "final_norm": vec(d),
        "blocks/ln1": vec(n, d),
        "blocks/ssm/in_proj": uni(1 / np.sqrt(d), n, d, g["proj"]),
        "blocks/ssm/conv_w": uni(conv, n, g["k"], g["conv"]),
        "blocks/ssm/conv_b": uni(conv, n, g["conv"]),
        "blocks/ssm/a_log": Leaf((n, g["h"]), "float32",
                                 ("log_uniform", *c["A_init_range"])),
        "blocks/ssm/dt_bias": Leaf((n, g["h"]), "float32", (
            "inv_softplus_log_uniform", c["dt_min"], c["dt_max"],
            c["dt_init_floor"])),
        "blocks/ssm/d_skip": vec(n, g["h"]),
        "blocks/ssm/norm_w": vec(n, g["di"]),
        "blocks/ssm/out_proj": uni(1 / np.sqrt(g["di"] * n), n, g["di"], d),
    }


def _segsum(a):
    """(..., T) → (..., T, T): the sum of a over (j, i] below the diagonal
    (i ≥ j), -inf above it. Summed from a masked copy rather than as a
    difference of two cumulative sums, which loses digits."""
    t = a.shape[-1]
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1),
                  jnp.broadcast_to(a[..., :, None], a.shape + (t,)), 0.0)
    x = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), x, -jnp.inf)


def ssd(x, dt, a, B, C, chunk: int, mm):
    """The chunked SSD. x (b, s, h, p), dt (b, s, h), a (h,) negative,
    B and C (b, s, n). Returns y (b, s, h, p) and the final state
    (b, h, p, n)."""
    b, s, h, p = x.shape
    pad = -s % chunk
    if pad:
        x, dt, B, C = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                       for v in (x, dt, B, C))
    c = (s + pad) // chunk
    X = (x * dt[..., None]).reshape(b, c, chunk, h, p)
    A = jnp.moveaxis((dt * a).reshape(b, c, chunk, h), 3, 1)   # (b, h, c, l)
    B, C = B.reshape(b, c, chunk, -1), C.reshape(b, c, chunk, -1)
    a_cum = jnp.cumsum(A, axis=-1)
    # Within each chunk.
    y_diag = mm("bcln,bcsn,bhcls,bcshp->bclhp", C, B, jnp.exp(_segsum(A)), X)
    # Each chunk's own final state, then the states passed along.
    to_end = jnp.exp(a_cum[..., -1:] - a_cum)
    states = mm("bcln,bhcl,bclhp->bchpn", B, to_end, X)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    passed = jnp.exp(_segsum(jnp.pad(a_cum[..., -1], [(0, 0), (0, 0),
                                                        (1, 0)])))
    states = mm("bhzc,bchpn->bzhpn", passed, states)
    # Each chunk's incoming state, read out at every position.
    y_off = mm("bcln,bchpn,bhcl->bclhp", C, states[:, :-1], jnp.exp(a_cum))
    y = (y_diag + y_off).reshape(b, c * chunk, h, p)[:, :s]
    return y, states[:, -1]


def _conv(xbc, w, bias):
    """Causal depthwise conv: out_t = bias + sum_j w[K-1-j] * x_{t-j}."""
    k = w.shape[0]
    out = bias + xbc * w[k - 1]
    for j in range(1, k):
        out = out + jnp.pad(xbc, [(0, 0), (j, 0), (0, 0)])[:, :-j] * w[k - 1 - j]
    return out


def forward(c: dict, params: dict, tokens, mm):
    """Logits (B, S, V), float32."""
    g = dims(c)
    eps = c["norm_epsilon"]
    di, n, h = g["di"], g["n"], g["h"]

    def layer(res, p):
        x = rmsnorm(res, p["ln1"], eps)
        s = p["ssm"]
        zxbcdt = mm("bsd,de->bse", x, s["in_proj"])
        z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
        xbc = jax.nn.silu(_conv(xbc, s["conv_w"], s["conv_b"]))
        xs, B, C = jnp.split(xbc, [di, di + n], axis=-1)
        xs = xs.reshape(*xs.shape[:2], h, g["p"])
        dt = jax.nn.softplus(dt + s["dt_bias"])
        y, _ = ssd(xs, dt, -jnp.exp(s["a_log"]), B, C, g["chunk"], mm)
        y = (y + xs * s["d_skip"][:, None]).reshape(*xs.shape[:2], di)
        y = rmsnorm(y * jax.nn.silu(z), s["norm_w"], eps)
        return res + mm("bse,ed->bsd", y, s["out_proj"]), None

    res = params["embed"][tokens]
    res, _ = jax.lax.scan(jax.checkpoint(layer), res, params["blocks"])
    res = rmsnorm(res, params["final_norm"], eps)
    return mm("bsd,vd->bsv", res, params["embed"])


def _flops_per_token(c: dict, seq: int) -> dict:
    """Model FLOPs of one token's forward pass at context ``seq``, per
    scope (2 per multiply-add). ``ssm_proj``: in_proj and out_proj.
    ``ssd``: the chunked form at the config's chunk length L (the
    sequence when shorter): within a chunk C·Bᵀ and its weighted sum over
    dt·x, each over the causal half, (L + 1) / 2 pairs a position; each
    chunk's final state; the incoming state read out at every position.
    ``head``: the tied LM head over every stored row. Not counted: the
    embedding lookup, norms, the conv, dt, the D skip, the gate, the
    states passed from chunk to chunk (a few per sequence), the
    optimizer, and any recomputation."""
    g = dims(c)
    d, di, h, p, n = g["d"], g["di"], g["h"], g["p"], g["n"]
    span = min(g["chunk"], seq)
    ssd_ = (2 * n + 2 * h * p) * (span + 1) / 2 + 2 * (2 * h * p * n)
    return {"ssm_proj": g["layers"] * (2 * d * g["proj"] + 2 * di * d),
            "ssd": g["layers"] * ssd_,
            "head": 2 * d * g["v"]}


ACT = 2          # bytes of an activation: the program computes in bfloat16
_SIZE = {"bfloat16": 2, "float32": 4}
_SCOPE_OF = (("blocks/ssm/in_proj", "ssm_proj"),
             ("blocks/ssm/out_proj", "ssm_proj"), ("blocks/ssm/", "ssd"),
             ("blocks/ln", "norm"), ("final_norm", "norm"),
             ("embed", "head"))


def _weight_bytes(c: dict) -> dict:
    out: dict = {}
    for path, leaf in param_spec(c).items():
        scope = next(s for q, s in _SCOPE_OF if path.startswith(q))
        out[scope] = out.get(scope, 0) + (int(np.prod(leaf.shape))
                                          * _SIZE[leaf.dtype])
    return out


def scope_work(c: dict, program: str, batch: int, seq: int) -> dict:
    """``{scope: (flops, bytes)}`` of one run of ``program`` over
    ``batch`` x ``seq`` tokens, per named scope of the program.

    FLOPs are model FLOPs as :func:`_flops_per_token` counts them;
    training is 3x the forward. The program rematerializes each block's
    forward pass in the backward one; that recomputation is not counted,
    so its time lowers a scope's roofline share.

    Bytes are a lower bound on compulsory HBM traffic in the stored
    dtypes, as the dense family counts them (``gqa_dense.scope_work``).
    Forward: a scope's weights read once, its input and output
    activations. The embedding reads the ids and the rows looked up; the
    head reads the whole tied table and writes the logits; ``ssm_proj``
    reads x and writes [z, xBC, dt] (in_proj), reads y and writes the
    block's output (out_proj); ``ssd`` reads [z, xBC, dt] and writes y,
    what a kernel fusing the conv, the scan, the skip and the gate must
    move, without the L x L decay and score matrices it keeps on chip;
    the loss reads the logits and the targets. Backward adds, per scope,
    the weights read again, the weight gradients written, the output
    gradient read and the input gradient written; the tied table's
    gradient is written once, by the head, and the embedding adds only
    its rows. Optimizer: per parameter, the parameter, both moments and
    the gradient read, the parameter and both moments written. Left out:
    rematerialization, the eval program's reading of the weights once per
    row, and the clipping norm's second pass over the gradients.
    """
    g = dims(c)
    d, v, n = g["d"], g["v"], g["layers"]
    t = batch * seq                    # tokens
    nt = batch * (seq - 1)             # predicted tokens
    act = t * d * ACT                  # one (B, S, d) activation
    proj, y = t * g["proj"] * ACT, t * g["di"] * ACT
    w = _weight_bytes(c)
    fl = {s: x * t for s, x in _flops_per_token(c, seq).items()}
    fwd = {
        "embed": (0.0, t * 4 + t * d * _SIZE["bfloat16"] + act),
        "norm": (0.0, w["norm"] + (n + 1) * 2 * act),
        "ssm_proj": (fl["ssm_proj"], w["ssm_proj"] + n * (2 * act + proj + y)),
        "ssd": (fl["ssd"], w["ssd"] + n * (proj + y)),
        "head": (fl["head"], w["head"] + act + t * v * ACT),
        "loss": (0.0, nt * v * ACT + nt * 4),
    }
    if program == "helix_eval_nll":
        fwd["loss"] = (0.0, fwd["loss"][1] + nt * 4)   # the NLLs written
        return fwd
    if program != "helix_train_step":
        raise ValueError(f"no work count for program {program!r}")
    bwd = {
        "embed": act + t * d * _SIZE["bfloat16"],
        "norm": 2 * w["norm"] + (n + 1) * 2 * act,
        "ssm_proj": 2 * w["ssm_proj"] + n * (2 * act + proj + y),
        "ssd": 2 * w["ssd"] + n * (proj + y),
        "head": 2 * w["head"] + t * v * ACT + act,
        "loss": nt * v * ACT,
    }
    out = {s: (3 * f, b + bwd[s]) for s, (f, b) in fwd.items()}
    opt = sum(int(np.prod(leaf.shape)) * (3 * _SIZE[leaf.dtype] + 2 * 2 * 4)
              for leaf in param_spec(c).values())
    out["optimizer"] = (0.0, float(opt))
    return out
