"""Dense decoder with grouped-query attention (InternLM2 / LLaMA family),
float32, written from the published description.

Pre-norm blocks: h += Wo·attn(RoPE(Wq x), RoPE(Wk x), Wv x) with a causal
softmax, each of ``num_key_value_heads`` K/V heads serving
``num_attention_heads / num_key_value_heads`` query heads in order; then
h += W_down (silu(W_gate x) * W_up x). RMSNorm before each, and before
the untied LM head. RoPE rotates the two halves of each head (the
``rotate_half`` convention) with base ``rope_theta``.

Parameters are stacked over layers under ``blocks`` (leading dim L);
layers run under ``lax.scan`` with each block rematerialized, so the
backward pass holds one layer's activations at a time.

Work counts (``scope_work``) follow the program's named scopes:
``embed``, ``norm``, ``attention``, ``mlp``, ``head``, ``loss`` and
``optimizer``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from weights import Leaf

from .common import rmsnorm


def dims(c: dict) -> dict:
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    return dict(d=c["hidden_size"], h=h, kv=kv, hd=c["hidden_size"] // h,
                f=c["intermediate_size"], v=c["vocab_size"],
                n=c["num_hidden_layers"])


def vocab(c: dict) -> int:
    return c["vocab_size"]


def param_spec(c: dict) -> dict:
    g = dims(c)
    d, n = g["d"], g["n"]
    std = ("normal", c["initializer_range"])
    mat = lambda *s: Leaf(s, "bfloat16", std)  # noqa: E731
    vec = lambda *s: Leaf(s, "float32", ("ones",))  # noqa: E731
    return {
        "embed": mat(g["v"], d),
        "final_norm": vec(d),
        "lm_head": mat(d, g["v"]),
        "blocks/ln1": vec(n, d),
        "blocks/attn/wq": mat(n, d, g["h"], g["hd"]),
        "blocks/attn/wk": mat(n, d, g["kv"], g["hd"]),
        "blocks/attn/wv": mat(n, d, g["kv"], g["hd"]),
        "blocks/attn/wo": mat(n, g["h"], g["hd"], d),
        "blocks/ln2": vec(n, d),
        "blocks/mlp/w_gate": mat(n, d, g["f"]),
        "blocks/mlp/w_up": mat(n, d, g["f"]),
        "blocks/mlp/w_down": mat(n, g["f"], d),
    }


def _rope(x, theta: float):
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = jnp.asarray(np.arange(s)[:, None] * inv[None, :], jnp.float32)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _flops_per_token(c: dict, seq: int) -> dict:
    """Model FLOPs of one token's forward pass at context ``seq``, per
    scope. Counted: every matmul of the model as published (2 FLOPs per
    multiply-add), causal attention over the lower triangle only, and the
    LM head. Not counted: the embedding lookup (a gather, whatever the
    program makes of it), norms, activations, the optimizer, and any
    recomputation."""
    d, f = c["hidden_size"], c["intermediate_size"]
    h, kv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = d // h
    attention = (2 * d * h * hd            # q
                 + 2 * 2 * d * kv * hd     # k, v
                 + 2 * h * hd * d          # o
                 + 2 * 2 * h * hd * (seq + 1) / 2)   # QK^T and PV, causal
    n = c["num_hidden_layers"]
    return {"attention": n * attention,
            "mlp": n * 3 * 2 * d * f,      # gate, up, down
            "head": 2 * d * c["vocab_size"]}


ACT = 2          # bytes of an activation: the program computes in bfloat16
_SIZE = {"bfloat16": 2, "float32": 4}
_SCOPE_OF = (("blocks/attn/", "attention"), ("blocks/mlp/", "mlp"),
             ("blocks/ln", "norm"), ("final_norm", "norm"),
             ("lm_head", "head"), ("embed", "embed"))


def _weight_bytes(c: dict) -> dict:
    out: dict = {}
    for path, leaf in param_spec(c).items():
        scope = next(s for p, s in _SCOPE_OF if path.startswith(p))
        out[scope] = out.get(scope, 0) + (int(np.prod(leaf.shape))
                                          * _SIZE[leaf.dtype])
    return out


def scope_work(c: dict, program: str, batch: int, seq: int) -> dict:
    """``{scope: (flops, bytes)}`` of one run of ``program`` over
    ``batch`` x ``seq`` tokens, per named scope of the program.

    FLOPs are model FLOPs as :func:`_flops_per_token` counts them, by
    scope (attention, mlp, head; the others do no matmul of the model);
    training is 3x the forward. Their sum is the program's count in
    ``flops.py``. The program rematerializes each block's forward pass
    in the backward one; that recomputation is not counted, so its time
    lowers a scope's roofline share.

    Bytes are a lower bound on compulsory HBM traffic in the stored
    dtypes (matrices bfloat16, norm weights float32, activations
    bfloat16, token ids int32, Adam moments float32). Forward: a scope's
    weights read once (the embedding: only the rows looked up), its
    input and output activations (the head's output is the logits; the
    loss reads the logits and the targets, and the eval program writes
    float32 NLLs). Backward adds, per scope, the weights read again (not
    the embedding's), the weight gradients written in the weights' dtype
    (the embedding's whole table), the output gradient read and the input
    gradient written (the loss writes the logits' gradient). Optimizer:
    per parameter, the parameter, both moments and the gradient read
    (the gradient in the parameter's dtype), the parameter and both
    moments written. Left out: anything a fused or blocked kernel need
    not send to HBM (attention scores, the MLP's hidden activations),
    rematerialization, the eval program's reading of the weights once
    per row, and the optimizer's second pass over the gradients for the
    clipping norm.
    """
    g = dims(c)
    d, v, n = g["d"], g["v"], g["n"]
    t = batch * seq                    # tokens
    nt = batch * (seq - 1)             # predicted tokens
    act = t * d * ACT                  # one (B, S, d) activation
    w = _weight_bytes(c)
    fl = {s: x * t for s, x in _flops_per_token(c, seq).items()}
    fwd = {
        "embed": (0.0, t * 4 + t * d * _SIZE["bfloat16"] + act),
        "norm": (0.0, w["norm"] + (2 * n + 1) * 2 * act),
        "attention": (fl["attention"], w["attention"] + n * 2 * act),
        "mlp": (fl["mlp"], w["mlp"] + n * 2 * act),
        "head": (fl["head"], w["head"] + act + t * v * ACT),
        "loss": (0.0, nt * v * ACT + nt * 4),
    }
    if program == "helix_eval_nll":
        fwd["loss"] = (0.0, fwd["loss"][1] + nt * 4)   # the NLLs written
        return fwd
    if program != "helix_train_step":
        raise ValueError(f"no work count for program {program!r}")
    bwd = {
        "embed": w["embed"] + act,
        "norm": 2 * w["norm"] + (2 * n + 1) * 2 * act,
        "attention": 2 * w["attention"] + n * 2 * act,
        "mlp": 2 * w["mlp"] + n * 2 * act,
        "head": 2 * w["head"] + t * v * ACT + act,
        "loss": nt * v * ACT,
    }
    out = {s: (3 * f, b + bwd[s]) for s, (f, b) in fwd.items()}
    opt = sum(int(np.prod(leaf.shape)) * (3 * _SIZE[leaf.dtype] + 2 * 2 * 4)
              for leaf in param_spec(c).values())
    out["optimizer"] = (0.0, float(opt))
    return out


def forward(c: dict, params: dict, tokens, mm):
    """Logits (B, S, V), float32."""
    g = dims(c)
    eps, theta = c["rms_norm_eps"], float(c["rope_theta"])
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, p):
        x = rmsnorm(h, p["ln1"], eps)
        a = p["attn"]
        q = _rope(mm("bsd,dhk->bshk", x, a["wq"]), theta)
        k = _rope(mm("bsd,dhk->bshk", x, a["wk"]), theta)
        v = mm("bsd,dhk->bshk", x, a["wv"])
        rep = g["h"] // g["kv"]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = mm("bqhk,bshk->bhqs", q, k) / np.sqrt(g["hd"])
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
        o = mm("bhqs,bshk->bqhk", probs, v)
        h = h + mm("bqhk,hkd->bqd", o, a["wo"])
        x = rmsnorm(h, p["ln2"], eps)
        m = p["mlp"]
        up = jax.nn.silu(mm("bsd,df->bsf", x, m["w_gate"])) * mm(
            "bsd,df->bsf", x, m["w_up"])
        return h + mm("bsf,fd->bsd", up, m["w_down"]), None

    h = params["embed"][tokens]
    h, _ = jax.lax.scan(jax.checkpoint(layer), h, params["blocks"])
    h = rmsnorm(h, params["final_norm"], eps)
    return mm("bsd,dv->bsv", h, params["lm_head"])
