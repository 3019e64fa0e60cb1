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
