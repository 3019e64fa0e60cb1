"""Decoder-only LM covering the dense / MoE / SSM / hybrid / VLM families.

Layer stacks are *scanned* (stacked parameter pytrees + ``jax.lax.scan``)
so 64-layer models compile fast and remat policies apply per block.
Heterogeneity is handled without unrolling:

  * gemma3's 5:1 local:global pattern → the per-layer window is **data**
    (an int32 array scanned alongside the layer params), keeping one
    homogeneous scan;
  * jamba's [7×mamba + 1×attn] × 4 with MoE on odd layers → scan over
    *groups*: the group structure is identical, so group params stack.

Caches: attention layers use (k, v) ring-written by ``cache_pos``; SSM
layers carry (conv_state, h). ``init_cache`` builds the right pytree per
family; prefill fills it in one forward.
"""
from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from . import layers, moe as moe_lib, ssd as ssd_lib
from .config import ArchConfig
from .params import P, init_params, param_specs
from ..sharding.activation import constrain, batch_axes, placement_mesh


class LMOut(NamedTuple):
    logits: jax.Array
    cache: Any
    aux_loss: jax.Array


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------
def _attn_layer_defs(cfg: ArchConfig) -> dict:
    d = {"ln1": layers.rmsnorm_defs(cfg.d_model),
         "attn": layers.attention_defs(cfg)}
    d.update(_ffn_defs(cfg, is_moe=cfg.moe is not None
             and cfg.moe.every_k_layers == 1))
    return d


def _ffn_defs(cfg: ArchConfig, is_moe: bool) -> dict:
    if is_moe:
        return {"ln2": layers.rmsnorm_defs(cfg.d_model),
                "moe": moe_lib.moe_defs(cfg.d_model, cfg.moe)}
    if cfg.d_ff:
        return {"ln2": layers.rmsnorm_defs(cfg.d_model),
                "mlp": layers.mlp_defs(cfg.d_model, cfg.d_ff)}
    return {}


def _ssm_layer_defs(cfg: ArchConfig, with_ffn: bool, is_moe: bool) -> dict:
    d = {"ln1": layers.rmsnorm_defs(cfg.d_model),
         "ssm": ssd_lib.ssm_defs(cfg.d_model, cfg.ssm)}
    if with_ffn:
        d.update(_ffn_defs(cfg, is_moe))
    return d


def _stack(defs: Any, n: int) -> Any:
    """Prepend a scanned 'layers' dim to every P leaf."""
    return jax.tree_util.tree_map(
        lambda p: P((n,) + p.shape, ("layers",) + p.axes, p.init,
                    p.scale, p.dtype),
        defs, is_leaf=lambda x: isinstance(x, P))


def _group_defs(cfg: ArchConfig) -> list[dict]:
    """Jamba-style group of ``attn_every`` layers (SSM…SSM, attn last)."""
    out = []
    for i in range(cfg.attn_every):
        is_moe = cfg.layer_is_moe(i)
        if i == cfg.attn_every - 1:
            d = {"ln1": layers.rmsnorm_defs(cfg.d_model),
                 "attn": layers.attention_defs(cfg)}
            d.update(_ffn_defs(cfg, is_moe))
        else:
            d = _ssm_layer_defs(cfg, with_ffn=True, is_moe=is_moe)
        out.append(d)
    return out


def param_defs(cfg: ArchConfig) -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    defs: dict = {
        "embed": P((v, d), ("vocab", "embed")),
        "final_norm": layers.rmsnorm_defs(d),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = P((d, v), ("embed", "vocab"))
    if cfg.family == "hybrid":
        n_groups = cfg.num_layers // cfg.attn_every
        defs["groups"] = _stack(_group_defs(cfg), n_groups)
    elif cfg.family == "ssm":
        defs["blocks"] = _stack(
            _ssm_layer_defs(cfg, with_ffn=bool(cfg.d_ff),
                            is_moe=False), cfg.num_layers)
    else:  # dense / moe / vlm
        defs["blocks"] = _stack(_attn_layer_defs(cfg), cfg.num_layers)
    return defs


def init(cfg: ArchConfig, key: jax.Array) -> dict:
    return init_params(param_defs(cfg), key)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------
def _window_groups(cfg: ArchConfig) -> tuple[int, int, int]:
    """(n_full_groups, group_size, n_tail_local) for window_cache mode."""
    g = cfg.global_every
    n_groups = cfg.num_layers // g
    tail = cfg.num_layers - n_groups * g
    return n_groups, g, tail


def init_cache(cfg: ArchConfig, batch: int, max_len: int) -> dict:
    kvh, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    kv_shape = (batch, max_len, kvh, hd)
    if cfg.window_cache and cfg.window is not None and cfg.global_every:
        ng, g, tail = _window_groups(cfg)
        w = min(cfg.window, max_len)
        neg = -(1 << 30)
        return {
            # local layers: ring buffers of `window` slots + absolute positions
            "kl": jnp.zeros((ng, g - 1, batch, w, kvh, hd), jnp.bfloat16),
            "vl": jnp.zeros((ng, g - 1, batch, w, kvh, hd), jnp.bfloat16),
            "kpl": jnp.full((ng, g - 1, batch, w), neg, jnp.int32),
            # global layers: full-length caches
            "kg": jnp.zeros((ng, 1) + kv_shape, jnp.bfloat16),
            "vg": jnp.zeros((ng, 1) + kv_shape, jnp.bfloat16),
            # tail local layers (num_layers % global_every)
            "kt": jnp.zeros((tail, batch, w, kvh, hd), jnp.bfloat16),
            "vt": jnp.zeros((tail, batch, w, kvh, hd), jnp.bfloat16),
            "kpt": jnp.full((tail, batch, w), neg, jnp.int32),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "hybrid":
        n_groups = cfg.num_layers // cfg.attn_every
        n_ssm = cfg.attn_every - 1
        conv, h = ssd_lib.init_ssm_state(cfg, cfg.ssm, batch)
        return {
            "k": jnp.zeros((n_groups,) + kv_shape, jnp.bfloat16),
            "v": jnp.zeros((n_groups,) + kv_shape, jnp.bfloat16),
            "conv": jnp.zeros((n_groups, n_ssm) + conv.shape, conv.dtype),
            "h": jnp.zeros((n_groups, n_ssm) + h.shape, h.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    if cfg.family == "ssm":
        conv, h = ssd_lib.init_ssm_state(cfg, cfg.ssm, batch)
        return {
            "conv": jnp.zeros((cfg.num_layers,) + conv.shape, conv.dtype),
            "h": jnp.zeros((cfg.num_layers,) + h.shape, h.dtype),
            "pos": jnp.zeros((), jnp.int32),
        }
    return {
        "k": jnp.zeros((cfg.num_layers,) + kv_shape, jnp.bfloat16),
        "v": jnp.zeros((cfg.num_layers,) + kv_shape, jnp.bfloat16),
        "pos": jnp.zeros((), jnp.int32),
    }


def _windows_array(cfg: ArchConfig) -> jnp.ndarray:
    return jnp.asarray(
        [cfg.layer_window(i) if cfg.layer_window(i) is not None
         else layers.GLOBAL_WINDOW for i in range(cfg.num_layers)],
        jnp.int32)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def vocab_split(cfg: ArchConfig) -> bool:
    """Whether the embedding table's vocab axis is split over more than one
    device: its spec resolved against the mesh the shardings come from
    (no mesh: whole on one device)."""
    mesh = placement_mesh()
    if mesh is None:
        return False
    spec = param_specs(P((cfg.vocab_size, cfg.d_model), ("vocab", "embed")),
                       mesh)
    return len(spec) > 0 and spec[0] is not None


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gather_rows(rows: int, table: jax.Array, tokens: jax.Array
                 ) -> jax.Array:
    return table[tokens]


def _gather_rows_fwd(rows, table, tokens):
    return table[tokens], tokens


def _gather_rows_bwd(rows, tokens, g):
    # Duplicate ids sum in float32 and each row rounds once, as the one-hot
    # matmul's float32 accumulator does; a scatter-add in the table's dtype
    # would round after every duplicate. Sorting the ids first sums them
    # over the batch's rows and writes each distinct row once: on a v5e
    # half the time of a float32 scatter-add into a zero (rows, d) table.
    d = g.shape[-1]
    ids = tokens.reshape(-1)
    n = ids.shape[0]
    order = jnp.argsort(ids)
    ids = ids[order]
    first = jnp.concatenate([jnp.ones((1,), bool), ids[1:] != ids[:-1]])
    seg = jnp.cumsum(first, dtype=jnp.int32) - 1
    sums = jax.ops.segment_sum(
        g.reshape(n, d)[order].astype(jnp.float32), seg, num_segments=n,
        indices_are_sorted=True)
    # Each segment's id; segments past the last keep the out-of-range id
    # ``rows`` and are dropped.
    seg_ids = jnp.full((n,), rows, ids.dtype).at[seg].set(ids)
    grad = jnp.zeros((rows, d), g.dtype).at[seg_ids].set(
        sums.astype(g.dtype), mode="drop", unique_indices=True)
    return grad, None


_gather_rows.defvjp(_gather_rows_fwd, _gather_rows_bwd)


def embed_lookup(cfg: ArchConfig, table: jax.Array, tokens: jax.Array
                 ) -> jax.Array:
    """Embedding lookup: a row gather wherever the table's vocab axis is
    whole on each device. With the vocab axis split (``vocab_split``),
    "onehot" expresses the lookup as a one-hot matmul — the one-hot fuses
    into the dot, and the sharded table contracts with a psum instead of
    XLA's replicate-the-table sharded-gather fallback."""
    table = table.astype(jnp.bfloat16)
    if cfg.embed_impl == "onehot" and vocab_split(cfg):
        oh = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=jnp.bfloat16)
        return oh @ table
    return _gather_rows(table.shape[0], table, tokens)


def _maybe_remat(fn, cfg: ArchConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(fn)  # "block": save only carries


def forward(cfg: ArchConfig, params: dict, tokens: jax.Array, *,
            positions: jax.Array | None = None,
            vision_embeds: jax.Array | None = None,
            mrope_positions: jax.Array | None = None,
            cache: dict | None = None) -> LMOut:
    """Token forward. tokens: (B, S) int32.

    With ``cache``: writes K/V (or SSM state) at ``cache['pos']`` and
    returns the updated cache — S == 1 is the decode step, S > 1 prefill.
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        h = embed_lookup(cfg, params["embed"], tokens)
    if vision_embeds is not None:
        npatch = vision_embeds.shape[1]
        h = jnp.concatenate(
            [vision_embeds.astype(h.dtype), h[:, npatch:]], axis=1)
    base = cache["pos"] if cache is not None else jnp.zeros((), jnp.int32)
    if positions is None:
        positions = base[None, None] + jnp.broadcast_to(
            jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    h = constrain(h, batch_axes(), None, None)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.family == "hybrid":
        h, new_cache, aux = _hybrid_stack(cfg, params, h, positions, cache)
    elif cfg.family == "ssm":
        h, new_cache, aux = _ssm_stack(cfg, params, h, positions, cache)
    elif (cfg.window_cache and cache is not None and cfg.window is not None
          and cfg.global_every):
        h, new_cache, aux = _windowed_stack(cfg, params, h, positions, cache)
    else:
        h, new_cache, aux = _attn_stack(cfg, params, h, positions, cache,
                                        mrope_positions)
    aux = aux + aux0

    with jax.named_scope("norm"):
        h = layers.rmsnorm(h, params["final_norm"], cfg.norm_eps)
    head = (params["embed"].T if cfg.tie_embeddings
            else params["lm_head"])
    with jax.named_scope("head"):
        logits = jnp.einsum("bsd,dv->bsv", h, head.astype(h.dtype))
    logits = constrain(logits, batch_axes(), None,
                       None if "model" in batch_axes() else "model")
    if new_cache is not None and cache is not None:
        new_cache["pos"] = base + s
    return LMOut(logits=logits, cache=new_cache, aux_loss=aux)


# --- homogeneous attention stack (dense / moe / vlm / gemma3) ----------------
def _attn_stack(cfg, params, h, positions, cache, mrope_positions):
    windows = _windows_array(cfg)
    has_cache = cache is not None
    base = cache["pos"] if has_cache else None
    is_moe = cfg.moe is not None and cfg.moe.every_k_layers == 1

    def body(carry, xs):
        h, aux = carry
        if has_cache:
            p, window, kc, vc = xs
        else:
            p, window = xs
            kc = vc = None
        with jax.named_scope("norm"):
            x = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
        with jax.named_scope("attention"):
            attn_out, new_kv = layers.attn_block(
                cfg, p["attn"], x, positions, window=window,
                kv_cache=(kc, vc) if has_cache else None,
                cache_pos=base if has_cache else None,
                mrope_positions=mrope_positions)
        h = h + attn_out
        with jax.named_scope("norm"):
            x = layers.rmsnorm(h, p["ln2"], cfg.norm_eps)
        if is_moe:
            moe_fn = {"shard_map": moe_lib.moe_block_sharded,
                         "a2a": moe_lib.moe_block_a2a}.get(
                             cfg.moe_impl, moe_lib.moe_block)
            with jax.named_scope("mlp"):
                ffn_out, a = moe_fn(cfg.moe, p["moe"], x)
            aux = aux + a
        else:
            with jax.named_scope("mlp"):
                ffn_out = layers.mlp_block(p["mlp"], x)
        h = h + ffn_out
        h = constrain(h, batch_axes(), None, None)
        if has_cache:
            return (h, aux), (new_kv[0], new_kv[1])
        return (h, aux), None

    body = _maybe_remat(body, cfg)
    init_carry = (h, jnp.zeros((), jnp.float32))
    if has_cache:
        xs = (params["blocks"], windows, cache["k"], cache["v"])
        (h, aux), (ks, vs) = jax.lax.scan(body, init_carry, xs, unroll=cfg.unroll)
        new_cache = {"k": ks, "v": vs, "pos": cache["pos"]}
    else:
        xs = (params["blocks"], windows)
        (h, aux), _ = jax.lax.scan(body, init_carry, xs, unroll=cfg.unroll)
        new_cache = None
    return h, new_cache, aux


# --- pure SSM stack (mamba2) ---------------------------------------------------
def _ssm_stack(cfg, params, h, positions, cache):
    has_cache = cache is not None
    has_ffn = bool(cfg.d_ff)

    def body(carry, xs):
        h, aux = carry
        if has_cache:
            p, conv, hst = xs
            state = (conv, hst)
        else:
            p, = xs
            state = None
        with jax.named_scope("norm"):
            x = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
        out, new_state = ssd_lib.ssm_block(cfg, cfg.ssm, p["ssm"], x, state)
        h = h + out
        if has_ffn:
            h = h + layers.mlp_block(p["mlp"],
                                     layers.rmsnorm(h, p["ln2"], cfg.norm_eps))
        h = constrain(h, batch_axes(), None, None)
        ys = new_state if has_cache else None
        return (h, aux), ys

    body = _maybe_remat(body, cfg)
    init_carry = (h, jnp.zeros((), jnp.float32))
    if has_cache:
        xs = (params["blocks"], cache["conv"], cache["h"])
        (h, aux), (convs, hs) = jax.lax.scan(body, init_carry, xs, unroll=cfg.unroll)
        new_cache = {"conv": convs, "h": hs, "pos": cache["pos"]}
    else:
        (h, aux), _ = jax.lax.scan(body, init_carry, (params["blocks"],), unroll=cfg.unroll)
        new_cache = None
    return h, new_cache, aux


# --- windowed group stack (gemma3 window_cache mode) -------------------------
def _windowed_stack(cfg, params, h, positions, cache):
    """Groups of [ (global_every−1) × local-ring, 1 × global ] layers, plus a
    tail of local layers — ring caches for locals, full cache for globals."""
    ng, g, tail = _window_groups(cfg)
    base = cache["pos"]
    w = cfg.window

    blocks = params["blocks"]
    main = jax.tree_util.tree_map(
        lambda t: t[:ng * g].reshape((ng, g) + t.shape[1:]), blocks)
    tailp = jax.tree_util.tree_map(lambda t: t[ng * g:], blocks)

    def ffn(p, h, aux):
        x = layers.rmsnorm(h, p["ln2"], cfg.norm_eps)
        if cfg.moe is not None and cfg.moe.every_k_layers == 1:
            moe_fn = {"shard_map": moe_lib.moe_block_sharded,
                         "a2a": moe_lib.moe_block_a2a}.get(
                             cfg.moe_impl, moe_lib.moe_block)
            out, a = moe_fn(cfg.moe, p["moe"], x)
            return h + out, aux + a
        return h + layers.mlp_block(p["mlp"], x), aux

    def body(carry, xs):
        h, aux = carry
        gp, kl, vl, kpl, kg, vg = xs
        new_l = {"k": [], "v": [], "p": []}
        for i in range(g):
            p = jax.tree_util.tree_map(lambda t: t[i], gp)
            x = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
            if i < g - 1:     # local ring layer
                out, (nk, nv, nkp) = layers.attn_block_ring(
                    cfg, p["attn"], x, positions,
                    (kl[i], vl[i], kpl[i]), base, w)
                new_l["k"].append(nk)
                new_l["v"].append(nv)
                new_l["p"].append(nkp)
            else:             # global layer, full cache
                out, new_kv = layers.attn_block(
                    cfg, p["attn"], x, positions, window=None,
                    kv_cache=(kg[0], vg[0]), cache_pos=base)
            h = h + out
            h, aux = ffn(p, h, aux)
        h = constrain(h, batch_axes(), None, None)
        ys = (jnp.stack(new_l["k"]), jnp.stack(new_l["v"]),
              jnp.stack(new_l["p"]),
              new_kv[0][None], new_kv[1][None])
        return (h, aux), ys

    body = _maybe_remat(body, cfg)
    xs = (main, cache["kl"], cache["vl"], cache["kpl"],
          cache["kg"], cache["vg"])
    (h, aux), (kls, vls, kpls, kgs, vgs) = jax.lax.scan(
        body, (h, jnp.zeros((), jnp.float32)), xs, unroll=cfg.unroll)

    kts, vts, kpts = [], [], []
    for i in range(tail):
        p = jax.tree_util.tree_map(lambda t: t[i], tailp)
        x = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
        out, (nk, nv, nkp) = layers.attn_block_ring(
            cfg, p["attn"], x, positions,
            (cache["kt"][i], cache["vt"][i], cache["kpt"][i]), base, w)
        kts.append(nk)
        vts.append(nv)
        kpts.append(nkp)
        h = h + out
        h, aux = ffn(p, h, aux)
    h = constrain(h, batch_axes(), None, None)

    new_cache = {
        "kl": kls, "vl": vls, "kpl": kpls, "kg": kgs, "vg": vgs,
        "kt": (jnp.stack(kts) if tail else cache["kt"]),
        "vt": (jnp.stack(vts) if tail else cache["vt"]),
        "kpt": (jnp.stack(kpts) if tail else cache["kpt"]),
        "pos": cache["pos"],
    }
    return h, new_cache, aux


# --- hybrid group stack (jamba) -------------------------------------------------
def _hybrid_stack(cfg, params, h, positions, cache):
    has_cache = cache is not None
    base = cache["pos"] if has_cache else None
    n_ssm = cfg.attn_every - 1

    def body(carry, xs):
        h, aux = carry
        if has_cache:
            gp, kc, vc, convs, hsts = xs
        else:
            gp, = xs
        new_convs, new_hs = [], []
        for i in range(cfg.attn_every):
            p = gp[i]
            is_moe = cfg.layer_is_moe(i)
            x = layers.rmsnorm(h, p["ln1"], cfg.norm_eps)
            if i < n_ssm:  # SSM sub-layer
                state = (convs[i], hsts[i]) if has_cache else None
                out, new_state = ssd_lib.ssm_block(
                    cfg, cfg.ssm, p["ssm"], x, state)
                if has_cache:
                    new_convs.append(new_state[0])
                    new_hs.append(new_state[1])
            else:          # attention sub-layer
                out, new_kv = layers.attn_block(
                    cfg, p["attn"], x, positions, window=None,
                    kv_cache=(kc, vc) if has_cache else None,
                    cache_pos=base if has_cache else None)
            h = h + out
            x = layers.rmsnorm(h, p["ln2"], cfg.norm_eps)
            if is_moe:
                moe_fn = {"shard_map": moe_lib.moe_block_sharded,
                             "a2a": moe_lib.moe_block_a2a}.get(
                                 cfg.moe_impl, moe_lib.moe_block)
                ffn_out, a = moe_fn(cfg.moe, p["moe"], x)
                aux = aux + a
            else:
                ffn_out = layers.mlp_block(p["mlp"], x)
            h = h + ffn_out
        h = constrain(h, batch_axes(), None, None)
        if has_cache:
            ys = (new_kv[0], new_kv[1],
                  jnp.stack(new_convs), jnp.stack(new_hs))
        else:
            ys = None
        return (h, aux), ys

    body = _maybe_remat(body, cfg)
    init_carry = (h, jnp.zeros((), jnp.float32))
    if has_cache:
        xs = (params["groups"], cache["k"], cache["v"],
              cache["conv"], cache["h"])
        (h, aux), (ks, vs, convs, hs) = jax.lax.scan(body, init_carry, xs, unroll=cfg.unroll)
        new_cache = {"k": ks, "v": vs, "conv": convs, "h": hs,
                     "pos": cache["pos"]}
    else:
        (h, aux), _ = jax.lax.scan(body, init_carry, (params["groups"],), unroll=cfg.unroll)
        new_cache = None
    return h, new_cache, aux
