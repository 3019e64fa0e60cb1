"""Train / prefill / decode steps for every architecture family.

``train_step`` is the unit the launcher jits onto the mesh:

  * microbatched gradient accumulation via ``lax.scan`` (``cfg.grad_accum``)
    with fp32 accumulators — the psum/reduce-scatter that GSPMD inserts for
    the data axis sits *inside* the scan body, so XLA's latency-hiding
    scheduler overlaps gradient reduction with the next microbatch's compute;
  * global-norm clipping + AdamW (fp32 moments, sharded like params);
  * bf16 gradients on the wire (see optim/compress.py).

``prefill_step`` / ``decode_step`` are the serving units: prefill builds the
KV/SSM cache in one forward; decode advances one token against it.
"""
from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ..models import encdec, lm, registry
from ..models.config import ArchConfig
from ..optim import adamw, schedules


class TrainState(NamedTuple):
    params: Any
    opt: adamw.AdamWState


def init_train_state(cfg: ArchConfig, key: jax.Array) -> TrainState:
    params = registry.init(cfg, key)
    return TrainState(params=params, opt=adamw.init(params))


# ---------------------------------------------------------------------------
# loss
# ---------------------------------------------------------------------------
def _xent(logits: jax.Array, targets: jax.Array, mask: jax.Array,
          impl: str = "gather") -> jax.Array:
    if impl == "onehot":
        # Vocab-sharding-friendly: both reductions contract the (sharded)
        # vocab axis with fused producers — no fp32 logits copy, no gather
        # across vocab shards (a psum appears instead).
        m = jax.lax.stop_gradient(jnp.max(logits, axis=-1, keepdims=True))
        shifted = (logits - m).astype(jnp.float32)
        logz = jnp.log(jnp.sum(jnp.exp(shifted), axis=-1)) + m[..., 0].astype(jnp.float32)
        oh = jax.nn.one_hot(targets, logits.shape[-1], dtype=logits.dtype)
        gold = jnp.einsum("...v,...v->...", logits, oh).astype(jnp.float32)
        nll = (logz - gold) * mask
        return nll.sum() / jnp.maximum(mask.sum(), 1.0)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = (logz - gold) * mask
    return nll.sum() / jnp.maximum(mask.sum(), 1.0)


def loss_fn(cfg: ArchConfig, params: Any, batch: dict) -> tuple[jax.Array, dict]:
    """batch keys: tokens (B,S) [+ frames / vision_embeds / mrope_positions /
    loss_mask]. Next-token LM loss (teacher-forced for enc-dec)."""
    tokens = batch["tokens"]
    mask = batch.get("loss_mask")
    if mask is None:
        mask = jnp.ones(tokens.shape, jnp.float32)
    if cfg.family == "audio":
        out = encdec.forward(cfg, params, batch["frames"], tokens)
    else:
        out = lm.forward(
            cfg, params, tokens,
            vision_embeds=batch.get("vision_embeds"),
            mrope_positions=batch.get("mrope_positions"))
    logits = out.logits[:, :-1]
    targets = tokens[:, 1:]
    with jax.named_scope("loss"):
        loss = _xent(logits, targets, mask[:, 1:], impl=cfg.xent_impl)
    aux = 0.01 * out.aux_loss
    return loss + aux, {"loss": loss, "aux_loss": out.aux_loss}


# ---------------------------------------------------------------------------
# train step
# ---------------------------------------------------------------------------
def train_step(cfg: ArchConfig, state: TrainState, batch: dict, *,
               peak_lr: float = 3e-4, warmup_steps: int = 100,
               total_steps: int = 10_000, clip_norm: float = 1.0
               ) -> tuple[TrainState, dict]:
    accum = max(cfg.grad_accum, 1)

    def split_micro(x):
        b = x.shape[0]
        return x.reshape((accum, b // accum) + x.shape[1:])

    grad_fn = jax.value_and_grad(
        lambda p, mb: loss_fn(cfg, p, mb), has_aux=True)

    if accum == 1:
        (_, metrics), grads = grad_fn(state.params, batch)
        grads = jax.tree_util.tree_map(
            lambda g: g.astype(jnp.float32), grads)
    else:
        micro = {}
        for k, v in batch.items():
            if k == "mrope_positions":   # (3, B, S) → (accum, 3, B/a, S)
                micro[k] = jnp.moveaxis(
                    v.reshape(3, accum, -1, v.shape[-1]), 1, 0)
            else:
                micro[k] = split_micro(v)

        def body(carry, mb):
            acc, metric_acc = carry
            (_, metrics), grads = grad_fn(state.params, mb)
            acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            metric_acc = jax.tree_util.tree_map(
                lambda a, m: a + m / accum, metric_acc, metrics)
            return (acc, metric_acc), None

        zero_grads = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        zero_metrics = {"loss": jnp.zeros((), jnp.float32),
                        "aux_loss": jnp.zeros((), jnp.float32)}
        (grads, metrics), _ = jax.lax.scan(
            body, (zero_grads, zero_metrics), micro, unroll=cfg.unroll)
        grads = jax.tree_util.tree_map(lambda g: g / accum, grads)

    with jax.named_scope("optimizer"):
        grads, gnorm = adamw.clip_by_global_norm(grads, clip_norm)
        # schedule is 1-indexed: step 0 would otherwise get lr == 0
        lr = schedules.warmup_cosine(
            state.opt.step + 1, peak_lr=peak_lr, warmup_steps=warmup_steps,
            total_steps=total_steps)
        new_params, new_opt = adamw.update(state.params, grads, state.opt,
                                           lr=lr)
    metrics = dict(metrics, grad_norm=gnorm, lr=lr,
                   step=new_opt.step.astype(jnp.float32))
    return TrainState(params=new_params, opt=new_opt), metrics


# ---------------------------------------------------------------------------
# serving steps
# ---------------------------------------------------------------------------
def prefill_step(cfg: ArchConfig, params: Any, batch: dict, *,
                 max_len: int) -> tuple[jax.Array, Any]:
    """Build the cache from a full prompt. Returns (last logits, cache)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    if cfg.family == "audio":
        enc_out = encdec.encode(cfg, params, batch["frames"])
        cache = encdec.init_cache(cfg, b, max_len,
                                  enc_len=enc_out.shape[1])
        out = encdec.decode(cfg, params, tokens, enc_out, cache=cache)
    else:
        cache = registry.init_cache(cfg, b, max_len)
        out = lm.forward(
            cfg, params, tokens, cache=cache,
            vision_embeds=batch.get("vision_embeds"),
            mrope_positions=batch.get("mrope_positions"))
    return out.logits[:, -1], out.cache


def decode_step(cfg: ArchConfig, params: Any, token: jax.Array,
                cache: Any) -> tuple[jax.Array, Any]:
    """One token against the cache. token: (B, 1). Returns (logits, cache)."""
    if cfg.family == "audio":
        out = encdec.decode(cfg, params, token, cache["enc_out"], cache=cache)
    else:
        out = lm.forward(cfg, params, token, cache=cache)
    return out.logits[:, 0], out.cache
