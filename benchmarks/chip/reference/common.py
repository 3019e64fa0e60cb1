"""Plain float32 training and evaluation around a model's forward pass.

Nothing here imports the program. ``exact`` computes every matmul in
float32 at ``Precision.HIGHEST`` (a TPU otherwise rounds float32 operands
to bf16); ``fp8`` is the control: every matmul operand quantized to fp8
with a per-tensor scale, E4M3 forward and E5M2 for the cotangents, the
usual recipe for fp8 training.

A model module supplies ``param_spec(config)``, ``vocab(config)`` and
``forward(config, params, tokens, mm) -> logits``; params are the nested
dict of :mod:`weights`, read as float32. It also counts its family's
work, for ``flops.py`` and the per-scope rooflines: ``scope_work(config,
program, batch, seq) -> {scope: (flops, bytes)}`` for ``program`` in
``helix_train_step`` and ``helix_eval_nll``, one entry per
``jax.named_scope`` the program puts on that family's layers; a
program's model FLOPs are the sum of its entries.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
ROWS = 1        # rows per block: one row's activations at a time


def exact(eq: str, *xs):
    return jnp.einsum(eq, *xs, precision=HIGHEST,
                      preferred_element_type=jnp.float32)


def _quant(x, dtype):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(x.dtype) * scale


@jax.custom_vjp
def _fp8(x):
    return _quant(x, jnp.float8_e4m3fn)


def _fp8_fwd(x):
    return _quant(x, jnp.float8_e4m3fn), None


def _fp8_bwd(_, g):
    return (_quant(g, jnp.float8_e5m2),)


_fp8.defvjp(_fp8_fwd, _fp8_bwd)


def fp8(eq: str, *xs):
    return exact(eq, *[_fp8(x) for x in xs])


MATMULS = {"float32": exact, "fp8": fp8}


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def token_nll(logits, tokens):
    """(B, S-1) next-token negative log-likelihoods, float32."""
    logits = logits[:, :-1].astype(jnp.float32)
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], -1)[..., 0]
    return jax.nn.logsumexp(logits, -1) - gold


def f32(params):
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)


def lr_at(step, opt: dict, total: int):
    """Warm-up then cosine to ``min_lr_ratio`` of the peak; 1-indexed."""
    step = jnp.asarray(step, jnp.float32)
    peak, warm = opt["peak_lr"], opt["warmup_steps"]
    frac = jnp.clip((step - warm) / jnp.maximum(total - warm, 1), 0.0, 1.0)
    cos = peak * (opt["min_lr_ratio"] + (1 - opt["min_lr_ratio"])
                  * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(step < warm, peak * step / jnp.maximum(warm, 1), cos)


def _blocks(tokens):
    b, s = tokens.shape
    return tokens.reshape(b // ROWS, ROWS, s)


def loss_and_grad(model, config, params, tokens, mm, keep=None):
    """Mean next-token loss over the batch and its gradient, summed over
    blocks of ``ROWS`` rows so that one block's activations fit.
    ``keep`` (a row count) plants the half-batch fault: only the first
    ``keep`` rows count, the mean taken over them."""
    if keep is not None:
        tokens = tokens[:keep]
    n = tokens.shape[0] * (tokens.shape[1] - 1)

    def block_loss(p, tok):
        return jnp.sum(token_nll(model.forward(config, p, tok, mm), tok)) / n

    vg = jax.value_and_grad(block_loss)

    def body(carry, tok):
        total, acc = carry
        loss, g = vg(params, tok)
        return (total + loss,
                jax.tree_util.tree_map(jnp.add, acc, g)), None

    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    (loss, grads), _ = jax.lax.scan(body, (jnp.zeros((), jnp.float32), zeros),
                                    _blocks(tokens))
    return loss, grads


def _norms(tree) -> dict:
    from weights import flat
    return {k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in flat(tree).items()}


@functools.partial(jax.jit, static_argnums=(0, 1, 6, 7, 8),
                   donate_argnums=(2, 3, 4))
def _step(model, config_key, params, m, v, step, mm_name, total, keep,
          tokens, opt):
    config = dict(config_key)
    mm = MATMULS[mm_name]
    loss, grads = loss_and_grad(model, config, params, tokens, mm, keep)
    gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                         for g in jax.tree_util.tree_leaves(grads)))
    grads = jax.tree_util.tree_map(
        lambda g: g * jnp.minimum(1.0, opt["clip_norm"]
                                  / jnp.maximum(gnorm, 1e-9)), grads)
    t = step.astype(jnp.float32)
    b1, b2 = opt["b1"], opt["b2"]
    lr = lr_at(step, opt, total)

    def upd(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        delta = (m_ / (1 - b1 ** t)) / (jnp.sqrt(v_ / (1 - b2 ** t))
                                        + opt["eps"])
        if p.ndim >= 2:
            delta = delta + opt["weight_decay"] * p
        return p - lr * delta, m_, v_

    out = jax.tree_util.tree_map(upd, params, grads, m, v)
    pick = lambda i: jax.tree_util.tree_map(  # noqa: E731
        lambda o: o[i], out, is_leaf=lambda x: isinstance(x, tuple))
    return pick(0), pick(1), pick(2), loss, _norms(grads)


def optimizer(sizes) -> dict:
    return {k: v for k, v in dict(
        sizes.adamw, peak_lr=sizes.peak_lr, warmup_steps=sizes.warmup_steps,
        clip_norm=sizes.clip_norm).items() if not isinstance(v, str)}


def train(model, config: dict, sizes, params0: dict, batches, mm_name: str,
          keep: int | None = None) -> dict:
    """``len(batches)`` AdamW steps from ``params0`` (float32 masters).
    Returns each step's loss, the first step's clipped gradient norm per
    leaf (as the optimizer gets it), the parameter change per leaf after
    the last step, and the final params."""
    opt = optimizer(sizes)
    key = _ConfigKey(config)
    params = jax.tree_util.tree_map(jnp.copy, params0)   # the step donates
    m = jax.tree_util.tree_map(jnp.zeros_like, params0)
    v = jax.tree_util.tree_map(jnp.zeros_like, params0)
    losses, grad_norms = [], None
    for i, tok in enumerate(batches):
        params, m, v, loss, gn = _step(
            model, key, params, m, v, jnp.asarray(i + 1, jnp.int32),
            mm_name, len(batches), keep, jnp.asarray(tok),
            opt)
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {k: float(x) for k, x in gn.items()}
    delta = jax.tree_util.tree_map(jnp.subtract, params, params0)
    return {"losses": losses, "grad_norms": grad_norms,
            "update_norms": {k: float(x) for k, x in
                             jax.device_get(_norms(delta)).items()},
            "params": params}


class _ConfigKey:
    """A config dict as a static argument (hash and equality by value)."""

    def __init__(self, config: dict):
        self.config = config
        self._key = repr(sorted(config.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, _ConfigKey) and self._key == other._key

    def __iter__(self):
        return iter(self.config.items())


@functools.partial(jax.jit, static_argnums=(0, 1, 3))
def _eval(model, config_key, params, mm_name, tokens):
    config = dict(config_key)
    mm = MATMULS[mm_name]
    return jax.lax.map(
        lambda tok: token_nll(model.forward(config, params, tok, mm), tok),
        _blocks(tokens)).reshape(
            tokens.shape[0], -1)


def eval_nll(model, config: dict, sizes, params: dict, tokens,
             mm_name: str):
    """Per-token NLL (B, S-1) of ``tokens`` under ``params``."""
    return jax.device_get(_eval(model, _ConfigKey(config), params,
                                mm_name, jnp.asarray(tokens)))
