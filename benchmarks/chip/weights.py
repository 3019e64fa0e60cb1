"""Seeded weights and keys, made by the benchmark (never by the program).

A parameter spec is a flat ``{path: Leaf}`` map, ``path`` being the
``/``-joined keys of the nested parameter dict (``blocks/attn/wq``). Each
leaf's values depend only on the seed, its path and its shape, so the
workflow (which hands the tree to the program) and the reference (which
reads it in float32) build the same numbers independently.
"""
from __future__ import annotations

import dataclasses
import zlib

import numpy as np

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Leaf:
    shape: tuple
    dtype: str                 # "bfloat16" | "float32": as the model stores it
    init: tuple                # ("normal", std) | ("ones",) | ("zeros",)
    #                            ("uniform", lo, hi) | ("log_uniform", lo, hi)
    #                            ("inv_softplus_log_uniform", lo, hi, floor)


def entropy(seed: int, *tags: int) -> list:
    """Seed-sequence entropy for any whole-number seed (64 bits and more)
    and non-negative integer tags. It ends in the tag count, never in a
    zero: numpy pads entropy with zeros, so [s, t] and [s, t, 0] would
    otherwise draw the same stream."""
    return [int(seed) % (1 << 128), *tags, len(tags) + 1]


def key_for(seed: int, *tags) -> jax.Array:
    """A raw threefry key from the seed and tags; the seed never passes
    through a 32-bit int."""
    words = np.random.SeedSequence(entropy(seed, *tags)
                                   ).generate_state(2, np.uint32)
    return jnp.asarray(words, jnp.uint32)


def rng_for(seed: int, *tags) -> np.random.Generator:
    """A host generator from the seed and tags."""
    return np.random.default_rng(entropy(seed, *tags))


def _leaf_values(key: jax.Array, path: str, leaf: Leaf) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    kind, *a = leaf.init
    shape = leaf.shape
    if kind == "normal":
        x = jax.random.normal(k, shape, jnp.float32) * a[0]
    elif kind == "ones":
        x = jnp.ones(shape, jnp.float32)
    elif kind == "zeros":
        x = jnp.zeros(shape, jnp.float32)
    elif kind == "uniform":
        x = jax.random.uniform(k, shape, jnp.float32, a[0], a[1])
    elif kind == "log_uniform":
        x = jnp.log(jax.random.uniform(k, shape, jnp.float32, a[0], a[1]))
    elif kind == "inv_softplus_log_uniform":
        # Mamba-2's dt bias: dt log-uniform in [lo, hi], floored, stored
        # as softplus^-1(dt) so that softplus(bias) == dt.
        lo, hi, floor = a
        u = jax.random.uniform(k, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (np.log(hi) - np.log(lo))
                                 + np.log(lo)), floor)
        x = dt + jnp.log(-jnp.expm1(-dt))
    else:
        raise ValueError(f"unknown init {leaf.init!r} for {path}")
    return x.astype(leaf.dtype)


def make(spec: dict, key: jax.Array) -> dict:
    """The nested parameter dict of ``spec``, each leaf in its stored
    dtype. Trace it under one ``jax.jit`` to make every leaf on the
    device in one call."""
    out: dict = {}
    for path, leaf in spec.items():
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = _leaf_values(key, path, leaf)
    return out


def flat(tree: dict, prefix: str = "") -> dict:
    """``{path: leaf}`` of a nested dict (the inverse of :func:`make`'s
    nesting)."""
    out = {}
    for k, v in tree.items():
        p = f"{prefix}/{k}" if prefix else str(k)
        if isinstance(v, dict):
            out.update(flat(v, p))
        else:
            out[p] = v
    return out
