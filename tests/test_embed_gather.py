"""The embedding lookup: a row gather where the mesh leaves the vocab axis
whole, the one-hot matmul where it splits it.

Checked on the chip benchmark's dense (internlm2) and tied state-space
(mamba2) configurations at their ``test_sizes``: the gather's forward and
its table gradient equal the one-hot's, the gradient summing duplicate ids
in float32 and rounding once; and the lowered forward and gradient take
the path the mesh calls for.
"""
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import AbstractMesh, AxisType

from repro.launch.mesh import make_local_mesh
from repro.models import lm

CHIP = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir,
                                    "benchmarks", "chip"))
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import cell  # noqa: E402
import workflow  # noqa: E402

CONFIGS = ["internlm2-1.8b-2l", "mamba2-130m"]   # dense; tied SSM


def _setup(name):
    config = cell.load(name + ".dpr", test_sizes=True).config
    ref = cell.reference_module(config)
    cfg = workflow.arch(config, ref.vocab(config))
    params = lm.init(cfg, jax.random.PRNGKey(0))
    b, s = config["train"]["batch"], config["train"]["seq_len"]
    toks = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0,
                              config["vocab_size"])
    return cfg, params, toks.at[:, ::2].set(5)   # half the ids one token


def _one_hot(monkeypatch):
    """Send ``embed_lookup`` down the one-hot path, as a vocab-sharded
    mesh does."""
    monkeypatch.setattr(lm, "vocab_split", lambda cfg: True)


def _grads(cfg, params, toks):
    def loss(p):
        logits = lm.forward(cfg, p, toks).logits.astype(jnp.float32)
        return (logits ** 2).mean()
    return jax.jit(jax.grad(loss))(params)


def _exact(a, b):
    return jax.tree_util.tree_all(jax.tree_util.tree_map(
        lambda x, y: bool(jnp.array_equal(x, y)), a, b))


@pytest.mark.parametrize("name", CONFIGS)
def test_gather_forward_equals_onehot(name, monkeypatch):
    cfg, params, toks = _setup(name)
    rows = lm.embed_lookup(cfg, params["embed"], toks)
    logits = lm.forward(cfg, params, toks).logits
    _one_hot(monkeypatch)
    assert _exact(rows, lm.embed_lookup(cfg, params["embed"], toks))
    assert _exact(logits, lm.forward(cfg, params, toks).logits)


@pytest.mark.parametrize("name", CONFIGS)
def test_gather_gradient_equals_onehot(name, monkeypatch):
    """Every leaf's gradient, the table's included: for mamba2 that is the
    tied head's dW plus the lookup's rows."""
    cfg, params, toks = _setup(name)
    got = _grads(cfg, params, toks)
    _one_hot(monkeypatch)
    want = _grads(cfg, params, toks)
    assert _exact(got, want)
    assert cfg.tie_embeddings == ("lm_head" not in got)


@pytest.mark.parametrize("name", CONFIGS)
def test_repeated_token_gradient_sums_in_float32(name, monkeypatch):
    """4096 copies of one id, each with cotangent 1: a float32 sum is 4096;
    a bfloat16 scatter-add stalls at 256, where 256 + 1 rounds back."""
    cfg, params, _ = _setup(name)
    toks = jnp.full((2, 2048), 7, jnp.int32)

    def table_grad():
        _, vjp = jax.vjp(lambda t: lm.embed_lookup(cfg, t, toks),
                         params["embed"])
        return vjp(jnp.ones((2, 2048, cfg.d_model), jnp.bfloat16))[0]

    got = table_grad()
    assert got.dtype == params["embed"].dtype
    np.testing.assert_array_equal(np.asarray(got[7], np.float32), 4096.0)
    assert not np.asarray(got.at[7].set(0)).any()
    _one_hot(monkeypatch)
    assert _exact(got, table_grad())


def _embed_ops(lowered, vocab):
    """(op, has a vocab-wide operand) for each op of the ``embed`` scope
    in a lowered program's StableHLO."""
    text = lowered.as_text(debug_info=True)
    names = dict(re.findall(r'^#(loc\d+) = loc\("([^"]*)"', text, re.M))
    ops = []
    for line in text.splitlines():
        m = re.search(r"loc\(#(loc\d+)\)$", line.strip())
        name = names.get(m.group(1), "") if m else ""
        if re.search(r"[/(]embed[)/]", name):
            wide = re.search(rf"tensor<(\d+x)*{vocab}x", line) is not None
            ops.append((name.rsplit("/", 1)[-1], wide))
    return ops


MESHES = {
    "none": None,
    "local": make_local_mesh,
    # launch/dryrun.py's pod: model=16 divides the 256-row test vocab.
    "split": lambda: jax.sharding.use_abstract_mesh(AbstractMesh(
        (16, 16), ("data", "model"), axis_types=(AxisType.Auto,) * 2)),
    # model=3 does not divide it, so the table stays whole.
    "undivided": lambda: jax.sharding.use_abstract_mesh(AbstractMesh(
        (2, 3), ("data", "model"), axis_types=(AxisType.Auto,) * 2)),
}


@pytest.mark.parametrize("mesh", list(MESHES))
@pytest.mark.parametrize("name", CONFIGS)
def test_lowered_lookup_follows_the_mesh(name, mesh):
    cfg, params, toks = _setup(name)
    shapes = jax.eval_shape(lambda: params)
    tok_shape = jax.ShapeDtypeStruct(toks.shape, toks.dtype)

    def fwd(p, t):
        return lm.forward(cfg, p, t).logits

    def grad(p, t):
        return jax.grad(lambda q: fwd(q, t).astype(jnp.float32).mean())(p)

    def lower():
        return [_embed_ops(jax.jit(f).lower(shapes, tok_shape),
                           cfg.vocab_size) for f in (fwd, grad)]

    if MESHES[mesh] is None:
        fwd_ops, grad_ops = lower()
    else:
        with MESHES[mesh]():
            fwd_ops, grad_ops = lower()
    wide_dots = [op for op, wide in fwd_ops + grad_ops
                 if op == "dot_general" and wide]
    ops = {op for op, _ in fwd_ops + grad_ops}
    if mesh == "split":
        assert lm.vocab_split(cfg) is False       # outside the mesh
        assert wide_dots and "gather" not in ops
        assert ("dot_general", True) in grad_ops  # the table's gradient
    else:
        assert not wide_dots
        assert "gather" in {op for op, _ in fwd_ops}
        assert "scatter-add" in {op for op, _ in grad_ops}
