"""The user's program: a Helix workflow that trains and evaluates a model.

Node for node it follows ``benchmarks/workflows.build_lm``:

    tokens ──┐
             ├─ train ──┐
    initState┘          ├─ evalLoss   (the declared output)
    evalTokens ─────────┘

Its knobs are three seeds: ``data_seed`` (the training batches),
``init_seed`` (the initial weights) and ``eval_seed`` (a held-out batch
that only ``evalLoss`` reads). A traffic mix edits them; Helix decides
what to reuse. The nodes call the program's public pieces
(``repro.train.steps``, ``repro.launch.shapes.train_shardings``,
``repro.launch.mesh``, ``repro.models.lm``); the weights come from the
benchmark's seeded maker, never from the program.

The jitted programs carry stable names (``helix_train_step``,
``helix_eval_nll``) so that the trace reduction finds them.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import Workflow
from repro.launch import shapes
from repro.launch.mesh import make_local_mesh
from repro.models import lm, registry
from repro.models.config import ArchConfig
from repro.optim import adamw
from repro.train import steps as train_steps

import weights

# Tags that keep the seeded streams apart.
TOKENS, EVAL_TOKENS, INIT = 1, 2, 3


@dataclasses.dataclass(frozen=True)
class Knobs:
    data_seed: int
    init_seed: int
    eval_seed: int


def arch(config: dict, vocab: int) -> ArchConfig:
    """The program's ArchConfig from the config's ``program`` mapping
    (``"$key"`` reads the config's own key)."""
    def resolve(v):
        if isinstance(v, dict):
            return {k: resolve(x) for k, x in v.items()}
        return config[v[1:]] if isinstance(v, str) and v.startswith("$") else v

    return ArchConfig(name=config["name"], vocab_size=vocab,
                      **resolve(config["program"]))


def tokens(config: dict, sizes, seed: int, tag: int, n: int) -> np.ndarray:
    """(n, batch, seq) token ids, uniform over the unpadded vocabulary."""
    return weights.rng_for(seed, tag).integers(
        0, config["vocab_size"], (n, sizes.batch, sizes.seq_len),
        dtype=np.int32)


class Programs:
    """The workflow's jitted programs for one configuration on ``mesh``,
    built once per process (a fresh ``jax.jit`` per node call would
    retrace)."""

    def __init__(self, config: dict, sizes, ref, mesh=None):
        self.config, self.sizes = config, sizes
        self.mesh = mesh if mesh is not None else make_local_mesh()
        self.cfg = cfg = arch(config, ref.vocab(config))
        spec = ref.param_spec(config)
        self.paths = sorted(spec)
        _check_layout(cfg, spec)
        self.state, self.batch_axes = shapes.train_shardings(cfg, self.mesh)
        b1 = sizes.adamw["b1"]

        def helix_init_state(key):
            params = weights.make(spec, key)
            return train_steps.TrainState(params=params,
                                          opt=adamw.init(params))

        def helix_train_step(state, batch):
            return train_steps.train_step(
                cfg, state, batch, peak_lr=sizes.peak_lr,
                warmup_steps=sizes.warmup_steps, total_steps=sizes.steps,
                clip_norm=sizes.clip_norm)

        def helix_grad_norms(m):
            # After one step m = (1 - b1) g: the clipped gradient as the
            # optimizer got it.
            flat = weights.flat(m)
            return jnp.stack([jnp.linalg.norm(flat[p].ravel()) / (1 - b1)
                              for p in self.paths])

        def helix_update_norms(params, params0):
            a, b = weights.flat(params), weights.flat(params0)
            return jnp.stack([jnp.linalg.norm(
                a[p].astype(jnp.float32).ravel()
                - b[p].astype(jnp.float32).ravel()) for p in self.paths])

        def helix_eval_nll(params, toks):
            # One row at a time: a row's float32 logits are 0.76 GB.
            def one(tok):
                logits = lm.forward(cfg, params, tok[None]).logits
                with jax.named_scope("loss"):
                    logits = logits[0, :-1].astype(jnp.float32)
                    gold = jnp.take_along_axis(logits, tok[1:, None],
                                               -1)[:, 0]
                    return jax.nn.logsumexp(logits, -1) - gold
            return jax.lax.map(one, toks)

        def helix_state_copy(state):
            return jax.tree_util.tree_map(jnp.copy, state)

        self.init = jax.jit(helix_init_state, out_shardings=self.state)
        # On the device: jax.device_put(..., may_alias=False) of a state
        # on its own device takes the bytes through the host (1.2-1.9 s
        # for 5 GB on a v5e, against 18 ms for this).
        self.copy = jax.jit(helix_state_copy, out_shardings=self.state)
        self.train = jax.jit(helix_train_step,
                             out_shardings=(self.state, None),
                             donate_argnums=0)
        self.grad_norms = jax.jit(helix_grad_norms)
        self.update_norms = jax.jit(helix_update_norms)
        self.eval_nll = jax.jit(helix_eval_nll)
        self.digest = jax.jit(state_digest)

    def put(self, toks):
        return jax.device_put(toks, shapes.batch_sharding(
            self.mesh, toks.shape, self.batch_axes))

    def trained_placement(self):
        """``sharding_for_leaf`` of the train node's value: the state's
        leaves onto their training placement, the logs left on host."""
        leaves = jax.tree_util.tree_leaves(
            {"grad_norms": None, "losses": None, "state": self.state,
             "update_norms": None}, is_leaf=lambda x: x is None)
        return lambda i, shape, dtype: leaves[i]


def _leaf_digest(x) -> jax.Array:
    """An exact checksum of one array's bits: the sum, modulo 2**32, of
    each element's bits times an odd weight drawn from its position. Any
    change to one element changes it; integer sums give the same answer
    in any order."""
    bits = jax.lax.bitcast_convert_type(
        x, {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32}[x.dtype.itemsize])
    bits = bits.ravel().astype(jnp.uint32)
    pos = jnp.arange(bits.size, dtype=jnp.uint32)
    return jnp.sum(bits * (pos * jnp.uint32(2654435761) | 1),
                   dtype=jnp.uint32)


def state_digest(state) -> jax.Array:
    """One checksum per leaf of a TrainState, made on the device."""
    return jnp.stack([_leaf_digest(x)
                      for x in jax.tree_util.tree_leaves(state)])


def _check_layout(cfg: ArchConfig, spec: dict) -> None:
    """The benchmark's parameter spec must be the program's tree: same
    paths, shapes and dtypes."""
    got = jax.eval_shape(functools.partial(registry.init, cfg),
                         jax.random.PRNGKey(0))
    got = {p: (tuple(x.shape), str(x.dtype))
           for p, x in weights.flat(got).items()}
    want = {p: (tuple(x.shape), x.dtype) for p, x in spec.items()}
    if got != want:
        diff = sorted(set(got.items()) ^ set(want.items()))
        raise ValueError(f"parameter layout differs from the program's: "
                         f"{diff[:6]}")


def build(pr: Programs, k: Knobs) -> Workflow:
    """One iteration's workflow at knobs ``k``."""
    config, sizes = pr.config, pr.sizes
    wf = Workflow("lm")
    vocab = config["vocab_size"]
    shape = (sizes.batch, sizes.seq_len)
    tok = wf.source("tokens", lambda: tokens(config, sizes, k.data_seed,
                                             TOKENS, sizes.steps),
                    config=("tok", vocab, shape, sizes.steps, k.data_seed))
    etok = wf.source("evalTokens", lambda: tokens(config, sizes, k.eval_seed,
                                                  EVAL_TOKENS, 1)[0],
                     config=("evaltok", vocab, shape, k.eval_seed))

    def init_state():
        with pr.mesh:
            return pr.init(weights.key_for(k.init_seed, INIT))

    state0 = wf.source("initState", init_state,
                       config=("init", pr.cfg, tuple(pr.paths), k.init_seed))

    def train(batches, state):
        with pr.mesh:
            # A copy the steps may donate: the node's input stays whole
            # for the store's writer and for the update norms. A state
            # loaded from the store comes back on its own placement.
            state = jax.device_put(state, pr.state)
            work = pr.copy(state)
            losses, grad_norms = [], None
            for i in range(sizes.steps):
                work, metrics = pr.train(work, {"tokens": pr.put(batches[i])})
                losses.append(metrics["loss"])
                if i == 0:
                    grad_norms = np.asarray(pr.grad_norms(work.opt.m),
                                            np.float64)
            update_norms = pr.update_norms(work.params, state.params)
        return {"state": work,
                "losses": np.asarray([float(x) for x in losses], np.float64),
                "grad_norms": grad_norms,
                "update_norms": np.asarray(update_norms, np.float64)}

    trained = wf.learner(
        "train", train, [tok, state0],
        config=("train", pr.cfg, shape, sizes.steps, sizes.peak_lr,
                sizes.warmup_steps, sizes.clip_norm))
    wf.load_shardings["train"] = pr.trained_placement()

    def eval_loss(et, tr):
        with pr.mesh:
            nll = np.asarray(pr.eval_nll(tr["state"].params, pr.put(et)))
            digest = np.asarray(pr.digest(tr["state"]))
        return {"eval_loss": float(nll.mean(dtype=np.float64)),
                "nll": nll.ravel().tolist(),
                "state_digest": digest.tolist(),
                "train_losses": tr["losses"].tolist(),
                "grad_norms": dict(zip(pr.paths, tr["grad_norms"].tolist())),
                "update_norms": dict(zip(pr.paths,
                                         tr["update_norms"].tolist()))}

    out = wf.reducer("evalLoss", eval_loss, [etok, trained], config="eval")
    wf.output(out)
    return wf
