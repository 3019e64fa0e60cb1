"""Assigned input shapes × per-arch input specs + sharding policies.

``input_specs(cfg, shape_name)`` returns ShapeDtypeStruct stand-ins for
every input of the lowered step (weak-type-correct, no allocation), and
``shardings(cfg, shape_name, mesh)`` the matching NamedSharding pytrees.

Sharding policy summary (see DESIGN.md §5):
  train    params+opt 2D (FSDP over data × TP over model); batch over
           (pod, data)
  prefill  params TP; batch over (pod, data)
  decode   params TP; batch over (pod, data); KV-cache *sequence* over
           model (32k·128 caches don't fit otherwise)
  long     batch=1 → KV-cache sequence over (data, model); SSM state
           replicated (it is O(1) per sequence)
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

from ..models import registry
from ..models.config import ArchConfig
from ..models.params import param_specs
from ..sharding import rules as rules_lib
from ..train import steps


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    kind: str          # train | prefill | decode
    seq: int
    batch: int


SHAPES = {
    "train_4k": ShapeSpec("train_4k", "train", 4_096, 256),
    "prefill_32k": ShapeSpec("prefill_32k", "prefill", 32_768, 32),
    "decode_32k": ShapeSpec("decode_32k", "decode", 32_768, 128),
    "long_500k": ShapeSpec("long_500k", "decode", 524_288, 1),
}

# long_500k policy (DESIGN.md §4): only sub-quadratic families.
LONG_OK_FAMILIES = ("ssm", "hybrid")


def long_ok(cfg: ArchConfig) -> bool:
    return cfg.family in LONG_OK_FAMILIES or cfg.window is not None


def cells(cfg: ArchConfig) -> list[str]:
    """The assigned (runnable) shapes for this arch."""
    out = ["train_4k", "prefill_32k", "decode_32k"]
    if long_ok(cfg):
        out.append("long_500k")
    return out


# ---------------------------------------------------------------------------
# input ShapeDtypeStructs
# ---------------------------------------------------------------------------
_VLM_PATCHES = 1024          # stubbed vision prefix length (train/prefill)
_AUDIO_DEC_LEN = 448         # whisper decoder target length


def _batch_sds(cfg: ArchConfig, sh: ShapeSpec) -> dict:
    f32, i32, bf16 = jnp.float32, jnp.int32, jnp.bfloat16
    S = jax.ShapeDtypeStruct
    b, s = sh.batch, sh.seq
    batch: dict = {}
    if cfg.family == "audio":
        batch["frames"] = S((b, s, cfg.d_model), bf16)
        batch["tokens"] = S((b, _AUDIO_DEC_LEN), i32)
        return batch
    batch["tokens"] = S((b, s), i32)
    if cfg.family == "vlm":
        batch["vision_embeds"] = S((b, _VLM_PATCHES, cfg.d_model), bf16)
        batch["mrope_positions"] = S((3, b, s), i32)
    return batch


def input_specs(cfg: ArchConfig, shape_name: str) -> tuple:
    """ShapeDtypeStruct stand-ins for the step's arguments."""
    sh = SHAPES[shape_name]
    key_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
    if sh.kind == "train":
        state = jax.eval_shape(
            lambda k: steps.init_train_state(cfg, k), key_sds)
        return (state, _batch_sds(cfg, sh))
    params = jax.eval_shape(lambda k: registry.init(cfg, k), key_sds)
    if sh.kind == "prefill":
        return (params, _batch_sds(cfg, sh))
    # decode: one new token against a seq-sized cache
    cache = jax.eval_shape(
        lambda: registry.init_cache(cfg, sh.batch, sh.seq))
    token = jax.ShapeDtypeStruct((sh.batch, 1), jnp.int32)
    return (params, token, cache)


# ---------------------------------------------------------------------------
# shardings
# ---------------------------------------------------------------------------
def _filter_spec(shape: tuple, entries: list, mesh) -> PartitionSpec:
    """Drop axes that don't exist / don't divide."""
    sizes = dict(mesh.shape)
    out = []
    for dim, e in zip(shape, entries):
        if e is None:
            out.append(None)
            continue
        axes = e if isinstance(e, tuple) else (e,)
        axes = tuple(a for a in axes if a in sizes)
        total = int(np.prod([sizes[a] for a in axes])) if axes else 1
        if axes and total > 1 and dim % total == 0:
            out.append(axes if len(axes) > 1 else axes[0])
        else:
            out.append(None)
    return PartitionSpec(*out)


def _named(mesh, shape, entries):
    return NamedSharding(mesh, _filter_spec(shape, entries, mesh))


def batch_sharding(mesh, shape: tuple, bd: tuple = ("pod", "data")
                   ) -> NamedSharding:
    """Leading (batch) dim over the mesh axes ``bd`` that exist and
    divide it; every other dim replicated."""
    return _named(mesh, shape, [bd] + [None] * (len(shape) - 1))


def _batch_shardings(cfg: ArchConfig, sh: ShapeSpec, mesh, batch_sds: dict,
                     bd: tuple = ("pod", "data")) -> dict:
    out = {}
    for k, sds in batch_sds.items():
        if k == "mrope_positions":
            out[k] = _named(mesh, sds.shape, [None, bd, None])
        else:
            out[k] = batch_sharding(mesh, sds.shape, bd)
    return out


def _params_shardings(cfg: ArchConfig, mesh, ruleset: dict):
    specs = param_specs(registry.param_defs(cfg), mesh, ruleset)
    return jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec), specs,
        is_leaf=lambda s: isinstance(s, PartitionSpec))


def train_shardings(cfg: ArchConfig, mesh, ruleset_name: str | None = None
                    ) -> tuple[steps.TrainState, tuple]:
    """TrainState shardings and the batch's mesh axes for training ``cfg``
    on ``mesh`` under ``ruleset_name`` (default: the config's
    ``train_ruleset``, else ``train_2d``). Optimizer moments shard like
    the params; the step counter is replicated."""
    rname = ruleset_name or cfg.train_ruleset or "train_2d"
    pshard = _params_shardings(cfg, mesh, rules_lib.RULESETS[rname])
    state = steps.TrainState(
        params=pshard,
        opt=steps.adamw.AdamWState(
            m=pshard, v=pshard, step=NamedSharding(mesh, PartitionSpec())))
    return state, rules_lib.BATCH_AXES_BY_RULESET.get(rname, ("pod", "data"))


def _cache_shardings(cfg: ArchConfig, sh: ShapeSpec, mesh, cache_sds):
    """KV cache: seq over model (decode_32k) or (data, model) (long_500k,
    batch=1); batch over (pod, data); SSM states: batch over (pod, data)."""
    long_ctx = sh.batch == 1
    bd = ("pod", "data")
    seq_axes = ("data", "model") if long_ctx else ("model",)

    def spec_for(path, sds):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(sds.shape)
        if name in ("kg", "vg"):      # (G, 1, B, S, KV, hd) global layers
            return [None, None, bd, seq_axes, None, None]
        if name in ("kl", "vl"):      # (G, g-1, B, W, KV, hd) ring buffers
            return [None, None, bd, None, None, None]
        if name == "kpl":
            return [None, None, bd, None]
        if name in ("kt", "vt"):      # (T, B, W, KV, hd)
            return [None, bd, None, None, None]
        if name == "kpt":
            return [None, bd, None]
        if name in ("k", "v"):
            if nd == 5:   # (L, B, S, KV, hd)
                return [None, bd, seq_axes, None, None]
            return [bd, seq_axes, None, None]
        if name == "conv":    # (L[, n_ssm], B, K-1, C)
            return [None] * (nd - 3) + [bd, None, ("model",)]
        if name == "h":       # (L[, n_ssm], B, H, P, N)
            return [None] * (nd - 4) + [bd, None, None, None]
        if name == "enc_out":  # (B, S_enc, D)
            return [bd, None, None]
        return [None] * nd
    flat, treedef = jax.tree_util.tree_flatten_with_path(cache_sds)
    out = [_named(mesh, sds.shape, spec_for(path, sds))
           for path, sds in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


# ---------------------------------------------------------------------------
# the jit-able step per cell
# ---------------------------------------------------------------------------
def build_step(cfg: ArchConfig, shape_name: str, mesh,
               ruleset_name: str | None = None):
    """Returns (fn, args_sds, in_shardings, out_shardings, donate) for
    jitting one (arch × shape) cell on ``mesh``."""
    sh = SHAPES[shape_name]
    args = input_specs(cfg, shape_name)
    if sh.kind == "train":
        state_shard, bd = train_shardings(cfg, mesh, ruleset_name)
        _, batch_sds = args
        in_shardings = (state_shard,
                        _batch_shardings(cfg, sh, mesh, batch_sds, bd=bd))
        out_shardings = (state_shard, None)

        def fn(state, batch):
            from ..sharding.activation import use_batch_axes
            with use_batch_axes(bd):
                return steps.train_step(cfg, state, batch)
        return fn, args, in_shardings, out_shardings, (0,)
    ruleset = rules_lib.RULESETS[ruleset_name or "serve"]
    if sh.kind == "prefill":
        params_sds, batch_sds = args
        pshard = _params_shardings(cfg, mesh, ruleset)
        in_shardings = (pshard, _batch_shardings(cfg, sh, mesh, batch_sds))
        cache_sds = jax.eval_shape(
            lambda p, b: steps.prefill_step(cfg, p, b, max_len=sh.seq)[1],
            params_sds, batch_sds)
        out_shardings = (None, _cache_shardings(cfg, sh, mesh, cache_sds))
        fn = lambda p, b: steps.prefill_step(cfg, p, b, max_len=sh.seq)
        return fn, args, in_shardings, out_shardings, ()
    # decode
    params_sds, token_sds, cache_sds = args
    pshard = _params_shardings(cfg, mesh, ruleset)
    cshard = _cache_shardings(cfg, sh, mesh, cache_sds)
    tshard = _named(mesh, token_sds.shape, [("pod", "data"), None])
    in_shardings = (pshard, tshard, cshard)
    out_shardings = (None, cshard)
    fn = lambda p, t, c: steps.decode_step(cfg, p, t, c)
    return fn, args, in_shardings, out_shardings, (2,)
