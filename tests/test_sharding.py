"""Sharding rules: divisibility fallback, EP vs expert-TP auto-selection,
batch/cache specs — resolved against an AbstractMesh (no 256 devices needed).
"""
import jax
from jax.sharding import AbstractMesh, PartitionSpec

from repro import configs
from repro.models import registry
from repro.models.params import P, param_specs

MESH = AbstractMesh((16, 16), ("data", "model"))
MESH3 = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def leaves_with_paths(tree):
    flat, _ = jax.tree_util.tree_flatten_with_path(
        tree, is_leaf=lambda x: isinstance(x, PartitionSpec))
    return {jax.tree_util.keystr(p): v for p, v in flat}


def test_dense_2d_sharding():
    cfg = configs.get("yi-9b")
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), MESH))
    # embedding: vocab → model, embed → data
    emb = specs["['embed']"]
    assert tuple(emb) == ("model", "data")
    # attention wq (layers, embed, heads, hd): embed→data, heads→model
    wq = specs["['blocks']['attn']['wq']"]
    assert tuple(wq)[:3] == (None, "data", "model")


def test_kv_heads_fallback_replicated():
    cfg = configs.get("yi-9b")     # kv=4 < 16-way model axis
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), MESH))
    wk = specs["['blocks']['attn']['wk']"]
    # (layers, embed, kv_heads=4, hd): kv_heads cannot take 'model'
    assert tuple(wk) == (None, "data")


def test_granite_gets_expert_parallelism():
    cfg = configs.get("granite-moe-1b-a400m")   # 32 experts % 16 == 0
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), MESH))
    wg = specs["['blocks']['moe']['w_gate']"]
    # (layers, experts, embed, ff): experts→model (EP), embed→data
    assert tuple(wg) == (None, "model", "data")


def test_qwen2moe_falls_back_to_expert_tp():
    cfg = configs.get("qwen2-moe-a2.7b")        # 60 experts % 16 != 0
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), MESH))
    wg = specs["['blocks']['moe']['w_gate']"]
    # experts replicated; embed→data; expert ff 1408→model (expert-TP)
    assert tuple(wg) == (None, None, "data", "model")


def test_axis_used_once_per_tensor():
    p = P((32, 32), ("mlp", "heads"))           # both want 'model'
    spec = param_specs({"w": p}, MESH)["w"]
    entries = [e for e in tuple(spec) if e is not None]
    assert entries.count("model") <= 1


def test_multipod_mesh_resolution():
    cfg = configs.get("internlm2-1.8b")
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), MESH3))
    wq = specs["['blocks']['attn']['wq']"]
    assert "model" in tuple(wq)                 # still TP on the pod mesh


def test_single_device_mesh_all_replicated():
    mesh1 = AbstractMesh((1, 1), ("data", "model"))
    cfg = configs.reduced(configs.get("internlm2-1.8b"))
    specs = leaves_with_paths(param_specs(registry.param_defs(cfg), mesh1))
    assert all(all(e is None for e in tuple(s)) for s in specs.values())
