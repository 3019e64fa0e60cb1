"""An ArchConfig takes its sub-configs as mappings (a config read from
JSON) and holds them as their dataclasses: hashable, and equal to the
config built in code. A config without sub-configs is as it was."""
import dataclasses
import os
import sys

from repro import configs
from repro.models.config import ArchConfig, MoECfg, SSMCfg

CHIP = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                    "chip")
if CHIP not in sys.path:
    sys.path.insert(0, CHIP)

import cell  # noqa: E402
import workflow  # noqa: E402

# The dense benchmark configuration's ArchConfig as the harness built it
# before sub-configs could be mappings: node signatures and compile-cache
# keys are made from this text and this hash.
INTERNLM2_REPR = (
    "ArchConfig(name='internlm2-1.8b-2l', family='dense', num_layers=2, "
    "d_model=2048, num_heads=16, num_kv_heads=8, d_ff=8192, "
    "vocab_size=92544, head_dim=0, use_bias=False, rope_theta=1000000, "
    "window=None, global_every=0, mrope_sections=None, attn_every=0, "
    "moe=None, ssm=None, encdec=None, tie_embeddings=False, norm_eps=1e-05, "
    "remat='block', scan_layers=True, attn_impl='chunked', grad_accum=1, "
    "embed_impl='onehot', xent_impl='onehot', moe_impl='einsum', "
    "window_cache=False, train_ruleset=None, unroll=False)")


def _arch(name):
    c = cell.load(name).config
    return workflow.arch(c, cell.reference_module(c).vocab(c))


def test_ssm_mapping_becomes_the_dataclass():
    got = _arch("mamba2-130m.dpr")
    want = SSMCfg(d_state=128, head_dim=64, expand=2, d_conv=4, chunk=256,
                  a_init_range=(1.0, 16.0))
    assert isinstance(got.ssm, SSMCfg)
    assert isinstance(got.ssm.a_init_range, tuple)
    assert got.ssm == want and hash(got.ssm) == hash(want)
    # The benchmark's configuration is the registry's model.
    reg = configs.get("mamba2-130m")
    assert got == reg and hash(got) == hash(reg)


def test_config_without_sub_configs_is_unchanged():
    got = _arch("internlm2-1.8b-2l.dpr")
    assert repr(got) == INTERNLM2_REPR
    assert hash(got) == hash(tuple(getattr(got, f.name)
                                   for f in dataclasses.fields(got)))


def test_moe_mapping_round_trips():
    moe = MoECfg(num_experts=32, top_k=8, expert_d_ff=1024, num_shared=1,
                 shared_d_ff=2048)
    built = dataclasses.replace(configs.get("granite-moe-1b-a400m"), moe=moe)
    read = dataclasses.replace(built, moe=dataclasses.asdict(moe))
    assert isinstance(read.moe, MoECfg)
    assert read == built and hash(read) == hash(built)
    assert repr(read) == repr(built)


def test_lists_become_tuples():
    cfg = ArchConfig(name="x", family="vlm", num_layers=1, d_model=8,
                     num_heads=1, num_kv_heads=1, d_ff=8, vocab_size=8,
                     mrope_sections=[1, 1, 2],
                     ssm={"a_init_range": [1, 16]})
    assert cfg.mrope_sections == (1, 1, 2)
    assert cfg.ssm.a_init_range == (1, 16)
    hash(cfg)
