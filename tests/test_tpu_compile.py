"""AOT compiles of the Pallas kernels at published widths for a described
TPU v5e (2x2 topology, one chip of it): the TPU compiler's tiling and
VMEM checks, which interpret mode never runs, with no chip attached. A
compile that passes is not a chip run — ``chip_smoke.py`` is.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.kernels.flash_attention.flash_attention import (
    flash_attention_pallas)
from repro.kernels.rmsnorm.rmsnorm import rmsnorm_pallas
from repro.kernels.ssd.ssd import ssd_chunk_pallas

BATCH, SEQ = 2, 2048


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent compilation
    cache off (a described chip's entries cannot be read back)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _flash(sds):
    cfg = configs.get("internlm2-1.8b")
    hd = cfg.resolved_head_dim
    fn = functools.partial(flash_attention_pallas, causal=True, window=0,
                           bq=512, bk=512, interpret=False)
    return fn, (sds((BATCH, SEQ, cfg.num_heads, hd), jnp.bfloat16),
                sds((BATCH, SEQ, cfg.num_kv_heads, hd), jnp.bfloat16),
                sds((BATCH, SEQ, cfg.num_kv_heads, hd), jnp.bfloat16),
                sds((BATCH,), jnp.int32))


def _ssd(sds):
    cfg = configs.get("mamba2-130m")
    s = cfg.ssm
    heads = s.expand * cfg.d_model // s.head_dim
    fn = functools.partial(ssd_chunk_pallas, chunk=s.chunk, interpret=False)
    return fn, (sds((BATCH, SEQ, heads, s.head_dim), jnp.bfloat16),
                sds((BATCH, SEQ, heads), jnp.float32),
                sds((BATCH, SEQ, heads), jnp.float32),
                sds((BATCH, SEQ, s.d_state), jnp.bfloat16),
                sds((BATCH, SEQ, s.d_state), jnp.bfloat16))


def _rmsnorm(sds):
    d = configs.get("internlm2-1.8b").d_model
    fn = functools.partial(rmsnorm_pallas, eps=1e-5, block_rows=256,
                           interpret=False)
    return fn, (sds((4 * SEQ, d), jnp.bfloat16), sds((d,), jnp.float32))


@pytest.mark.parametrize("kernel", [_flash, _ssd, _rmsnorm],
                         ids=["flash_attention", "ssd", "rmsnorm"])
def test_kernel_compiles_for_v5e(kernel, one_chip):
    fn, args = kernel(functools.partial(jax.ShapeDtypeStruct,
                                        sharding=one_chip))
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
