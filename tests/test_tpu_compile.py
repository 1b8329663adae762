"""The main path's Pallas kernels compile for a TPU v5e chip.

Nothing runs: each test compiles a kernel at a real width for one chip of
a described (not attached) ``v5e:2x2`` topology and checks that the
compiled program holds the kernel (``tpu_custom_call``).  The compiler
refuses here what it would refuse on the chip — a block not aligned to
the tiling, too much fast memory — which interpret-mode tests cannot see.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers all
import this file.  Keep every such compile in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.grouped_matmul.kernel import gmm_pallas


@pytest.fixture(scope="module")
def topo():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies

        try:
            yield topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        finally:
            jax.config.update("jax_enable_compilation_cache", was_on)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, one_chip) -> str:
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("m,k,g,n,dtype", [
    # a ragged client wave: 2048 rows of 64 clients, 128-wide MLP layers
    (2048, 128, 64, 128, jnp.float32),
    # an OLMoE-width expert GEMM: d_model 2048, 64 experts of d_ff 1024
    (4096, 2048, 64, 1024, jnp.bfloat16),
], ids=["ragged_wave_f32", "moe_bf16"])
def test_gmm_pallas_compiles_for_v5e(one_chip, m, k, g, n, dtype):
    text = _compiled_text(
        gmm_pallas,
        [((m, k), dtype), ((g, k, n), dtype), ((g,), jnp.int32)],
        one_chip)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("hq,hk,d", [
    (16, 16, 64),    # qwen1.5-0.5b heads
    (32, 8, 128),    # grouped-query attention at head dim 128
], ids=["mha_d64", "gqa_d128"])
def test_flash_attention_pallas_compiles_for_v5e(one_chip, hq, hk, d):
    s = 4096
    text = _compiled_text(
        flash_attention_pallas,
        [((1, hq, s, d), jnp.bfloat16), ((1, hk, s, d), jnp.bfloat16),
         ((1, hk, s, d), jnp.bfloat16)],
        one_chip)
    assert "tpu_custom_call" in text
