"""Compile the fused Pallas kernel for a TPU v5e without a chip.

The TPU compiler ships with jaxlib, so a described ``v5e:2x2`` topology is
enough to lower and compile the kernel with ``interpret=False`` — the
check that Mosaic accepts the kernel at real vocabulary sizes, which an
interpret-mode test on the CPU cannot make. The topology is described in a
module fixture (never at import), and the tests skip where it cannot be.
Nothing runs: these tests say nothing about results or speed.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.sampling import SamplingParams
from repro.kernels import fused_kernel, ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # an AOT compile for a described chip is written to the persistent
    # cache but cannot be read back without one: keep the cache off
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _fused_operands(sharding, B, V):
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return ([s((B, V), jnp.float32), s((B, V), jnp.int32),
             s((B, V), jnp.int32)]
            + [s((B,), jnp.float32)] * 4 + [s((B,), jnp.int32)]
            + [s((B,), jnp.float32)] * 3 + [s((V,), jnp.int32)])


@pytest.mark.parametrize("V,k_cap", [
    (49152, 256),            # smollm-360m's vocabulary
    (153600, 1024),          # Qwen3-8B's 151936, padded to block_v
])
def test_fused_kernel_compiles_for_v5e(one_chip, V, k_cap):
    """B=16 spans two row blocks, so the (B, 1) column blocks are
    exercised too."""
    fn = jax.jit(lambda *a: fused_kernel.fused_sample(
        *a, k_cap=k_cap, block_b=8, block_v=2048, interpret=False))
    compiled = fn.lower(*_fused_operands(one_chip, 16, V)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ops_wrapper_compiles_the_kernel_when_lowered_for_tpu(one_chip):
    """``ops.fused_sample`` picks compiled mode from the lowering platform:
    on the CPU it interprets, lowered for the TPU it emits the kernel."""
    def call(z, cp, co, rep, pres, freq, temp, tk, tp, mp, u, hot):
        params = SamplingParams(
            temperature=temp, top_k=tk, top_p=tp, min_p=mp,
            repetition_penalty=rep, presence_penalty=pres,
            frequency_penalty=freq)
        return ops.fused_sample(z, cp, co, params, u, hot, k_cap=256,
                                block_v=2048)

    compiled = jax.jit(call).lower(
        *_fused_operands(one_chip, 16, 49152)).compile()
    assert "tpu_custom_call" in compiled.as_text()
