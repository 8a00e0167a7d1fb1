"""Compile the served-path Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached topology, so these tests catch what interpret mode cannot: a
block that breaks the (8, 128) tiling, or a kernel that asks for more
VMEM than the core has. Shapes are the real widths of the served path:
16 slots × 16 heads at head_dim 128 for the fused recurrent decode
kernels, and a 1024-row store for the lookup kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.fused_recurrent import ops as FR
from repro.kernels.lookup import ops as LK

SLOTS, HEADS, W, D = 16, 16, 8, 128


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    return compiled


@pytest.mark.parametrize("varlen", [False, True], ids=["fixed", "varlen"])
@pytest.mark.parametrize("variant", ["linear", "linear_normalize", "gated"])
def test_fused_recurrent_compiles_for_v5e(one_chip, variant, varlen):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    s = sds((SLOTS, HEADS, D, D), jnp.float32)
    z = sds((SLOTS, HEADS, D), jnp.float32)
    row = sds((SLOTS, HEADS, W, D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32) if varlen else None

    def run(s, z, q, k, v, g, lens):
        if variant == "gated":
            return FR.fused_recurrent_gated(s, q, k, v, g, lens=lens,
                                            interpret=False)
        return FR.fused_recurrent_linear(
            s, q, k, v, z=z, normalize=variant == "linear_normalize",
            lens=lens, interpret=False)

    _compile(run, s, z, row, row, row, row, lens)


@pytest.mark.parametrize("k", [64, 128])
def test_mass_lookup_indexed_compiles_for_v5e(one_chip, k):
    store = jax.ShapeDtypeStruct((1024, k, k), jnp.float32,
                                 sharding=one_chip)
    rows = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((64, 256, k), jnp.float32, sharding=one_chip)
    _compile(lambda c, r, x: LK.mass_lookup_indexed(
        c, r, x, block_m=128, interpret=False), store, rows, q)
