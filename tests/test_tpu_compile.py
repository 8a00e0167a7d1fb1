"""Compile the served-path Pallas kernels for a TPU v5e, with no chip.

The TPU compiler is installed with JAX and compiles for a described,
unattached topology, so these tests catch what interpret mode cannot: a
block that breaks the (8, 128) tiling, or a kernel that asks for more
VMEM than the core has. Shapes are the real widths of the served path:
16 slots × 16 heads at head_dim 128 for the fused recurrent decode
kernels, and a 1024-row store for the lookup kernel. The decode segment
is compiled whole at qwen3-0.6b's widths, two layers deep, to pin that
the linear state stack is updated in place by the kernel.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.fused_recurrent import ops as FR
from repro.kernels.lookup import ops as LK
from repro.models import lm
from repro.sharding import Rules

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


def _compile(fn, *args, **jit_kw):
    compiled = jax.jit(fn, **jit_kw).lower(*args).compile()
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


@pytest.mark.parametrize("variant", ["linear", "linear_normalize", "gated"])
def test_stacked_entry_compiles_for_v5e(one_chip, variant):
    """The stacked entry at served widths: a 4-layer state stack, one
    decode step, the slot freeze as lens; the donated stack is the
    kernel's output."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    layers = 4
    s = sds((layers, SLOTS, HEADS, D, D), jnp.float32)
    z = sds((layers, SLOTS, HEADS, D), jnp.float32)
    row = sds((SLOTS, HEADS, 1, D), jnp.bfloat16)
    lens = sds((SLOTS,), jnp.int32)
    layer = sds((), jnp.int32)

    def run(s, z, q, k, v, g, lens, layer):
        if variant == "gated":
            return FR.fused_recurrent_gated(s, q, k, v, g, lens=lens,
                                            layer=layer, interpret=False)
        return FR.fused_recurrent_linear(
            s, q, k, v, z=z, normalize=variant == "linear_normalize",
            lens=lens, layer=layer, interpret=False)

    text = _compile(run, s, z, row, row, row, row, lens, layer,
                    donate_argnums=(0, 1)).as_text()
    assert re.search(r"input_output_alias=\{ \{1\}: \(0,", text)


# opcodes that may produce an f32 (..., 128, 128) state array inside the
# segment: the stack passes through the loops by reference alone
_STATE_PASS_THROUGH = {"parameter", "get-tuple-element", "bitcast"}


@pytest.mark.parametrize("backend", ["linear", "gated_linear"])
def test_segment_updates_state_stack_in_place(one_chip, monkeypatch,
                                              backend):
    """The linear decode segment, compiled whole for the v5e: the layer
    scan carries the state stack and the fused kernel rewrites its layer
    in place, so no op copies, slices, selects or writes back the stack
    or a layer of it, and the donated input is the output."""
    monkeypatch.setattr(FR, "_on_cpu", lambda: False)   # not interpret
    cfg = dataclasses.replace(
        get_config("qwen3-0.6b").with_backend(backend), n_layers=2,
        decode_kernel="fused")
    rules = Rules.null()

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(sds, jax.eval_shape(
        lambda: lm.init_params(jax.random.PRNGKey(0), cfg)))
    state = jax.tree.map(sds, jax.eval_shape(
        lambda: lm.init_decode_state(cfg, SLOTS, 256, rules)))
    slots = jax.ShapeDtypeStruct((SLOTS,), jnp.int32, sharding=one_chip)
    active = jax.ShapeDtypeStruct((SLOTS,), jnp.bool_, sharding=one_chip)

    def segment(params, state, tok, pos, active, remaining):
        return lm.generate_segment(params, state, tok, pos, active,
                                   remaining, 8, cfg, rules)

    text = _compile(segment, params, state, slots, slots, active, slots,
                    donate_argnums=(1,)).as_text()
    stack = f"f32[{cfg.n_layers},{SLOTS * HEADS},{D},{D}]"
    assert re.search(re.escape(stack) + r"\{[^}]*\}[^=]* custom-call\(",
                     text), "the kernel does not take the whole stack"
    made = set(re.findall(
        r"= f32\[[\d,]*,128,128\]\{[^}]*\} ([\w-]+)\(", text))
    assert made and made <= _STATE_PASS_THROUGH, made
    assert re.search(r"input_output_alias=\{ \{\d+\}: \(\d+,", text)


@pytest.mark.parametrize("k", [64, 128])
def test_mass_lookup_indexed_compiles_for_v5e(one_chip, k):
    store = jax.ShapeDtypeStruct((1024, k, k), jnp.float32,
                                 sharding=one_chip)
    rows = jax.ShapeDtypeStruct((64,), jnp.int32, sharding=one_chip)
    q = jax.ShapeDtypeStruct((64, 256, k), jnp.float32, sharding=one_chip)
    _compile(lambda c, r, x: LK.mass_lookup_indexed(
        c, r, x, block_m=128, interpret=False), store, rows, q)
