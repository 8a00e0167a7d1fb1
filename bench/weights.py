"""Random weights from the seed, made on the device in one jitted call.

The tree has the layout the program's ``lm`` module takes for a dense
attention stack (one scanned block kind, stacked over layers, tied or
separate output head), in the configuration's ``torch_dtype``. The
benchmark makes the weights itself, so that the program and the
reference both take them from here and neither takes them from the
other.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def shapes(conf: Dict[str, Any]) -> Dict[str, Any]:
    """Leaf shapes of the tree, per layer where a leaf is stacked."""
    d, f = conf["hidden_size"], conf["intermediate_size"]
    h, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh = conf["head_dim"]
    return {
        "attn": {"wq": (d, h * dh), "wk": (d, hkv * dh),
                 "wv": (d, hkv * dh), "wo": (h * dh, d)},
        "mlp": {"w_gate": (d, f), "w_up": (d, f), "w_down": (f, d)},
    }


def _make(conf: Dict[str, Any], key) -> Dict[str, Any]:
    dt = DTYPES[conf.get("torch_dtype", "bfloat16")]
    n_layers, d = conf["num_hidden_layers"], conf["hidden_size"]
    dh, v = conf["head_dim"], conf["vocab_size"]
    keys = iter(jax.random.split(key, 16))

    def dense(shape):
        # 1/sqrt(fan-in) keeps each projection's output at unit scale
        w = jax.random.normal(next(keys), (n_layers,) + shape, jnp.float32)
        return (w / math.sqrt(shape[0])).astype(dt)

    def scale(n):
        # norm gains near 1, not equal to it, so that a reference that
        # left one out would disagree
        g = 1.0 + 0.1 * jax.random.normal(next(keys), (n_layers, n))
        return g.astype(dt)

    sh = shapes(conf)
    attn = {k: dense(s) for k, s in sh["attn"].items()}
    attn["q_norm"] = scale(dh)
    attn["k_norm"] = scale(dh)
    block = {
        "norm1": {"scale": scale(d)},
        "norm2": {"scale": scale(d)},
        "attn": attn,
        "mlp": {k: dense(s) for k, s in sh["mlp"].items()},
    }
    embed = (0.02 * jax.random.normal(next(keys), (v, d))).astype(dt)
    params = {
        "embed": embed,
        "final_norm": {"scale": (1.0 + 0.1 * jax.random.normal(
            next(keys), (d,))).astype(dt)},
        "stack": (block,),
        "tail": (),
        "shared": {},
    }
    if not conf["tie_word_embeddings"]:
        params["lm_head"] = (jax.random.normal(next(keys), (d, v))
                             / math.sqrt(d)).astype(dt)
    return params


def make_params(conf: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The weights for ``seed``, on the default device. Seeds of any
    size are folded into a 32-bit key without collisions below 2**64."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(functools.partial(_make, conf))(key)
