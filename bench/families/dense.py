"""The dense family: a stack of one attention-and-MLP block kind.

Configuration files are written with the published ``config.json`` keys
of a Qwen3-style dense decoder (qk-norm where ``model_type`` is
``qwen3``), plus ``attention_backend`` and ``serving``. The weight tree
has the layout the program's ``lm`` module takes for such a stack (one
scanned block kind, stacked over layers, tied or separate output head),
in the configuration's ``torch_dtype``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

REFERENCE = "dense"
WORK = "dense_lm"
SMOKE = dict(hidden_size=64, num_hidden_layers=2, num_attention_heads=4,
             num_key_value_heads=2, head_dim=16, intermediate_size=128,
             vocab_size=256)

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for the file."""
    from repro.configs.base import ModelConfig
    if conf.get("hidden_act", "silu") != "silu":
        raise ValueError(f"hidden_act {conf['hidden_act']!r}: the program "
                         "serves swiglu MLPs only")
    if float(conf.get("rms_norm_eps", 1e-6)) != 1e-6:
        raise ValueError("the program's rmsnorm uses eps 1e-6")
    dtype = conf.get("torch_dtype", "bfloat16")
    return ModelConfig(
        name=f"{conf['model_type']}-{conf['attention_backend']}",
        family="dense",
        n_layers=conf["num_hidden_layers"],
        d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        head_dim=conf["head_dim"],
        d_ff=conf["intermediate_size"],
        vocab_size=conf["vocab_size"],
        qk_norm=conf["model_type"] == "qwen3",
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        attention_backend=conf["attention_backend"],
        decode_kernel=conf.get("decode_kernel", "auto"),
        dtype=dtype,
        param_dtype=dtype,
    )


def grows_kv(conf: Dict[str, Any]) -> bool:
    """A softmax layer's KV cache grows with the context; the linear
    backends' state does not."""
    return not model_config(conf).fixed_state_decode


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


def make(conf: Dict[str, Any], key) -> Dict[str, Any]:
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
