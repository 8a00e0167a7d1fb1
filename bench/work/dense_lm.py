"""Operations and bytes of a dense transformer LM, from its shapes.

Counts are what the algorithm needs, whatever computes it: one multiply
and one add are two operations; padding, inactive slots and work done
twice are not counted.
"""

from __future__ import annotations

from typing import Dict


def _dims(conf: Dict):
    return (conf["num_hidden_layers"], conf["hidden_size"],
            conf["num_attention_heads"], conf["num_key_value_heads"],
            conf["head_dim"], conf["intermediate_size"],
            conf["vocab_size"])


def matmul_flops(conf: Dict) -> int:
    """Projections and MLP of every layer, for one token."""
    n_layers, d, h, hkv, dh, f, _ = _dims(conf)
    per_layer = d * h * dh + 2 * d * hkv * dh + h * dh * d + 3 * d * f
    return 2 * n_layers * per_layer


def head_flops(conf: Dict) -> int:
    """The output head, for one token."""
    _, d, _, _, _, _, v = _dims(conf)
    return 2 * d * v


def attention_flops(conf: Dict, ctx_sum: int, n_tokens: int) -> int:
    """Attention of ``n_tokens`` tokens whose contexts (positions
    attended, the token's own included) add up to ``ctx_sum``.

    softmax: scores and weighted sum, 4 * heads * head_dim per position
    attended. linear: the state update k v^T and read S^T q, 4 * head_dim^2
    per head, and the key-sum normaliser, 4 * head_dim per head,
    whatever the context.
    """
    n_layers, _, h, _, dh, _, _ = _dims(conf)
    if conf["attention_backend"] == "softmax":
        per = 4 * h * dh * ctx_sum
    else:
        per = (4 * h * dh * dh + 4 * h * dh) * n_tokens
    return n_layers * per


_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def weight_bytes(conf: Dict) -> int:
    """Bytes of the weights one decode step reads once: projections,
    MLPs and norms of every layer, the final norm and the head, in the
    configuration's ``torch_dtype``."""
    n_layers, d, _, _, dh, _, v = _dims(conf)
    norms = n_layers * (2 * d + (2 * dh if conf["model_type"] == "qwen3"
                                 else 0)) + d
    params = matmul_flops(conf) // 2 + d * v + norms
    return params * _DTYPE_BYTES[conf.get("torch_dtype", "bfloat16")]


def decode_state_bytes(conf: Dict, ctx_sum: int, n_tokens: int) -> int:
    """Bytes of attention state that decoding ``n_tokens`` tokens, with
    contexts adding up to ``ctx_sum``, must move.

    linear: each token's slot reads and writes its float32 state (heads x
    head_dim^2) and key sum (heads x head_dim) in every layer, whatever
    the context. softmax: each token reads the key and value rows of its
    context and writes its own, key/value heads x head_dim each, in the
    configuration's dtype, in every layer.
    """
    n_layers, _, h, hkv, dh, _, _ = _dims(conf)
    if conf["attention_backend"] == "softmax":
        row = 2 * hkv * dh * _DTYPE_BYTES[conf.get("torch_dtype",
                                                   "bfloat16")]
        return n_layers * row * (ctx_sum + n_tokens)
    return n_layers * 2 * h * (dh * dh + dh) * 4 * n_tokens


def range_ctx_sum(first: int, last: int, offset: int) -> int:
    """Sum of ``offset + i`` for ``i`` in ``[first, last)``."""
    n = max(0, last - first)
    return n * offset + (first + last - 1) * n // 2 if n else 0
