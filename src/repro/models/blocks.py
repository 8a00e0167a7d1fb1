"""Block-kind dispatch: one interface over the five block families.

kinds:
  "attn"         pre-norm self-attention (backend-selectable) + MLP/MoE
  "shared_attn"  same block but ONE parameter set shared across all its
                 sites (Zamba-style); per-site decode state stays separate
  "cross"        cross-attention to pre-encoded modality memory + MLP
  "mamba"        Mamba-2 SSD block (no separate FFN)
  "rwkv"         RWKV-6 block (time-mix + channel-mix, internal norms)

Every kind implements:
  params / param_specs / state_init / state_specs / apply / decode
so the LM can scan over a heterogeneous ``layer_pattern`` uniformly.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import mamba2 as M
from repro.models import moe as MOE
from repro.models import rwkv6 as R
from repro.sharding import Rules, constrain

Array = jax.Array
Params = Dict[str, Any]

ATTN_KINDS = ("attn", "shared_attn", "cross")


def _uses_moe(kind: str, cfg: ModelConfig) -> bool:
    return cfg.moe is not None and kind == "attn"


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def block_params(kind: str, key, cfg: ModelConfig,
                 dtype=jnp.float32) -> Params:
    if kind == "mamba":
        return {"norm1": L.norm_params(cfg.norm, cfg.d_model, dtype),
                "mamba": M.mamba2_params(key, cfg, dtype)}
    if kind == "rwkv":
        return R.rwkv6_params(key, cfg, dtype)
    k1, k2 = jax.random.split(key)
    p = {"norm1": L.norm_params(cfg.norm, cfg.d_model, dtype),
         "norm2": L.norm_params(cfg.norm, cfg.d_model, dtype)}
    if kind == "cross":
        p["cross"] = A.cross_attention_params(k1, cfg, dtype)
        p["xgate"] = jnp.zeros((1,), dtype)   # tanh-gated injection
    else:
        p["attn"] = A.attention_params(k1, cfg, dtype)
    if _uses_moe(kind, cfg):
        p["moe"] = MOE.moe_params(k2, cfg, dtype)
    else:
        p["mlp"] = L.mlp_params(k2, cfg.d_model, cfg.d_ff, cfg.act, dtype)
    return p


def block_param_specs(kind: str, cfg: ModelConfig) -> Params:
    norm_spec = ({"scale": (None,)} if cfg.norm == "rmsnorm"
                 else {"scale": (None,), "bias": (None,)})
    if kind == "mamba":
        return {"norm1": norm_spec, "mamba": M.mamba2_param_specs(cfg)}
    if kind == "rwkv":
        return R.rwkv6_param_specs(cfg)
    p = {"norm1": dict(norm_spec), "norm2": dict(norm_spec)}
    if kind == "cross":
        p["cross"] = A.cross_attention_param_specs(cfg)
        p["xgate"] = (None,)
    else:
        p["attn"] = A.attention_param_specs(cfg)
    if _uses_moe(kind, cfg):
        p["moe"] = MOE.moe_param_specs(cfg)
    else:
        mlp = {"w_up": ("fsdp", "ffn"), "w_down": ("ffn", "fsdp")}
        if cfg.act == "swiglu":
            mlp["w_gate"] = ("fsdp", "ffn")
        p["mlp"] = mlp
    return p


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------

def block_state_init(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16, rules=None):
    if kind == "mamba":
        return M.init_mamba_state(cfg, batch, dtype)
    if kind == "rwkv":
        return R.init_rwkv_state(cfg, batch, dtype)
    if kind == "cross":
        hkv, dh, h = cfg.n_kv_heads, cfg.head_dim, cfg.n_heads
        n = cfg.n_img_tokens
        if cfg.attention_backend == "softmax":
            return A.CrossMemory(
                k=jnp.zeros((batch, hkv, n, dh), dtype),
                v=jnp.zeros((batch, hkv, n, dh), dtype), c=None, z=None)
        return A.CrossMemory(
            k=None, v=None,
            c=jnp.zeros((batch, hkv, dh, dh), jnp.float32),
            z=jnp.zeros((batch, hkv, dh), jnp.float32))
    return A.init_attn_state(cfg, batch, max_len, dtype, rules)


def block_state_specs(kind: str, cfg: ModelConfig):
    if kind == "mamba":
        return M.mamba_state_specs(cfg)
    if kind == "rwkv":
        return R.rwkv_state_specs(cfg)
    if kind == "cross":
        if cfg.attention_backend == "softmax":
            return A.CrossMemory(
                k=("batch", "kv_heads_state", None, "head_dim_state"),
                v=("batch", "kv_heads_state", None, "head_dim_state"),
                c=None, z=None)
        return A.CrossMemory(k=None, v=None,
                             c=("batch", "kv_heads_state", None, None),
                             z=("batch", "kv_heads_state", None))
    return A.attn_state_specs(cfg)


# ---------------------------------------------------------------------------
# apply (full sequence)
# ---------------------------------------------------------------------------

def block_apply(
    kind: str,
    p: Optional[Params],
    x: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    shared: Optional[Params] = None,
    memory: Optional[Array] = None,
    want_state: bool = False,
    varlen: Optional[Array] = None,
) -> Tuple[Array, Any, Array]:
    """Returns (x, state_or_None, aux_loss). ``varlen``: (B,) per-row
    valid lengths for bucket-padded batched prefill (attention blocks
    only — callers guard the pattern)."""
    zero = jnp.zeros((), jnp.float32)
    if kind == "shared_attn":
        p = shared
    if kind == "mamba":
        assert varlen is None, "varlen prefill: attention blocks only"
        h, st = M.mamba2_apply(p["mamba"], L.apply_norm(cfg.norm,
                               p["norm1"], x), cfg, rules,
                               want_state=want_state)
        return x + h, st, zero
    if kind == "rwkv":
        assert varlen is None, "varlen prefill: attention blocks only"
        x, st = R.rwkv6_apply(p, x, cfg, rules, want_state=want_state)
        return x, st, zero

    # attention family. Sub-block outputs are constrained to the
    # sequence-sharded residual layout BEFORE the adds, so GSPMD emits
    # reduce-scatter at the TP contraction instead of all-reduce + local
    # slice — Megatron-SP's ḡ, 1/3 less wire per sub-block (§Perf iter 10).
    h1 = L.apply_norm(cfg.norm, p["norm1"], x)
    if kind == "cross":
        assert varlen is None, "varlen prefill: attention blocks only"
        mem = A.encode_cross_memory(p["cross"], memory, cfg)
        att = A.cross_attention_apply(p["cross"], h1, mem, cfg, rules)
        att = jnp.tanh(p["xgate"]).astype(att.dtype) * att
        st = mem if want_state else None
    else:
        att, st = A.attention_apply(p["attn"], h1, cfg, rules,
                                    want_state=want_state, varlen=varlen)
    x = x + constrain(att, rules, "batch", "seq_sp", "embed")
    h2 = L.apply_norm(cfg.norm, p["norm2"], x)
    if _uses_moe(kind, cfg):
        ff, aux = MOE.moe_apply(p["moe"], h2, cfg, rules)
    else:
        ff, aux = L.mlp(p["mlp"], h2, cfg.act), zero
    return x + constrain(ff, rules, "batch", "seq_sp", "embed"), st, aux


# ---------------------------------------------------------------------------
# decode (single token)
# ---------------------------------------------------------------------------

def _freeze_rows(active: Array, new: Any, old: Any) -> Any:
    """Per-row (slot-axis-0) select over a block state pytree — the
    generic inactive-slot freeze for state kinds without a row-level
    masked write (Mamba conv/SSM states, RWKV mix states)."""
    def sel(n, o):
        shape = [1] * n.ndim
        shape[0] = active.shape[0]
        return jnp.where(active.reshape(shape), n, o)
    return jax.tree.map(sel, new, old)


def block_decode(
    kind: str,
    p: Optional[Params],
    x: Array,
    state: Any,
    pos: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    shared: Optional[Params] = None,
    active: Optional[Array] = None,
    layer: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """x: (B, D) one token per sequence; pos: () shared position or (B,)
    per-slot positions (continuous batching). ``active``: (B,) bool slot
    mask — inactive rows keep their state bit-for-bit (attention blocks
    mask at row granularity inside ``attention_decode``; other kinds via
    a generic per-leaf select). ``layer``: the block's index in its
    layer stack when ``state`` is that whole stack
    (``A.decodes_in_place``); the new stack comes back. Returns
    (x, new_state)."""
    if kind == "shared_attn":
        p = shared
    if kind == "mamba":
        h, st = M.mamba2_decode(
            p["mamba"], L.apply_norm(cfg.norm, p["norm1"], x), state, cfg,
            rules)
        if active is not None:
            st = _freeze_rows(active, st, state)
        return x + h, st
    if kind == "rwkv":
        x_out, st = R.rwkv6_decode(p, x, state, cfg, rules)
        if active is not None:
            st = _freeze_rows(active, st, state)
        return x_out, st

    h1 = L.apply_norm(cfg.norm, p["norm1"], x)
    if kind == "cross":
        att = A.cross_attention_apply(
            p["cross"], h1[:, None, :], state, cfg, rules)[:, 0]
        att = jnp.tanh(p["xgate"]).astype(att.dtype) * att
        st = state   # memory is static during decode
    else:
        with jax.named_scope("decode.attention"):
            att, st = A.attention_decode(p["attn"], h1, state, pos, cfg,
                                         rules, active=active,
                                         layer=layer)
    x = x + att
    h2 = L.apply_norm(cfg.norm, p["norm2"], x)
    with jax.named_scope("decode.mlp"):
        if _uses_moe(kind, cfg):
            ff, _ = MOE.moe_apply(p["moe"], h2, cfg, rules)
        else:
            ff = L.mlp(p["mlp"], h2, cfg.act)
    return x + ff, st


# ---------------------------------------------------------------------------
# decode (W-token window, one fused kernel launch per attention layer)
# ---------------------------------------------------------------------------

def block_decode_window(
    kind: str,
    p: Optional[Params],
    x: Array,
    state: Any,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    shared: Optional[Params] = None,
    lens: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """x: (B, W, D) — W known tokens per sequence; pos0: () shared
    window start or (B,) per-sequence starts (speculative verify in the
    slot engine). ``lens``: (B,) int32 per-row valid window lengths
    (variable-length masked windows; lens=0 rows frozen bit-for-bit).
    Returns (x, new_state).

    Attention blocks under the linear backends advance their fixed-size
    state W steps inside ONE fused recurrent kernel (masked per-row when
    ``lens`` is given); cross blocks are position-independent lookups
    against static memory; every other kind (softmax KV cache, Mamba,
    RWKV) falls back to scanning the single-token ``block_decode`` over
    the window — per-slot positions flow through ``pos0 + w`` into the
    per-slot KV-cache row writes, and ``lens`` becomes a per-step
    ``active = w < lens`` row mask on those writes.
    """
    if kind == "shared_attn":
        p = shared
    linear_attn = (kind in ("attn", "shared_attn")
                   and cfg.attention_backend in ("linear", "gated_linear"))
    if kind == "cross":
        h1 = L.apply_norm(cfg.norm, p["norm1"], x)
        att = A.cross_attention_apply(p["cross"], h1, state, cfg, rules)
        att = jnp.tanh(p["xgate"]).astype(att.dtype) * att
        st = state   # memory is static during decode
    elif linear_attn:
        h1 = L.apply_norm(cfg.norm, p["norm1"], x)
        att, st = A.attention_decode_window(
            p["attn"], h1, state, pos0, cfg, rules, lens=lens)
    else:
        def step(st, xw):
            x_t, w = xw
            act = None if lens is None else w < lens
            y, st = block_decode(kind, p, x_t, st, pos0 + w, cfg, rules,
                                 shared=shared, active=act)
            return st, y

        st, y = jax.lax.scan(
            step, state,
            (jnp.moveaxis(x, 1, 0), jnp.arange(x.shape[1])))
        return jnp.moveaxis(y, 0, 1), st

    x = x + att
    h2 = L.apply_norm(cfg.norm, p["norm2"], x)
    if _uses_moe(kind, cfg):
        ff, _ = MOE.moe_apply(p["moe"], h2, cfg, rules)
    else:
        ff = L.mlp(p["mlp"], h2, cfg.act)
    return x + ff, st


# ---------------------------------------------------------------------------
# ingest (chunk-PARALLEL varlen window — chunked-prefill continuation)
# ---------------------------------------------------------------------------

def block_ingest_window(
    kind: str,
    p: Optional[Params],
    x: Array,
    state: Any,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    shared: Optional[Params] = None,
    lens: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """Like :func:`block_decode_window`, but attention blocks under the
    linear backends continue their state through the chunk-PARALLEL
    prefill kernels (``attention_ingest_window``) instead of the
    sequential recurrence — prefill FLOPs per ingested chunk rather than
    W decode steps. Every other kind keeps the masked per-step fallback
    (the softmax cache has no cheap parallel continuation)."""
    linear_attn = (kind in ("attn", "shared_attn")
                   and cfg.attention_backend in ("linear", "gated_linear"))
    if not linear_attn or lens is None:
        return block_decode_window(kind, p, x, state, pos0, cfg, rules,
                                   shared=shared, lens=lens)
    if kind == "shared_attn":
        p = shared
    h1 = L.apply_norm(cfg.norm, p["norm1"], x)
    att, st = A.attention_ingest_window(
        p["attn"], h1, state, pos0, cfg, rules, lens=lens)
    x = x + att
    h2 = L.apply_norm(cfg.norm, p["norm2"], x)
    if _uses_moe(kind, cfg):
        ff, _ = MOE.moe_apply(p["moe"], h2, cfg, rules)
    else:
        ff = L.mlp(p["mlp"], h2, cfg.act)
    return x + ff, st
