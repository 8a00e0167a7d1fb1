"""GQA attention with pluggable backend — the paper's technique as a
first-class feature of every transformer layer.

``attention_backend`` selects:

* ``softmax``      — classic attention (paper §2): O(T²) compute, O(T·k)
                     decode state (the KV cache).
* ``linear``       — the paper's §3 mechanism in untied (q, k, v) form:
                     chunk-parallel causal linear attention, O(T·k²)
                     compute, **fixed-size (k×k per head) decode state**.
* ``gated_linear`` — the paper's §4 generalisation C ← αC + βffᵀ with
                     data-dependent decay α (per-channel "vector" mode =
                     GLA/RWKV-6 family; per-head "scalar" mode =
                     RetNet/Mamba-2 family) and optionally the paper's
                     exact sigmoid feature gate f = σ(Wh+b)⊙h.

All three backends share the projection/RoPE/GQA plumbing, so switching
the backend swaps only the O(T²)-vs-O(T·k²) core — exactly the paper's
"remove the softmax" ablation, at framework scale.

Decode state (``AttnState``) is a tagged union: KV cache for softmax,
(Dk, Dv) matrix state + key-sum normaliser for the linear family. The
linear decode step is O(k²) per token independent of context length —
the paper's fast-lookup property — which is what makes the ``long_500k``
shape lowerable for every arch under the linear backends.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.linear_attention import safe_denom
from repro.models import layers as L
from repro.models import xla_attention as xattn
from repro.sharding import Rules, constrain

Array = jax.Array
Params = Dict[str, Array]


# ---------------------------------------------------------------------------
# feature maps (linear backends)
# ---------------------------------------------------------------------------

def feature_map(x: Array, kind: str) -> Array:
    """φ applied to q/k before the linear-attention inner product.

    ``identity`` is the paper's exact formulation (φ(h) = h); ``elu1``
    (ELU+1, Katharopoulos et al.) keeps features positive so the key-sum
    normaliser is well conditioned — the documented deviation used by the
    LM backends.
    """
    if kind == "identity":
        return x
    if kind == "elu1":
        return jax.nn.elu(x) + 1.0
    if kind == "relu":
        return jax.nn.relu(x)
    raise ValueError(f"unknown feature map {kind!r}")


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def attention_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    p = {
        "wq": L.dense_init(ks[0], d, h * dh, dtype),
        "wk": L.dense_init(ks[1], d, hkv * dh, dtype),
        "wv": L.dense_init(ks[2], d, hkv * dh, dtype),
        "wo": L.dense_init(ks[3], h * dh, d, dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((dh,), dtype)
        p["k_norm"] = jnp.ones((dh,), dtype)
    if cfg.attention_backend == "gated_linear":
        # decay projection (paper §4 α_t as a data-dependent gate)
        gd = dh if cfg.decay_mode == "vector" else 1
        p["w_gate"] = L.dense_init(ks[4], d, h * gd, dtype, scale=0.01)
        p["b_gate"] = jnp.full((h * gd,), 4.0, dtype)  # init: slow decay
        p["gn_scale"] = jnp.ones((h, dh), dtype)
        p["gn_bias"] = jnp.zeros((h, dh), dtype)
    if cfg.attention_backend in ("linear", "gated_linear") and \
            cfg.feature_gate:
        # the paper's exact gate f = σ(W h + b) ⊙ h applied to keys/values
        p["w_fgate"] = L.dense_init(ks[5], d, hkv * dh, dtype)
        p["b_fgate"] = jnp.zeros((hkv * dh,), dtype)
    return p


def attention_param_specs(cfg: ModelConfig) -> Dict[str, tuple]:
    """Logical sharding names, same tree structure as attention_params.

    Projections are stored flat (d, h·dh); the flat output dim shards
    over the model axis (always divisible for the assigned archs even
    when the head *count* is not — e.g. yi-34b's 56×128 = 7168 = 16·448).
    Activation-side head sharding is chosen at apply time
    (:func:`softmax_shard_mode`).
    """
    p = {
        "wq": ("fsdp", "heads"),
        "wk": ("fsdp", "kv_heads_flat"),
        "wv": ("fsdp", "kv_heads_flat"),
        "wo": ("heads", "fsdp"),
    }
    if cfg.qk_norm:
        p["q_norm"] = (None,)
        p["k_norm"] = (None,)
    if cfg.attention_backend == "gated_linear":
        p["w_gate"] = ("fsdp", "heads")
        p["b_gate"] = ("heads",)
        p["gn_scale"] = ("heads", None)
        p["gn_bias"] = ("heads", None)
    if cfg.attention_backend in ("linear", "gated_linear") and \
            cfg.feature_gate:
        p["w_fgate"] = ("fsdp", "kv_heads_flat")
        p["b_fgate"] = ("kv_heads_flat",)
    return p


# ---------------------------------------------------------------------------
# decode state
# ---------------------------------------------------------------------------

class AttnState(NamedTuple):
    """Tagged decode state. Exactly one family of fields is used:

    softmax:  k_cache, v_cache (B, S, Hkv, Dh) + pos
    linear:   s (B, H, Dk, Dv) matrix state [+ z (B, H, Dk) normaliser]
              — the paper's fixed-size representation; O(1) in context.
    """
    k_cache: Optional[Array]
    v_cache: Optional[Array]
    s: Optional[Array]
    z: Optional[Array]


def init_attn_state(cfg: ModelConfig, batch: int, max_len: int,
                    dtype=jnp.bfloat16, rules: Optional[Rules] = None
                    ) -> AttnState:
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cfg.attention_backend == "softmax":
        return AttnState(
            k_cache=jnp.zeros((batch, max_len, hkv, dh), dtype),
            v_cache=jnp.zeros((batch, max_len, hkv, dh), dtype),
            s=None, z=None,
        )
    # linear family: pad the state head dim to the model-axis size so the
    # per-step state read-modify-write shards instead of replicating
    # (yi-34b: 56 heads on 16 → 28 GB/dev/step replicated; §Perf cell C)
    # z only exists when the normaliser is on — prefill and decode both
    # return z=None otherwise, and the scan-based generation loop needs
    # the state pytree structure to be step-invariant.
    hp = padded_head_count(rules, h) if rules is not None else h
    z = (jnp.zeros((batch, hp, dh), jnp.float32)
         if cfg.attention_backend == "linear" and cfg.linear_normalize
         else None)
    return AttnState(
        k_cache=None, v_cache=None,
        s=jnp.zeros((batch, hp, dh, dh), jnp.float32), z=z,
    )


def attn_state_specs(cfg: ModelConfig) -> AttnState:
    """Logical names for the decode state (same structure)."""
    if cfg.attention_backend == "softmax":
        return AttnState(
            k_cache=("batch", None, "kv_heads_state", "head_dim_state"),
            v_cache=("batch", None, "kv_heads_state", "head_dim_state"),
            s=None, z=None,
        )
    z = (("batch", "heads_state", None)
         if cfg.attention_backend == "linear" and cfg.linear_normalize
         else None)
    return AttnState(k_cache=None, v_cache=None,
                     s=("batch", "heads_state", None, None), z=z)


# ---------------------------------------------------------------------------
# shared projection plumbing
# ---------------------------------------------------------------------------

def softmax_shard_mode(cfg: ModelConfig, rules: Rules) -> str:
    """Pick the softmax-attention TP dim with the best utilisation.

    The model axis (size m) can shard the kv-head dim or the GQA group
    dim; neither need divide m — GSPMD pads uneven shards, costing
    ceil(n/m)·m/n waste. We pick whichever of Hkv / G wastes least
    (perfect division preferred). E.g. deepseek (Hkv=16) → "kv" at 1.0,
    qwen3-moe (G=16) → "group" at 1.0, yi-34b (Hkv=8, G=7, m=16) → "kv"
    at 0.5 — documented in DESIGN.md §5 as the 2×-waste fallback that a
    ring-attention shard_map path would remove.
    """
    m = rules.model_size
    if m <= 1:
        return "kv"

    def util(n: int) -> float:
        return n / (-(-n // m) * m)

    g = cfg.n_heads // cfg.n_kv_heads
    return "kv" if util(cfg.n_kv_heads) >= util(g) else "group"


def _project_qkv(p: Params, x: Array, cfg: ModelConfig, rules: Rules
                 ) -> Tuple[Array, Array, Array]:
    """x: (B, T, D) → q (B, G, Hkv, T, Dh), k/v (B, Hkv, T, Dh)."""
    b, t, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, t, g, hkv, dh)
    k = (x @ p["wk"].astype(x.dtype)).reshape(b, t, hkv, dh)
    v = (x @ p["wv"].astype(x.dtype)).reshape(b, t, hkv, dh)
    q = jnp.transpose(q, (0, 2, 3, 1, 4))      # (B, G, Hkv, T, Dh)
    k = jnp.transpose(k, (0, 2, 1, 3))         # (B, Hkv, T, Dh)
    v = jnp.transpose(v, (0, 2, 1, 3))
    if cfg.qk_norm:
        q = _head_rmsnorm(q, p["q_norm"])
        k = _head_rmsnorm(k, p["k_norm"])
    # all backends are constrained on the flattened-H view downstream:
    # the flat head dim shards over `model` (uneven allowed), which keeps
    # every loop-carried attention tensor on ONE consistent sharding —
    # group/kv-dim sharding churned inside scan carries (§Perf iter 2).
    return q, k, v


def padded_head_count(rules: Rules, h: int) -> int:
    """Round the flat head count up to a multiple of the model-axis size.

    GSPMD handles uneven dims by *resharding them inside loop bodies*
    (e.g. yi-34b's 56 heads on a 16-way axis → per-pair 896 MiB
    all-gathers, §Perf iteration 6). Explicit zero-padding keeps one even
    16-way layout through every scan; the pad heads cost ≤ (m−1)/h extra
    attention FLOPs and are sliced off before the output projection.
    """
    m = rules.model_size
    return -(-h // m) * m if m > 1 else h


def _pad_head_dim(x: Array, h_pad: int, axis: int = 1) -> Array:
    pad = h_pad - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _head_rmsnorm(x: Array, scale: Array, eps: float = 1e-6) -> Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)
            ).astype(x.dtype)


def _merge_heads(p: Params, o: Array, cfg: ModelConfig, x_dtype) -> Array:
    """o: (B, G, Hkv, T, Dh) → (B, T, D) through wo."""
    b, g, hkv, t, dh = o.shape
    o = jnp.transpose(o, (0, 3, 1, 2, 4)).reshape(b, t, g * hkv * dh)
    return o.astype(x_dtype) @ p["wo"].astype(x_dtype)


def _rope(q: Array, k: Array, positions: Array, cfg: ModelConfig
          ) -> Tuple[Array, Array]:
    """positions: (T,) shared, (B,) single-token decode, or (B, T)
    per-sequence windows (speculative verify: every slot's window starts
    at its own depth); q (B,G,Hkv,T,D), k (B,Hkv,T,D)."""
    cos, sin = L.rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta)
    if positions.ndim == 2:                              # (B, T) window
        c = cos[:, None, None]                           # (B,1,1,T,D/2)
        s = sin[:, None, None]
    elif positions.ndim == 1 and q.shape[3] == positions.shape[0]:
        c = cos[None, None, None]                        # (1,1,1,T,D/2)
        s = sin[None, None, None]
    else:                                                # decode: (B,)
        c = cos[:, None, None, None]
        s = sin[:, None, None, None]
    q = _apply_rot(q, c, s)
    k = _apply_rot(k, c[:, :, 0] if c.ndim == 5 else c,
                   s[:, :, 0] if s.ndim == 5 else s)
    return q, k


def _apply_rot(x: Array, c: Array, s: Array) -> Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    x1, x2 = jnp.split(xf, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c],
                           axis=-1).astype(dt)


def _gate_kv(p: Params, x: Array, k: Array, v: Array, cfg: ModelConfig
             ) -> Tuple[Array, Array]:
    """Paper §4 sigmoid feature gate: f = σ(W h + b) ⊙ h, applied to the
    key/value features that enter the state update C ← C + f fᵀ."""
    b, t, _ = x.shape
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    gate = jax.nn.sigmoid(x @ p["w_fgate"].astype(x.dtype)
                          + p["b_fgate"].astype(x.dtype))
    gate = jnp.transpose(gate.reshape(b, t, hkv, dh), (0, 2, 1, 3))
    return k * gate, v * gate


def _decay(p: Params, x: Array, cfg: ModelConfig) -> Array:
    """Data-dependent log-decay g_t ≤ 0 (the paper's α_t = exp(g_t)).

    Returns (B, H, T, Dk) for vector mode, (B, H, T, 1) for scalar.
    """
    b, t, _ = x.shape
    h = cfg.n_heads
    gd = cfg.head_dim if cfg.decay_mode == "vector" else 1
    raw = x @ p["w_gate"].astype(x.dtype) + p["b_gate"].astype(x.dtype)
    raw = jnp.transpose(raw.reshape(b, t, h, gd), (0, 2, 1, 3))
    # log α = −softplus(−raw)·scale: raw→+∞ ⇒ α→1 (remember);
    # raw→−∞ ⇒ α→0 (forget). Clamped in the chunked kernel.
    return -jax.nn.softplus(-raw.astype(jnp.float32)) * (
        1.0 / cfg.decay_temp)


# ---------------------------------------------------------------------------
# full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------

def attention_apply(
    p: Params,
    x: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    positions: Optional[Array] = None,
    want_state: bool = False,
    varlen: Optional[Array] = None,
) -> Tuple[Array, Optional[AttnState]]:
    """Full-sequence attention. x: (B, T, D) → (B, T, D).

    ``want_state=True`` additionally returns the decode state after the
    last position (prefill → decode handoff). For the linear backends the
    state is the paper's fixed-size k×k representation of the prefix.

    ``varlen``: (B,) int32 per-row valid prompt lengths for bucket-padded
    batched prefill. Rows are END-padded; the pad positions' key/value
    (and decay) contributions are zeroed before the state accumulation,
    so each row's state — and its logits at positions < varlen[b] — are
    BIT-IDENTICAL to prefilling that row alone unpadded: zero terms add
    exactly, exp(0)=1 decays multiply exactly, and causality already
    keeps later pad keys out of valid softmax queries. Outputs at pad
    positions are garbage the caller must ignore.
    """
    b, t, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    if positions is None:
        positions = jnp.arange(t)
    vmask = None
    if varlen is not None:
        # (B, 1, T, 1) over the flat-head (B, H, T, D) layout
        vmask = (jnp.arange(t)[None, :] <
                 jnp.asarray(varlen, jnp.int32)[:, None])[:, None, :, None]

    q, k, v = _project_qkv(p, x, cfg, rules)
    if cfg.rope:
        q, k = _rope(q, k, positions, cfg)

    backend = cfg.attention_backend
    state: Optional[AttnState] = None

    if backend == "softmax":
        # flash custom-VJP: O(T) residuals (vs O(T²) through scan-AD —
        # EXPERIMENTS.md §Perf iteration 1). K/V broadcast to the flat
        # q-head dim so train/prefill attention runs on ONE evenly
        # shardable layout (§Perf iteration 2); decode keeps the compact
        # (B, S, Hkv, D) GQA cache.
        hp = padded_head_count(rules, h)
        qh = constrain(
            _pad_head_dim(q.reshape(b, h, t, dh), hp), rules,
            "batch", "heads_lin", None, None)
        kh = constrain(_pad_head_dim(jnp.broadcast_to(
            k[:, None], (b, g, hkv, t, dh)).reshape(b, h, t, dh), hp),
            rules, "batch", "heads_lin", None, None)
        vh = constrain(_pad_head_dim(jnp.broadcast_to(
            v[:, None], (b, g, hkv, t, dh)).reshape(b, h, t, dh), hp),
            rules, "batch", "heads_lin", None, None)
        block_spec = (rules.spec(None, "batch", "heads_lin", None, None)
                      if rules.mesh_axes else None)
        o_h = xattn.flash_attention(qh, kh, vh, None, cfg.attn_block_q, 0,
                                    block_spec)
        o = o_h[:, :h].reshape(b, g, hkv, t, dh)
        if want_state:
            state = AttnState(
                k_cache=jnp.transpose(k, (0, 2, 1, 3)),
                v_cache=jnp.transpose(v, (0, 2, 1, 3)),
                s=None, z=None,
            )
    else:
        qf = feature_map(q, cfg.feature_map)
        kf = feature_map(k, cfg.feature_map)
        if cfg.feature_gate:
            kf, v = _gate_kv(p, x, kf, v, cfg)
        # expand GQA: per-q-head view (B, H, T, D) with k/v broadcast;
        # flat head dim padded to the model-axis size and sharded evenly
        # (§Perf iteration 6).
        hp = padded_head_count(rules, h)
        qh = constrain(
            _pad_head_dim(qf.reshape(b, h, t, dh), hp), rules,
            "batch", "heads_lin", None, None)
        kh = constrain(_pad_head_dim(jnp.broadcast_to(
            kf[:, None], (b, g, hkv, t, dh)).reshape(b, h, t, dh), hp),
            rules, "batch", "heads_lin", None, None)
        vh = constrain(_pad_head_dim(jnp.broadcast_to(
            v[:, None], (b, g, hkv, t, dh)).reshape(b, h, t, dh), hp),
            rules, "batch", "heads_lin", None, None)
        if vmask is not None:
            # zero pad-position k/v so they are inert in the state sum
            kh = jnp.where(vmask, kh, 0).astype(kh.dtype)
            vh = jnp.where(vmask, vh, 0).astype(vh.dtype)

        if backend == "linear":
            from repro.core.linear_attention import (
                causal_linear_attention, causal_linear_attention_chunked)
            if want_state:
                o_h, s_f = causal_linear_attention_chunked(
                    qh, kh, vh, chunk_size=cfg.linear_chunk,
                    normalize=cfg.linear_normalize,
                )
            else:  # training: the paper's §3.3 backward (recompute)
                o_h = causal_linear_attention(
                    qh, kh, vh, chunk_size=cfg.linear_chunk,
                    normalize=cfg.linear_normalize,
                )
                s_f = None
            if want_state:
                # state stays head-padded: decode consumes it directly.
                # The normaliser z = Σ_t k_t is a plain sum — the old
                # cumsum materialised a full (B,H,T,Dk) fp32 tensor only
                # to keep its last slice, and computed it even when the
                # normaliser was off.
                zf = (jnp.sum(kh.astype(jnp.float32), axis=2)
                      if cfg.linear_normalize else None)
                state = AttnState(k_cache=None, v_cache=None,
                                  s=s_f, z=zf)
        else:  # gated_linear
            from repro.core.gated import chunked_gla, \
                gated_linear_attention
            gd = _pad_head_dim(_decay(p, x, cfg), hp)
            if vmask is not None:
                # pad positions must not decay the state: log-decay 0
                gd = jnp.where(vmask[:, :, :, :1], gd, 0.0)
            if want_state:
                o_h, s_f = chunked_gla(
                    qh, kh, vh, gd, chunk_size=cfg.linear_chunk,
                )
            else:  # training: §3.3 recompute backward
                o_h = gated_linear_attention(
                    qh, kh, vh, gd, chunk_size=cfg.linear_chunk)
                s_f = None
            o_h = o_h[:, :h]
            o_h = L.groupnorm_heads(
                jnp.transpose(o_h, (0, 2, 1, 3)),
                p["gn_scale"].astype(jnp.float32),
                p["gn_bias"].astype(jnp.float32),
            )
            o_h = jnp.transpose(o_h, (0, 2, 1, 3))
            if want_state:
                state = AttnState(k_cache=None, v_cache=None,
                                  s=s_f, z=None)
        o = o_h[:, :h].reshape(b, g, hkv, t, dh)

    y = _merge_heads(p, o, cfg, x.dtype)
    return y, state


# ---------------------------------------------------------------------------
# single-token / windowed decode
# ---------------------------------------------------------------------------

def _use_fused_decode(cfg: ModelConfig) -> bool:
    """Resolve ``cfg.decode_kernel``. "auto" picks the Pallas kernels on
    TPU only — they use pltpu VMEM scratch and the sequential minor-grid
    carry, neither of which lowers on GPU — and the jnp scan reference
    everywhere else (on CPU Pallas would run under the slow interpreter;
    tests force "fused" to validate the kernel path via interpret mode).

    ``decode_kernel="fused"`` forced on any other backend (GPU, …)
    raises: the TPU-only kernels cannot lower there, and a silent switch
    to the reference would hide which path ran.
    """
    if cfg.decode_kernel == "auto":
        return jax.default_backend() == "tpu"
    if cfg.decode_kernel != "fused":
        return False
    platform = jax.default_backend()
    if platform in ("tpu", "cpu"):  # cpu: Pallas interpret mode
        return True
    raise ValueError(
        f"decode_kernel='fused' requested on platform {platform!r}, "
        "which cannot lower the TPU Pallas decode kernels (VMEM scratch "
        "/ minor-grid carry); use decode_kernel='auto' or 'reference'")


def decodes_in_place(state, cfg: ModelConfig) -> bool:
    """Whether a layer scan carries this block state whole, as a stack,
    for each layer's fused kernel to advance its own layer in place:
    the linear family's matrix state (an ``AttnState`` holding ``s``)
    under the fused decode kernel. Every other state — KV caches, Mamba,
    RWKV, cross memory, anything under the jnp reference — is sliced
    out per layer by the scan and written back."""
    return (isinstance(state, AttnState) and state.s is not None
            and _use_fused_decode(cfg))


def _recurrent_linear(s, q, k, v, z, cfg: ModelConfig, lens=None,
                      layer=None):
    """W-step linear decode recurrence behind ``cfg.decode_kernel``:
    the fused Pallas kernel (VMEM-resident state, in-place HBM update)
    or the jnp scan reference. Shapes: s (B,H,Dk,Dv); q,k (B,H,W,Dk);
    v (B,H,W,Dv); z (B,H,Dk)|None; lens (B,)|None per-row valid
    lengths (varlen masked kernels). ``layer``: an int32 scalar with s
    (and z) stacked over layers, (L,B,H,Dk,Dv) — the fused kernel's
    in-place stacked entry."""
    from repro.kernels.fused_recurrent import ops as FR
    from repro.kernels.fused_recurrent import ref as FRref
    if _use_fused_decode(cfg):
        return FR.fused_recurrent_linear(
            s, q, k, v, z=z, normalize=cfg.linear_normalize, lens=lens,
            layer=layer)
    assert layer is None, "a stacked state needs the fused decode kernel"
    return FRref.fused_recurrent_linear_ref(
        s, q, k, v, z=z, normalize=cfg.linear_normalize, lens=lens)


def _recurrent_gated(s, q, k, v, g, cfg: ModelConfig, lens=None,
                     layer=None):
    """W-step gated decode recurrence behind ``cfg.decode_kernel``.
    Shapes: s (B,H,Dk,Dv), or (L,B,H,Dk,Dv) with ``layer``;
    q,k,g (B,H,W,Dk); v (B,H,W,Dv); lens (B,)|None."""
    from repro.kernels.fused_recurrent import ops as FR
    from repro.kernels.fused_recurrent import ref as FRref
    if _use_fused_decode(cfg):
        return FR.fused_recurrent_gated(s, q, k, v, g, lens=lens,
                                        layer=layer)
    assert layer is None, "a stacked state needs the fused decode kernel"
    return FRref.fused_recurrent_gated_ref(s, q, k, v, g, lens=lens)


def attention_decode(
    p: Params,
    x: Array,
    state: AttnState,
    pos: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    active: Optional[Array] = None,
    layer: Optional[Array] = None,
) -> Tuple[Array, AttnState]:
    """One decode step. x: (B, D); pos: () current position, or (B,)
    per-sequence positions (continuous batching: each slot sits at its
    own point in its own request).

    softmax: O(pos) cache read. linear family: O(k²) — independent of pos
    (the paper's constant-time lookup).

    ``active``: (B,) bool slot mask. An inactive row's state is frozen
    bit-for-bit AT ROW GRANULARITY: the linear family's fused kernel
    leaves the row untouched in place (its varlen mask at lens = 0), the
    jnp reference selects its O(k²) matrix after the step, and the
    softmax baseline gates the ONE written KV-cache row — reading the
    current row back and writing where(active, new, current) — instead
    of a whole-(max_len) cache select per step, which is what makes slot
    masking affordable for the KV-cache backend at large max_len.

    ``layer``: an int32 scalar when ``state`` is the whole layer stack
    of a linear-family block under the fused kernel
    (:func:`decodes_in_place`): s (L, B, H, Dk, Dv) [, z (L, B, H, Dk)].
    The kernel advances that layer in place in the stack, which comes
    back whole.
    """
    b, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    pos = jnp.asarray(pos, jnp.int32)
    xt = x[:, None, :]  # (B, 1, D)
    q, k, v = _project_qkv(p, xt, cfg, rules)
    if cfg.rope:
        posb = jnp.broadcast_to(pos, (b,))
        q, k = _rope(q, k, posb, cfg)

    backend = cfg.attention_backend
    if backend == "softmax":
        k_new = jnp.transpose(k, (0, 2, 1, 3)).astype(state.k_cache.dtype)
        v_new = jnp.transpose(v, (0, 2, 1, 3)).astype(state.v_cache.dtype)
        if pos.ndim == 0 and active is None:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                state.k_cache, k_new, pos, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                state.v_cache, v_new, pos, axis=1)
        elif active is None:  # per-slot positions: one row per sequence
            upd = jax.vmap(
                lambda c, u, p_i: jax.lax.dynamic_update_slice_in_dim(
                    c, u, p_i, axis=0))
            k_cache = upd(state.k_cache, k_new, pos)
            v_cache = upd(state.v_cache, v_new, pos)
        else:
            # row-level slot masking: write where(active, new, current)
            # back to the row — an inactive slot's cache is untouched
            # bit-for-bit at O(row) cost instead of an O(max_len) select
            posb = jnp.broadcast_to(pos, (b,))

            def upd_row(c, u, p_i, a_i):
                cur = jax.lax.dynamic_slice_in_dim(c, p_i, 1, axis=0)
                return jax.lax.dynamic_update_slice_in_dim(
                    c, jnp.where(a_i, u, cur), p_i, axis=0)

            upd = jax.vmap(upd_row)
            k_cache = upd(state.k_cache, k_new, posb, active)
            v_cache = upd(state.v_cache, v_new, posb, active)
        kc = jnp.transpose(k_cache, (0, 2, 1, 3))
        vc = jnp.transpose(v_cache, (0, 2, 1, 3))
        o = xattn.decode_attention(q[:, :, :, 0], kc, vc, pos + 1)
        new_state = AttnState(k_cache=k_cache, v_cache=v_cache,
                              s=None, z=None)
    else:
        qf = feature_map(q[:, :, :, 0], cfg.feature_map)   # (B,G,Hkv,Dh)
        kf = feature_map(k[:, :, 0], cfg.feature_map)      # (B,Hkv,Dh)
        vt = v[:, :, 0]
        if cfg.feature_gate:
            k2, v2 = _gate_kv(p, xt, kf[:, :, None], vt[:, :, None], cfg)
            kf, vt = k2[:, :, 0], v2[:, :, 0]
        hp = state.s.shape[-3]         # padded head count (≥ h)
        qh = _pad_head_dim(qf.reshape(b, h, dh), hp)
        kh = _pad_head_dim(jnp.broadcast_to(
            kf[:, None], (b, g, hkv, dh)).reshape(b, h, dh), hp)
        vh = _pad_head_dim(jnp.broadcast_to(
            vt[:, None], (b, g, hkv, dh)).reshape(b, h, dh), hp)
        # the fused kernel freezes inactive rows itself (lens = 0 at
        # W = 1); the reference selects after the step
        fused = _use_fused_decode(cfg)
        lens = None if active is None or not fused \
            else active.astype(jnp.int32)
        freeze = active is not None and not fused

        if backend == "linear":
            o_w, s_new, z_new = _recurrent_linear(
                state.s, qh[:, :, None], kh[:, :, None], vh[:, :, None],
                state.z, cfg, lens=lens, layer=layer)
            o_h = o_w[:, :, 0]
            if freeze:  # O(k²) per-row freeze
                sel = active[:, None, None, None]
                s_new = jnp.where(sel, s_new, state.s)
                if z_new is not None:
                    z_new = jnp.where(sel[..., 0], z_new, state.z)
            new_state = AttnState(k_cache=None, v_cache=None,
                                  s=s_new, z=z_new)
        else:
            gd = _decay(p, xt, cfg)[:, :, 0]               # (B, H, gd)
            gd = jnp.broadcast_to(gd, (b, h, dh)) if gd.shape[-1] == 1 \
                else gd
            gd = _pad_head_dim(gd, hp)
            o_w, s_new = _recurrent_gated(
                state.s, qh[:, :, None], kh[:, :, None], vh[:, :, None],
                gd[:, :, None], cfg, lens=lens, layer=layer)
            o_h = o_w[:, :, 0]
            o_h = L.groupnorm_heads(
                o_h[:, :h][:, None], p["gn_scale"].astype(jnp.float32),
                p["gn_bias"].astype(jnp.float32))[:, 0]
            if freeze:  # O(k²) per-row freeze
                s_new = jnp.where(active[:, None, None, None],
                                  s_new, state.s)
            new_state = AttnState(k_cache=None, v_cache=None,
                                  s=s_new, z=None)
        o = o_h[:, :h].reshape(b, g, hkv, dh)

    y = _merge_heads(p, o[:, :, :, None], cfg, x.dtype)[:, 0]
    return y, new_state


def attention_decode_window(
    p: Params,
    x: Array,
    state: AttnState,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    lens: Optional[Array] = None,
) -> Tuple[Array, AttnState]:
    """Decode W known tokens in one fused kernel launch.

    x: (B, W, D) token activations; pos0: () position of the first, or
    (B,) per-sequence window start positions (speculative verify in the
    slot engine). Linear family only — the fixed-size state advances W
    steps inside the kernel with the state VMEM-resident, so per-window
    HBM state traffic is O(Dk·Dv) instead of O(W·Dk·Dv). The softmax
    KV-cache backend has no such recurrence; callers fall back to
    scanning single-token decode (see blocks.block_decode_window).

    ``lens``: (B,) int32 per-row valid window lengths — row b advances
    only its first lens[b] tokens through the varlen masked kernels
    (lens=0 rows frozen bit-for-bit), so ONE launch serves slots
    consuming different token counts (chunked admission, batched
    speculative rewind).
    """
    backend = cfg.attention_backend
    assert backend in ("linear", "gated_linear"), backend
    b, w, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    q, k, v = _project_qkv(p, x, cfg, rules)
    if cfg.rope:
        pos0 = jnp.asarray(pos0, jnp.int32)
        positions = (pos0[:, None] + jnp.arange(w) if pos0.ndim == 1
                     else pos0 + jnp.arange(w))
        q, k = _rope(q, k, positions, cfg)

    qf = feature_map(q, cfg.feature_map)       # (B, G, Hkv, W, Dh)
    kf = feature_map(k, cfg.feature_map)       # (B, Hkv, W, Dh)
    if cfg.feature_gate:
        kf, v = _gate_kv(p, x, kf, v, cfg)
    hp = state.s.shape[1]          # padded head count (≥ h)
    qh = _pad_head_dim(qf.reshape(b, h, w, dh), hp)
    kh = _pad_head_dim(jnp.broadcast_to(
        kf[:, None], (b, g, hkv, w, dh)).reshape(b, h, w, dh), hp)
    vh = _pad_head_dim(jnp.broadcast_to(
        v[:, None], (b, g, hkv, w, dh)).reshape(b, h, w, dh), hp)

    if lens is not None:
        lens = jnp.clip(jnp.asarray(lens, jnp.int32), 0, w)
    if backend == "linear":
        o_w, s_new, z_new = _recurrent_linear(
            state.s, qh, kh, vh, state.z, cfg, lens=lens)
        new_state = AttnState(k_cache=None, v_cache=None,
                              s=s_new, z=z_new)
    else:
        gd = _decay(p, x, cfg)                             # (B, H, W, gd)
        gd = jnp.broadcast_to(gd, (b, h, w, dh)) if gd.shape[-1] == 1 \
            else gd
        gd = _pad_head_dim(gd, hp)
        o_w, s_new = _recurrent_gated(state.s, qh, kh, vh, gd, cfg,
                                      lens=lens)
        o_w = L.groupnorm_heads(
            jnp.transpose(o_w[:, :h], (0, 2, 1, 3)),
            p["gn_scale"].astype(jnp.float32),
            p["gn_bias"].astype(jnp.float32),
        )
        o_w = jnp.transpose(o_w, (0, 2, 1, 3))
        new_state = AttnState(k_cache=None, v_cache=None,
                              s=s_new, z=None)

    o = o_w[:, :h].reshape(b, g, hkv, w, dh)
    y = _merge_heads(p, o, cfg, x.dtype)
    return y, new_state


def attention_ingest_window(
    p: Params,
    x: Array,
    state: AttnState,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    lens: Array,
) -> Tuple[Array, AttnState]:
    """Chunk-PARALLEL variable-length window: continue a partially
    encoded prefix over up to W more known tokens per row.

    x: (B, W, D); pos0: (B,) per-row window start positions; lens: (B,)
    valid counts (0 = inert row). Linear family only. Unlike
    :func:`attention_decode_window` (the sequential recurrent form, one
    state update per token), this runs the same chunk-parallel kernels
    as prefill — masked pad/invalid positions contribute zero key/value
    terms and exp(0)=1 decay — CONTINUING from the carried state (and
    key-sum normaliser), so long-prompt ingestion costs prefill FLOPs,
    not W sequential decode steps. Chunked-prefill continuation is the
    intended caller; outputs at masked positions are garbage.
    """
    backend = cfg.attention_backend
    assert backend in ("linear", "gated_linear"), backend
    b, w, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    lens = jnp.clip(jnp.asarray(lens, jnp.int32), 0, w)
    q, k, v = _project_qkv(p, x, cfg, rules)
    if cfg.rope:
        pos0 = jnp.asarray(pos0, jnp.int32)
        positions = jnp.broadcast_to(pos0, (b,))[:, None] + jnp.arange(w)
        q, k = _rope(q, k, positions, cfg)

    qf = feature_map(q, cfg.feature_map)       # (B, G, Hkv, W, Dh)
    kf = feature_map(k, cfg.feature_map)       # (B, Hkv, W, Dh)
    if cfg.feature_gate:
        kf, v = _gate_kv(p, x, kf, v, cfg)
    hp = state.s.shape[1]          # padded head count (≥ h)
    qh = _pad_head_dim(qf.reshape(b, h, w, dh), hp)
    kh = _pad_head_dim(jnp.broadcast_to(
        kf[:, None], (b, g, hkv, w, dh)).reshape(b, h, w, dh), hp)
    vh = _pad_head_dim(jnp.broadcast_to(
        v[:, None], (b, g, hkv, w, dh)).reshape(b, h, w, dh), hp)
    vmask = (jnp.arange(w)[None, :] < lens[:, None])[:, None, :, None]
    kh = jnp.where(vmask, kh, 0).astype(kh.dtype)
    vh = jnp.where(vmask, vh, 0).astype(vh.dtype)

    if backend == "linear":
        from repro.core.linear_attention import (
            causal_linear_attention_chunked)
        o_w, s_new = causal_linear_attention_chunked(
            qh, kh, vh, chunk_size=cfg.linear_chunk,
            initial_state=state.s, initial_z=state.z,
            normalize=cfg.linear_normalize)
        z_new = (state.z + jnp.sum(kh.astype(jnp.float32), axis=2)
                 if cfg.linear_normalize else None)
        new_state = AttnState(k_cache=None, v_cache=None,
                              s=s_new, z=z_new)
    else:
        from repro.core.gated import chunked_gla
        gd = _decay(p, x, cfg)                             # (B, H, W, gd)
        gd = _pad_head_dim(gd, hp)
        gd = jnp.where(vmask[:, :, :, :1], gd, 0.0)  # inert: exp(0)=1
        o_w, s_new = chunked_gla(
            qh, kh, vh, gd, chunk_size=cfg.linear_chunk,
            initial_state=state.s)
        o_w = L.groupnorm_heads(
            jnp.transpose(o_w[:, :h], (0, 2, 1, 3)),
            p["gn_scale"].astype(jnp.float32),
            p["gn_bias"].astype(jnp.float32),
        )
        o_w = jnp.transpose(o_w, (0, 2, 1, 3))
        new_state = AttnState(k_cache=None, v_cache=None,
                              s=s_new, z=None)

    o = o_w[:, :h].reshape(b, g, hkv, w, dh)
    y = _merge_heads(p, o, cfg, x.dtype)
    return y, new_state


# ---------------------------------------------------------------------------
# cross attention (VLM) — the paper's document/query setting verbatim
# ---------------------------------------------------------------------------

def cross_attention_params(key, cfg: ModelConfig, dtype=jnp.float32
                           ) -> Params:
    d, h, hkv, dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    return {
        "wq": L.dense_init(ks[0], d, h * dh, dtype),
        "wk": L.dense_init(ks[1], d, hkv * dh, dtype),
        "wv": L.dense_init(ks[2], d, hkv * dh, dtype),
        "wo": L.dense_init(ks[3], h * dh, d, dtype),
    }


def cross_attention_param_specs(cfg: ModelConfig) -> Dict[str, tuple]:
    return {
        "wq": ("fsdp", "heads"),
        "wk": ("fsdp", "kv_heads_flat"),
        "wv": ("fsdp", "kv_heads_flat"),
        "wo": ("heads", "fsdp"),
    }


class CrossMemory(NamedTuple):
    """Pre-encoded modality memory. softmax keeps (k, v) — O(n_img·k)
    per layer; linear keeps the paper's C = KᵀV fixed-size state —
    O(k²) per layer regardless of image-token count."""
    k: Optional[Array]
    v: Optional[Array]
    c: Optional[Array]
    z: Optional[Array]


def encode_cross_memory(p: Params, memory: Array, cfg: ModelConfig
                        ) -> CrossMemory:
    """memory: (B, N_img, D) precomputed patch embeddings (frontend stub).

    This is exactly the paper's encode-once document compression: for the
    linear backend the N_img×k key/value matrices collapse into C = KᵀV.
    """
    b, n, _ = memory.shape
    hkv, dh = cfg.n_kv_heads, cfg.head_dim
    k = jnp.transpose((memory @ p["wk"].astype(memory.dtype))
                      .reshape(b, n, hkv, dh), (0, 2, 1, 3))
    v = jnp.transpose((memory @ p["wv"].astype(memory.dtype))
                      .reshape(b, n, hkv, dh), (0, 2, 1, 3))
    if cfg.attention_backend == "softmax":
        return CrossMemory(k=k, v=v, c=None, z=None)
    kf = feature_map(k, cfg.feature_map)
    c = jnp.einsum("bhnk,bhnv->bhkv", kf.astype(jnp.float32),
                   v.astype(jnp.float32))
    z = jnp.sum(kf.astype(jnp.float32), axis=2)
    return CrossMemory(k=None, v=None, c=c, z=z)


def cross_attention_apply(p: Params, x: Array, mem: CrossMemory,
                          cfg: ModelConfig, rules: Rules) -> Array:
    """x: (B, T, D) queries against the encoded memory → (B, T, D)."""
    b, t, _ = x.shape
    h, hkv, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    g = h // hkv
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, t, g, hkv, dh)
    q = jnp.transpose(q, (0, 2, 3, 1, 4))
    if cfg.attention_backend == "softmax":
        n = mem.k.shape[2]
        scores = jnp.einsum(
            "bghtd,bhnd->bghtn", q.astype(jnp.float32) * dh ** -0.5,
            mem.k.astype(jnp.float32))
        pr = jax.nn.softmax(scores, axis=-1)
        o = jnp.einsum("bghtn,bhnd->bghtd", pr,
                       mem.v.astype(jnp.float32)).astype(x.dtype)
    else:
        qf = feature_map(q, cfg.feature_map).astype(jnp.float32)
        o = jnp.einsum("bghtk,bhkv->bghtv", qf, mem.c)
        if cfg.linear_normalize:
            denom = jnp.einsum("bghtk,bhk->bght", qf, mem.z)
            o = o / safe_denom(denom)[..., None]
        o = o.astype(x.dtype)
    return _merge_heads(p, o, cfg, x.dtype)
