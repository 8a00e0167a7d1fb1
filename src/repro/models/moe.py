"""Mixture-of-Experts FFN (DeepSeekMoE / Qwen3-MoE style).

Shared experts (always-on dense MLPs, DeepSeekMoE's "2 shared") are folded
into one dense SwiGLU of width ``n_shared · d_ff_expert``. Routed experts
use top-k softmax routing with a *sort-based capacity dispatch*:

  1. every (token, k-choice) pair is ranked within its expert by routing
     weight order (stable argsort over expert ids),
  2. pairs whose intra-expert rank exceeds the capacity
     ``C = ceil(cap_factor · N · k / E)`` are dropped (weight 0) —
     GShard-style dropping, bounded buffers,
  3. kept pairs are scattered into an (E·C, D) buffer, the experts run as
     one batched (E, C, D) × (E, D, F) einsum (MXU-shaped, experts sharded
     over the ``model`` axis = expert parallelism), and outputs scatter
     back weighted by the router.

Memory is O(N·k + E·C·D) — no (N, E, C) one-hot dispatch tensor. Under
plain ``jit`` GSPMD chooses the collectives for the gather/scatter across
the expert-sharded buffer; the explicit ``shard_map`` all-to-all variant
is the §Perf hillclimb path (see EXPERIMENTS.md).

The router aux loss is the standard load-balance loss
``E · Σ_e f_e · p_e`` (fraction-of-tokens × mean-probability).
"""

from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.sharding import Rules, constrain

Array = jax.Array
Params = Dict[str, Array]


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------

def moe_params(key, cfg: ModelConfig, dtype=jnp.float32) -> Params:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    e, f = m.n_experts, m.d_ff_expert
    scale = 1.0 / (d ** 0.5)
    p = {
        "router": L.dense_init(ks[0], d, e, jnp.float32, scale=0.02),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * scale).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * scale).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * scale /
                   (2 * cfg.n_layers) ** 0.5).astype(dtype),
    }
    if m.n_shared > 0:
        p["shared"] = L.mlp_params(ks[4], d, m.n_shared * f, "swiglu", dtype)
    return p


def moe_param_specs(cfg: ModelConfig) -> Dict[str, tuple]:
    p = {
        "router": (None, None),                 # tiny; replicated
        "w_gate": ("experts", "fsdp", None),
        "w_up": ("experts", "fsdp", None),
        "w_down": ("experts", None, "fsdp"),
    }
    if cfg.moe.n_shared > 0:
        p["shared"] = {
            "w_up": ("fsdp", "ffn"),
            "w_gate": ("fsdp", "ffn"),
            "w_down": ("ffn", "fsdp"),
        }
    return p


# ---------------------------------------------------------------------------
# routing + dispatch
# ---------------------------------------------------------------------------

def route(router_w: Array, x_flat: Array, top_k: int
          ) -> Tuple[Array, Array, Array]:
    """x_flat: (N, D) → (weights (N,K), experts (N,K), aux_loss ()).

    Softmax-then-topk with renormalised weights (DeepSeek/Mixtral style).
    """
    logits = x_flat.astype(jnp.float32) @ router_w.astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                    # (N, E)
    weights, experts = jax.lax.top_k(probs, top_k)             # (N, K)
    weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-9)

    e = logits.shape[-1]
    # load-balance aux: E · Σ_e (token fraction to e) · (mean prob of e)
    onehot = jax.nn.one_hot(experts, e, dtype=jnp.float32)     # (N, K, E)
    frac = jnp.mean(jnp.sum(onehot, axis=1), axis=0)           # (E,)
    mean_p = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_p) / top_k
    return weights, experts, aux


def capacity(n_tokens: int, top_k: int, n_experts: int, factor: float
             ) -> int:
    c = int(factor * n_tokens * top_k / n_experts)
    return max(8, -(-c // 8) * 8)  # round up to 8 for clean tiling


def moe_apply(p: Params, x: Array, cfg: ModelConfig, rules: Rules
              ) -> Tuple[Array, Array]:
    """x: (B, T, D) → (out (B, T, D), aux_loss ()). Also handles (B, D).

    Dispatch strategy: the explicit shard_map all-to-all path whenever a
    model axis exists and divides the expert count (§Perf cell A — GSPMD
    replicates the (N·K, D) dispatch tensor otherwise); the einsum path
    is the single-device / baseline fallback.
    """
    m = cfg.moe
    if (m.dispatch == "shard_map" and rules.model_size > 1
            and m.n_experts % rules.model_size == 0 and x.ndim == 3):
        return moe_apply_shard_map(p, x, cfg, rules)
    squeeze = x.ndim == 2
    if squeeze:
        x = x[:, None, :]
    b, t, d = x.shape
    n = b * t
    x_flat = x.reshape(n, d)

    weights, experts, aux = route(p["router"], x_flat, m.top_k)
    cap = capacity(n, m.top_k, m.n_experts, m.capacity_factor)

    # ---- rank each (token, choice) within its expert --------------------
    flat_expert = experts.reshape(-1)                          # (N*K,)
    # stable sort by expert id; position within the sorted segment is the
    # intra-expert rank. order[i] = index of i-th pair in sorted order.
    order = jnp.argsort(flat_expert, stable=True)
    # rank_in_sorted[j] = j - start_of_segment(expert_of(order[j]))
    sorted_experts = flat_expert[order]
    seg_start = jnp.searchsorted(sorted_experts,
                                 jnp.arange(m.n_experts), side="left")
    rank_sorted = jnp.arange(n * m.top_k) - seg_start[sorted_experts]
    rank = jnp.zeros((n * m.top_k,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))

    keep = rank < cap
    slot = jnp.where(keep, flat_expert * cap + rank, m.n_experts * cap)

    # ---- dispatch: scatter tokens into the (E·C, D) expert buffer -------
    token_idx = jnp.repeat(jnp.arange(n), m.top_k)
    buf = jnp.zeros((m.n_experts * cap + 1, d), x.dtype)
    buf = buf.at[slot].set(x_flat[token_idx], mode="drop")
    expert_in = buf[:-1].reshape(m.n_experts, cap, d)
    expert_in = constrain(expert_in, rules, "experts", None, None)

    # ---- expert computation: batched SwiGLU over the expert dim ---------
    gate = jnp.einsum("ecd,edf->ecf", expert_in,
                      p["w_gate"].astype(x.dtype))
    up = jnp.einsum("ecd,edf->ecf", expert_in, p["w_up"].astype(x.dtype))
    act = jax.nn.silu(gate) * up
    expert_out = jnp.einsum("ecf,efd->ecd", act,
                            p["w_down"].astype(x.dtype))
    expert_out = constrain(expert_out, rules, "experts", None, None)

    # ---- combine: gather slots back, weight, and sum over k -------------
    out_flat = expert_out.reshape(m.n_experts * cap, d)
    out_flat = jnp.concatenate(
        [out_flat, jnp.zeros((1, d), x.dtype)], axis=0)       # drop slot
    gathered = out_flat[slot]                                  # (N*K, D)
    w = (weights.reshape(-1) * keep).astype(x.dtype)
    combined = jax.ops.segment_sum(
        gathered * w[:, None], token_idx, num_segments=n)

    # ---- shared experts (always-on dense path) ---------------------------
    if m.n_shared > 0:
        combined = combined + L.mlp(p["shared"], x_flat, "swiglu")

    out = combined.reshape(b, t, d)
    if squeeze:
        out = out[:, 0]
    return out, aux.astype(jnp.float32)


# ---------------------------------------------------------------------------
# shard_map expert parallelism — explicit all_to_all dispatch
# ---------------------------------------------------------------------------
#
# Per-device program (tokens arrive (B_loc, T_loc, D): batch over the DP
# axes, sequence over the model axis — exactly the sequence-parallel
# residual layout, so dispatch starts from fully-sharded tokens):
#
#   1. route locally; build an (E, cap_src, D) send buffer by the same
#      sort/scatter used in the einsum path (all local);
#   2. all_to_all over the model axis: device m receives, for each of its
#      E/M local experts, the cap_src-token slices from every peer —
#      wire bytes per device ≈ N_loc·K·capfactor·D, ~300× less than the
#      GSPMD-replicated dispatch (EXPERIMENTS.md §Perf cell A);
#   3. experts' weights are FSDP-sharded on d_model: explicit all_gather
#      over the DP axes (reverse-mode: reduce-scatter of their grads);
#   4. batched expert SwiGLU; reverse all_to_all; local weighted combine.
#
# The router aux tallies are psum'd over all axes so every device returns
# the identical global load-balance loss.

def _ambient_mesh():
    from jax.interpreters import pxla
    return pxla.thread_resources.env.physical_mesh


def moe_apply_shard_map(p: Params, x: Array, cfg: ModelConfig,
                        rules: Rules) -> Tuple[Array, Array]:
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    mesh = _ambient_mesh()
    model_ax = "model"
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    M = rules.model_size
    e_loc = m.n_experts // M

    x_spec = rules.spec("batch", "seq_sp", None, shape=x.shape)
    w_spec = rules.spec("experts", "fsdp", None)
    w_spec_t = rules.spec("experts", None, "fsdp")

    def body(x_blk, router_w, w_gate, w_up, w_down):
        nb, tb, d = x_blk.shape
        n_loc = nb * tb
        xf = x_blk.reshape(n_loc, d)

        # -- local routing + aux tallies (psum'd to global) ---------------
        logits = xf.astype(jnp.float32) @ router_w.astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = jax.lax.top_k(probs, m.top_k)
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-9)
        onehot = jax.nn.one_hot(experts, m.n_experts, dtype=jnp.float32)
        cnt = jnp.sum(onehot, axis=(0, 1))                  # (E,)
        psum_axes = dp_axes + (model_ax,)
        cnt_g = jax.lax.psum(cnt, psum_axes)
        p_g = jax.lax.psum(jnp.sum(probs, 0), psum_axes)
        n_g = n_loc * mesh.devices.size
        aux = m.n_experts * jnp.sum(
            (cnt_g / (n_g * m.top_k)) * (p_g / n_g))

        # -- local capacity dispatch (same sort trick, local shapes) ------
        cap = capacity(n_loc, m.top_k, m.n_experts, m.capacity_factor)
        flat_expert = experts.reshape(-1)
        order = jnp.argsort(flat_expert, stable=True)
        sorted_experts = flat_expert[order]
        seg_start = jnp.searchsorted(
            sorted_experts, jnp.arange(m.n_experts), side="left")
        rank_sorted = jnp.arange(n_loc * m.top_k) \
            - seg_start[sorted_experts]
        rank = jnp.zeros((n_loc * m.top_k,), jnp.int32).at[order].set(
            rank_sorted.astype(jnp.int32))
        keep = rank < cap
        slot = jnp.where(keep, flat_expert * cap + rank,
                         m.n_experts * cap)
        token_idx = jnp.repeat(jnp.arange(n_loc), m.top_k)
        send = jnp.zeros((m.n_experts * cap + 1, d), x_blk.dtype)
        send = send.at[slot].set(xf[token_idx], mode="drop")

        # -- all_to_all over the model axis --------------------------------
        send = send[:-1].reshape(M, e_loc * cap, d)
        recv = jax.lax.all_to_all(
            send, model_ax, split_axis=0, concat_axis=0, tiled=False)
        # recv[src, :, :] = slices sent by peer src for MY local experts
        expert_in = jnp.transpose(
            recv.reshape(M, e_loc, cap, d), (1, 0, 2, 3)
        ).reshape(e_loc, M * cap, d)

        # -- FSDP gather of local expert weights ---------------------------
        def fsdp_gather(w):
            for ax in dp_axes:
                w = jax.lax.all_gather(w, ax, axis=1, tiled=True)
            return w

        wg = fsdp_gather(w_gate)            # (E_loc, D, F)
        wu = fsdp_gather(w_up)
        wd_ = w_down                        # (E_loc, F, D_loc): gather on
        for ax in dp_axes:                  # the OUTPUT dim instead
            wd_ = jax.lax.all_gather(wd_, ax, axis=2, tiled=True)

        gate = jnp.einsum("ecd,edf->ecf", expert_in,
                          wg.astype(x_blk.dtype))
        up = jnp.einsum("ecd,edf->ecf", expert_in,
                        wu.astype(x_blk.dtype))
        act = jax.nn.silu(gate) * up
        expert_out = jnp.einsum("ecf,efd->ecd", act,
                                wd_.astype(x_blk.dtype))

        # -- return to senders + local combine ------------------------------
        back = jnp.transpose(
            expert_out.reshape(e_loc, M, cap, d), (1, 0, 2, 3)
        ).reshape(M, e_loc * cap, d)
        got = jax.lax.all_to_all(
            back, model_ax, split_axis=0, concat_axis=0, tiled=False)
        out_flat = got.reshape(m.n_experts * cap, d)
        out_flat = jnp.concatenate(
            [out_flat, jnp.zeros((1, d), x_blk.dtype)], axis=0)
        gathered = out_flat[slot]
        w = (weights.reshape(-1) * keep).astype(x_blk.dtype)
        combined = jax.ops.segment_sum(
            gathered * w[:, None], token_idx, num_segments=n_loc)
        return combined.reshape(nb, tb, d), aux.astype(jnp.float32)

    out, aux = jax.shard_map(
        body, mesh=mesh,
        in_specs=(x_spec, P(None, None), w_spec, w_spec, w_spec_t),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, p["router"], p["w_gate"], p["w_up"], p["w_down"])

    if m.n_shared > 0:
        out = out + L.mlp(p["shared"], x, "swiglu")
    return out, aux


# ---------------------------------------------------------------------------
# dense-fallback oracle (tests): run every expert on every token
# ---------------------------------------------------------------------------

def moe_dense_oracle(p: Params, x: Array, cfg: ModelConfig) -> Array:
    """O(N·E) reference without dispatch/capacity — equals moe_apply when
    nothing is dropped (capacity ≥ max expert load)."""
    m = cfg.moe
    b, t, d = x.shape
    x_flat = x.reshape(-1, d)
    weights, experts, _ = route(p["router"], x_flat, m.top_k)

    gate = jnp.einsum("nd,edf->enf", x_flat, p["w_gate"].astype(x.dtype))
    up = jnp.einsum("nd,edf->enf", x_flat, p["w_up"].astype(x.dtype))
    act = jax.nn.silu(gate) * up
    all_out = jnp.einsum("enf,efd->end", act, p["w_down"].astype(x.dtype))

    onehot = jax.nn.one_hot(experts, m.n_experts, dtype=x.dtype)  # (N,K,E)
    w = jnp.einsum("nk,nke->ne", weights.astype(x.dtype), onehot)
    out = jnp.einsum("ne,end->nd", w, all_out)
    if m.n_shared > 0:
        out = out + L.mlp(p["shared"], x_flat, "swiglu")
    return out.reshape(b, t, d)
