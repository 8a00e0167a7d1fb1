"""Unified language model over heterogeneous block stacks.

One ``TransformerLM`` definition serves all 10 assigned architectures:
the layer stack is a repeating ``layer_pattern`` unit (e.g. ``("attn",)``
for dense transformers, ``("mamba",)*5 + ("shared_attn",)`` for Zamba-2,
``("attn",)*4 + ("cross",)`` for the vision model) scanned with stacked
parameters — HLO size stays O(pattern), which is what lets 100-layer
models lower in seconds during the 40-cell dry-run.

The paper's technique enters through ``cfg.attention_backend`` on every
attention block (softmax | linear | gated_linear); for the linear family
the decode state of the whole model is a stack of fixed-size k×k matrices
— O(1) in context length — which is what makes the 500k-token decode
shape lowerable.

Cross-entropy is computed against vocab-sharded logits without ever
gathering them (per-shard max/sum + psum via GSPMD), the standard
large-vocab trick.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import blocks as B
from repro.models import layers as L
from repro.sharding import Rules, constrain

Array = jax.Array
Params = Dict[str, Any]


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# parameter construction
# ---------------------------------------------------------------------------

def init_params(key, cfg: ModelConfig) -> Params:
    """Build the full parameter tree.

    Structure:
      embed:      (V, D) token embedding
      stack:      tuple (one per pattern position) of block param trees
                  stacked over the repeat dim R (leading axis)
      tail:       tuple of unstacked block param trees
      shared:     one "shared_attn" block param set (Zamba) or None
      final_norm: norm params
      lm_head:    (D, V) unless cfg.tie_embeddings
    """
    pdt = _dtype(cfg.param_dtype)
    pattern, reps, tail = cfg.pattern_and_repeats
    k_embed, k_stack, k_tail, k_shared, k_head = jax.random.split(key, 5)

    params: Params = {
        "embed": L.embed_init(k_embed, cfg.vocab_size, cfg.d_model, pdt),
        "final_norm": L.norm_params(cfg.norm, cfg.d_model, pdt),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.dense_init(
            k_head, cfg.d_model, cfg.vocab_size, pdt)

    stack = []
    pos_keys = jax.random.split(k_stack, len(pattern))
    for pos, kind in enumerate(pattern):
        if kind == "shared_attn":
            stack.append({})  # parameters live in params["shared"]
            continue
        rep_keys = jax.random.split(pos_keys[pos], reps)
        stack.append(jax.vmap(
            lambda kk: B.block_params(kind, kk, cfg, pdt))(rep_keys))
    params["stack"] = tuple(stack)

    tail_params = []
    tail_keys = jax.random.split(k_tail, max(len(tail), 1))
    for i, kind in enumerate(tail):
        tail_params.append(
            {} if kind == "shared_attn"
            else B.block_params(kind, tail_keys[i], cfg, pdt))
    params["tail"] = tuple(tail_params)

    needs_shared = "shared_attn" in pattern or "shared_attn" in tail
    params["shared"] = (B.block_params("attn", k_shared, cfg, pdt)
                        if needs_shared else {})
    return params


def param_specs(cfg: ModelConfig) -> Params:
    """Logical sharding names, same tree structure as init_params."""
    pattern, _, tail = cfg.pattern_and_repeats

    from repro.sharding import is_logical_spec

    def stacked(tree):
        # prepend the scan ("layers") axis to every leaf spec
        return jax.tree.map(
            lambda names: ("layers",) + tuple(names),
            tree, is_leaf=is_logical_spec)

    specs: Params = {
        "embed": ("vocab", "embed"),
        "final_norm": ({"scale": (None,)} if cfg.norm == "rmsnorm"
                       else {"scale": (None,), "bias": (None,)}),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = ("embed", "vocab")
    specs["stack"] = tuple(
        {} if kind == "shared_attn"
        else stacked(B.block_param_specs(kind, cfg))
        for kind in pattern)
    specs["tail"] = tuple(
        {} if kind == "shared_attn" else B.block_param_specs(kind, cfg)
        for kind in tail)
    needs_shared = "shared_attn" in pattern or "shared_attn" in tail
    specs["shared"] = (B.block_param_specs("attn", cfg)
                       if needs_shared else {})
    return specs


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))


def cast_params(params: Params, dtype) -> Params:
    """Cast float matrices to the compute dtype.

    Only ndim ≥ 2 leaves are cast — those carry ~all FSDP all-gather
    bytes; small vectors (norm scales, decay logits ``a_log``, biases)
    stay fp32 for numerical headroom.
    """
    def cast(x):
        if x.ndim >= 2 and jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(dtype)
        return x
    return jax.tree.map(cast, params)


# ---------------------------------------------------------------------------
# forward (train / prefill)
# ---------------------------------------------------------------------------

def forward(
    params: Params,
    tokens: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    memory: Optional[Array] = None,
    want_state: bool = False,
    varlen: Optional[Array] = None,
) -> Tuple[Array, Array, Any]:
    """tokens: (B, T) int32 → (logits (B, T, V), aux_loss, states|None).

    ``memory``: (B, N_img, D) precomputed modality embeddings for "cross"
    blocks (frontend stub per the assignment).

    ``varlen``: (B,) int32 per-row valid lengths for bucket-padded
    batched prefill (rows END-padded to T). Pad positions are inert in
    every attention state accumulation, so row b's states and its logits
    at positions < varlen[b] are bit-identical to an unpadded forward of
    that row alone; logits at pad positions are garbage. Attention-only
    layer patterns (see :func:`prefill_varlen`).
    """
    adt = _dtype(cfg.dtype)
    pattern, reps, tail = cfg.pattern_and_repeats

    # Cast float params to the compute dtype ONCE, outside the layer scan:
    # the per-layer FSDP all-gathers then move bf16, not fp32 — half the
    # wire bytes (§Perf iteration 5). Gradients flow through the cast, so
    # the data-parallel gradient reduction is bf16 too (the documented
    # compression lever); the fp32 master copy only meets Adam.
    params = cast_params(params, adt)

    x = jnp.take(params["embed"], tokens, axis=0)
    x = constrain(x, rules, "batch", "seq_sp", "embed")
    mem = None if memory is None else memory.astype(adt)
    shared = params["shared"]

    # Sequence parallelism (§Perf iteration 4): the residual stream is
    # sharded over (batch, seq); remat then saves T/model_size of each
    # unit input per device instead of a model-axis-replicated copy.
    # GSPMD turns the TP all-reduces at block outputs into
    # reduce-scatter(seq) + all-gather(seq) around the block — Megatron-SP
    # derived from sharding constraints alone.
    def unit(carry, unit_params):
        x, aux = carry
        states = []
        for pos, kind in enumerate(pattern):
            x, st, a = B.block_apply(
                kind, unit_params[pos] if kind != "shared_attn" else None,
                x, cfg, rules, shared=shared, memory=mem,
                want_state=want_state, varlen=varlen)
            x = constrain(x, rules, "batch", "seq_sp", "embed")
            aux = aux + a
            states.append(st)
        return (x, aux), tuple(states) if want_state else None

    body = unit
    if cfg.remat == "unit":
        body = jax.checkpoint(
            unit, policy=jax.checkpoint_policies.nothing_saveable)

    (x, aux), stack_states = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), params["stack"],
        length=reps)

    tail_states = []
    for i, kind in enumerate(tail):
        x, st, a = B.block_apply(
            kind, params["tail"][i] if kind != "shared_attn" else None,
            x, cfg, rules, shared=shared, memory=mem,
            want_state=want_state, varlen=varlen)
        x = constrain(x, rules, "batch", "seq_sp", "embed")
        aux = aux + a
        tail_states.append(st)

    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    # Logits stay sequence-sharded: the (small) head matrix is gathered
    # instead of the (huge) logits, and the cross-entropy reductions are
    # then fully local — no (B, T, V)-sized collective anywhere.
    logits = x.astype(adt) @ head.astype(adt)
    logits = constrain(logits, rules, "batch", "seq_sp", None)

    states = None
    if want_state:
        states = {"stack": stack_states, "tail": tuple(tail_states)}
    return logits, aux, states


# ---------------------------------------------------------------------------
# loss (vocab-sharded cross entropy — logits never gathered)
# ---------------------------------------------------------------------------

def cross_entropy(logits: Array, labels: Array, rules: Rules,
                  z_loss: float = 0.0) -> Array:
    """Mean token cross-entropy over vocab-sharded logits.

    max / sum-exp / label-select all reduce over the sharded vocab axis,
    so GSPMD lowers them to (B, T)-sized all-reduces instead of gathering
    the (B, T, V) logits — the large-vocab TP trick.
    """
    lf = logits.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(lf, axis=-1, keepdims=True))
    shifted = lf - m
    sum_exp = jnp.sum(jnp.exp(shifted), axis=-1)
    lse = jnp.log(sum_exp) + m[..., 0]
    col = jax.lax.broadcasted_iota(jnp.int32, lf.shape, lf.ndim - 1)
    label_logit = jnp.sum(
        jnp.where(col == labels[..., None], lf, 0.0), axis=-1)
    nll = lse - label_logit
    if z_loss:
        nll = nll + z_loss * jnp.square(jnp.log(sum_exp) + m[..., 0])
    return jnp.mean(nll)


def lm_loss(params: Params, batch: Dict[str, Array], cfg: ModelConfig,
            rules: Rules) -> Tuple[Array, Dict[str, Array]]:
    """batch: {"tokens": (B,T), "labels": (B,T) [, "memory": (B,N,D)]}."""
    logits, aux, _ = forward(
        params, batch["tokens"], cfg, rules, memory=batch.get("memory"))
    xent = cross_entropy(logits, batch["labels"], rules)
    aux_w = cfg.moe.router_aux_weight if cfg.moe is not None else 0.0
    loss = xent + aux_w * aux
    return loss, {"xent": xent, "aux": aux}


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_decode_state(cfg: ModelConfig, batch: int, max_len: int,
                      rules: Optional[Rules] = None) -> Any:
    """Zero decode state for the whole stack.

    softmax backend: per-layer KV caches, O(max_len) memory.
    linear family / SSM / RWKV: fixed-size matrix states, O(1) in
    max_len — the paper's property, and why long_500k decode states fit.
    """
    adt = _dtype(cfg.dtype)
    pattern, reps, tail = cfg.pattern_and_repeats

    def stacked_state(kind):
        st = B.block_state_init(kind, cfg, batch, max_len, adt, rules)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (reps,) + x.shape), st)

    return {
        "stack": tuple(stacked_state(k) for k in pattern),
        "tail": tuple(B.block_state_init(k, cfg, batch, max_len, adt,
                                         rules)
                      for k in tail),
    }


def decode_state_specs(cfg: ModelConfig) -> Any:
    pattern, _, tail = cfg.pattern_and_repeats

    from repro.sharding import is_logical_spec

    def stacked(tree):
        return jax.tree.map(
            lambda names: ("layers",) + tuple(names),
            tree, is_leaf=is_logical_spec)

    return {
        "stack": tuple(stacked(B.block_state_specs(k, cfg))
                       for k in pattern),
        "tail": tuple(B.block_state_specs(k, cfg) for k in tail),
    }


def decode_step(
    params: Params,
    state: Any,
    token: Array,
    pos: Array,
    cfg: ModelConfig,
    rules: Rules,
    active: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """One autoregressive step. token: (B,) int32; pos: () int32 shared
    position, or (B,) int32 per-sequence positions (continuous batching:
    every slot decodes at its own depth in its own request).

    ``active``: (B,) bool slot mask — inactive rows keep their state
    bit-for-bit, masked at ROW granularity inside each block (the
    softmax backend gates the one written KV-cache row instead of
    selecting whole caches; see ``attention_decode``).

    Returns (logits (B, V), new_state). For the linear backends the cost
    is O(k²) per layer — independent of pos (paper's fast lookup).

    A stacked block state that the fused kernel advances in place
    (``A.decodes_in_place``: the linear family's matrix state) rides
    through the layer scan as carry, whole, and each layer's kernel
    reads and writes its own layer of it: no per-layer slice, write-back
    or stack copy. Every other stacked state is scanned over as before.
    """
    from repro.models.attention import decodes_in_place

    adt = _dtype(cfg.dtype)
    pattern, reps, tail = cfg.pattern_and_repeats

    params = cast_params(params, adt)
    if rules.model_size > 1:
        # one-hot contraction against the vocab-sharded table: a (B, V/16)
        # local matmul + tiny psum instead of all-gathering the whole
        # embedding every generated token (§Perf cell C iteration 2).
        onehot = jax.nn.one_hot(token, cfg.vocab_size, dtype=adt)
        onehot = constrain(onehot, rules, "batch", "vocab")
        x = onehot @ params["embed"].astype(adt)
    else:
        x = jnp.take(params["embed"], token, axis=0).astype(adt)
    x = constrain(x, rules, "batch", "embed")
    shared = params["shared"]

    in_place = tuple(decodes_in_place(st, cfg) for st in state["stack"])
    carried = tuple(st if ip else None
                    for st, ip in zip(state["stack"], in_place))
    scanned = tuple(None if ip else st
                    for st, ip in zip(state["stack"], in_place))

    def unit(carry, xs):
        x, carried = carry
        unit_params, unit_state, layer = xs
        new_carried, new_states = [], []
        for p_i, kind in enumerate(pattern):
            ip = in_place[p_i]
            x, st = B.block_decode(
                kind, unit_params[p_i] if kind != "shared_attn" else None,
                x, carried[p_i] if ip else unit_state[p_i], pos, cfg,
                rules, shared=shared, active=active,
                layer=layer if ip else None)
            new_carried.append(st if ip else None)
            new_states.append(None if ip else st)
        return (x, tuple(new_carried)), tuple(new_states)

    # named scopes (decode.*) add op metadata only: a profiler trace
    # attributes each device op to the layer scan, attention, MLP or head
    with jax.named_scope("decode.layers"):
        (x, carried), new_scanned = jax.lax.scan(
            unit, (x, carried),
            (params["stack"], scanned, jnp.arange(reps, dtype=jnp.int32)),
            length=reps)
        new_stack = tuple(c if ip else s for c, s, ip
                          in zip(carried, new_scanned, in_place))

        new_tail = []
        for i, kind in enumerate(tail):
            x, st = B.block_decode(
                kind, params["tail"][i] if kind != "shared_attn" else None,
                x, state["tail"][i], pos, cfg, rules, shared=shared,
                active=active)
            new_tail.append(st)

    with jax.named_scope("decode.head"):
        x = L.apply_norm(cfg.norm, params["final_norm"], x)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        logits = x @ head.astype(adt)
        logits = constrain(logits, rules, "batch", "vocab")
    return logits, {"stack": new_stack, "tail": tuple(new_tail)}


def sample_token(logits: Array, temperature: float,
                 key: Optional[Array] = None) -> Array:
    """logits: (B, V) → (B,) int32. temperature is a PYTHON float decided
    at trace time: 0.0 = greedy (no PRNG consumed), > 0 = categorical."""
    if temperature and temperature > 0.0:
        assert key is not None, "temperature sampling needs a PRNG key"
        return jax.random.categorical(
            key, logits.astype(jnp.float32) / temperature, axis=-1
        ).astype(jnp.int32)
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def generate(
    params: Params,
    state: Any,
    tok0: Array,
    pos0: Array,
    n_steps: int,
    cfg: ModelConfig,
    rules: Rules,
    *,
    temperature: float = 0.0,
    key: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """Fused generation loop: ``n_steps`` autoregressive decode steps as
    ONE ``lax.scan`` — the whole generation is a single device dispatch,
    with greedy/temperature sampling folded into the scan body.

    tok0: (B,) first input token (e.g. sampled from prefill logits);
    pos0: () its position. Returns (tokens (B, n_steps), final_state)
    where tokens[:, i] is the token sampled after consuming the i-th
    input. For the linear backends every step is O(k²) against the
    fixed-size state, so per-token cost is flat in context length AND
    free of per-token dispatch/HBM-round-trip overhead — the serving
    half of the paper's fast-lookup claim.
    """
    greedy = not (temperature and temperature > 0.0)
    if key is None:
        if not greedy:
            raise ValueError("temperature sampling needs a PRNG key")
        key = jax.random.PRNGKey(0)  # carried but never consumed
    pos0 = jnp.asarray(pos0, jnp.int32)
    tok0 = tok0.astype(jnp.int32)
    # pre-cast once: the per-step cast inside decode_step becomes a
    # no-op, so the scan body carries no loop-invariant cast work
    params = cast_params(params, _dtype(cfg.dtype))

    def step(carry, _):
        tok, st, pos, k = carry
        logits, st = decode_step(params, st, tok, pos, cfg, rules)
        if greedy:
            sub = None          # no PRNG consumed in the hot loop
        else:
            k, sub = jax.random.split(k)
        nxt = sample_token(logits, temperature, sub)
        return (nxt, st, pos + 1, k), nxt

    (_, state_f, _, _), toks = jax.lax.scan(
        step, (tok0, state, pos0, key), None, length=n_steps)
    return jnp.moveaxis(toks, 0, 1), state_f


# ---------------------------------------------------------------------------
# continuous batching: slot-masked segments + slot state swaps
# ---------------------------------------------------------------------------
#
# The whole-stack decode state is {"stack": …, "tail": …} where "stack"
# leaves carry (reps, S, …) and "tail" leaves (S, …) — the slot (batch)
# axis is 1 and 0 respectively. The two helpers below are the only places
# that encode this axis arithmetic.

def _over_slots(fn, a: Any, b: Any) -> Any:
    """Map ``fn(leaf_a, leaf_b, slot_axis)`` over two whole-stack states."""
    stack = tuple(
        jax.tree.map(lambda x, y: fn(x, y, 1), sa, sb)
        for sa, sb in zip(a["stack"], b["stack"]))
    tail = tuple(
        jax.tree.map(lambda x, y: fn(x, y, 0), ta, tb)
        for ta, tb in zip(a["tail"], b["tail"]))
    return {"stack": stack, "tail": tail}


def _map_slots(fn, a: Any) -> Any:
    """Map ``fn(leaf, slot_axis)`` over one whole-stack state."""
    stack = tuple(jax.tree.map(lambda x: fn(x, 1), sa)
                  for sa in a["stack"])
    tail = tuple(jax.tree.map(lambda x: fn(x, 0), ta)
                 for ta in a["tail"])
    return {"stack": stack, "tail": tail}


def snapshot_state(state: Any, slot: Array) -> Any:
    """Extract slot ``slot`` of a stacked engine state as a batch-1
    whole-stack state: one ``dynamic_slice`` per leaf, the inverse of
    :func:`restore_state`.

    This is the speculative-decoding rewind primitive: a slot's state is
    snapshotted before a verify window, and on draft rejection the
    accepted prefix is re-advanced from the snapshot. For the linear
    family a snapshot is the paper's fixed-size representation —
    O(k²) per layer regardless of how much context the slot has
    consumed — which is what makes rewind cheap (a KV-cache backend
    copies O(max_len·k) bytes instead).
    """
    slot = jnp.asarray(slot, jnp.int32)

    def read(x, axis):
        start = [jnp.int32(0)] * x.ndim
        start[axis] = slot
        size = list(x.shape)
        size[axis] = 1
        return jax.lax.dynamic_slice(x, start, size)

    return _map_slots(read, state)


def restore_state(engine_state: Any, snapshot: Any, slot: Array) -> Any:
    """Write a batch-1 whole-stack state into slot ``slot`` of the
    stacked engine state: one ``dynamic_update_slice`` per leaf.

    Shared by engine admission (swap in a freshly prefilled request) and
    speculative rewind (put a re-advanced snapshot back); the two are the
    same O(k²)-per-layer copy for the linear family.
    """
    slot = jnp.asarray(slot, jnp.int32)

    def write(e, r, axis):
        start = [jnp.int32(0)] * e.ndim
        start[axis] = slot
        return jax.lax.dynamic_update_slice(e, r.astype(e.dtype), start)

    return _over_slots(write, engine_state, snapshot)


def where_state(active: Array, new: Any, old: Any) -> Any:
    """Per-slot select over a whole-stack decode state: slots where
    ``active`` is False keep their old state bit-for-bit (a parked or
    finished request must not advance while its neighbours decode).

    Cost: one select per state leaf. O(k²) per layer for the linear
    family (why slot masking is cheap for this paper's states); for the
    softmax baseline the select spans the full (S, max_len, Hkv, Dh)
    caches. The decode hot loop therefore does NOT use this anymore —
    ``decode_step(active=...)`` masks at row granularity inside each
    block (softmax gates its one written cache row) — but it remains
    the right tool for whole-state merges outside the step, e.g.
    committing a speculative verify state into accepting slots."""
    def sel(n, o, axis):
        shape = [1] * n.ndim
        shape[axis] = active.shape[0]
        return jnp.where(active.reshape(shape), n, o)

    return _over_slots(sel, new, old)


def slot_state_finite(state: Any) -> Array:
    """Per-slot finiteness probe over a stacked engine decode state:
    returns (S,) bool, True where EVERY float leaf of that slot is
    finite.

    This is the serving engine's numeric-fault detector: a NaN/Inf that
    escapes the safe_denom clamps (or is injected by a fault harness)
    would otherwise sit in a slot's stacked state and silently poison
    every later tenant of the slot. One fused ``jnp.isfinite`` reduction
    over all leaves amortises the check to a single tiny device program
    per segment boundary; the (S,) result is resolved host-side by the
    scheduler (quarantine + snapshot-retry). Non-float leaves are
    trivially finite and skipped.
    """
    flags = []

    def probe(x, axis):
        if jnp.issubdtype(x.dtype, jnp.floating):
            m = jnp.moveaxis(x, axis, 0)
            flags.append(jnp.all(jnp.isfinite(m.reshape(m.shape[0], -1)),
                                 axis=-1))
        return x

    _map_slots(probe, state)
    out = flags[0]
    for f in flags[1:]:
        out = out & f
    return out


def write_slot_state(engine_state: Any, request_state: Any,
                     slot: Array) -> Any:
    """Swap a batch-1 request state into slot ``slot`` of the stacked
    engine state.

    This is the admission cost model of the serving engine: one
    ``dynamic_update_slice`` per state leaf. For the linear family every
    leaf is the paper's fixed-size representation, so admitting a request
    is an O(k²)-per-layer copy — independent of how much context the
    request has consumed — where a KV-cache backend moves O(T·k) bytes.

    (Alias of :func:`restore_state` — admission and speculative rewind
    share one slot-write primitive.)
    """
    return restore_state(engine_state, request_state, slot)


# ---------------------------------------------------------------------------
# row-ranged KV snapshots: O(W·k) copies for the softmax baseline
# ---------------------------------------------------------------------------
#
# The linear-family states are already fixed-size, so snapshot/restore
# cost O(k²) regardless of context. The softmax baseline's AttnState KV
# caches are (…, max_len, Hkv, Dh): a whole-cache snapshot moves
# O(max_len·k) bytes however few rows were ever written. The three
# helpers below cut every KV copy to the W written rows — the primitive
# both speculative rewind and paged prefix caching need. They rely on a
# read-masking invariant of ``attention_decode``: cache reads are masked
# to pos+1 and the row at pos is rewritten before pos advances, so rows
# at index ≥ pos are never read — a restore that leaves them stale is
# bit-identical (greedy) to one that overwrites them.

def snapshot_state_rows(state: Any, slot: Array, n_rows: int) -> Any:
    """:func:`snapshot_state`, but each softmax KV cache keeps only its
    first ``n_rows`` rows (static, so jit specializes per width bucket).
    The slice fuses with the slot ``dynamic_slice``, so the copy is
    O(n_rows·k) per layer. ``n_rows`` must be ≥ the slot's written row
    count (its position). Linear/recurrent leaves are untouched — for
    them this IS :func:`snapshot_state`, the paper's fixed-size
    representation."""
    from repro.models.attention import AttnState

    snap = snapshot_state(state, slot)

    def shrink(st):
        if not isinstance(st, AttnState) or st.k_cache is None:
            return st
        t = st.k_cache.ndim - 3     # the S dim of (..., S, Hkv, Dh)
        if n_rows >= st.k_cache.shape[t]:
            return st
        cut = lambda x: jax.lax.slice_in_dim(x, 0, n_rows, axis=t)
        return AttnState(k_cache=cut(st.k_cache),
                         v_cache=cut(st.v_cache), s=st.s, z=st.z)

    return jax.tree.map(shrink, snap,
                        is_leaf=lambda x: isinstance(x, AttnState))


def restore_state_rows(engine_state: Any, snapshot: Any,
                       slot: Array) -> Any:
    """Write a possibly row-ranged batch-1 snapshot into slot ``slot``.

    ``dynamic_update_slice`` writes only the extent of its update
    operand, so a snapshot whose KV time axis was cut to W rows by
    :func:`snapshot_state_rows` costs O(W·k) per layer to restore; KV
    rows ≥ W keep the slot's previous contents (never read — see the
    read-masking invariant above). A full-width snapshot makes this
    exactly :func:`restore_state`, which is why the two share one
    implementation and the engine's admission program serves both."""
    return restore_state(engine_state, snapshot, slot)


def where_state_rows(active: Array, new: Any, old: Any,
                     start: Array, width: int) -> Any:
    """Row-ranged per-slot select: like :func:`where_state`, but each
    softmax KV cache is merged only over rows [start_s, start_s+width)
    per slot — one ``dynamic_slice`` + select + ``dynamic_update_slice``
    of W rows instead of a select spanning the whole (S, max_len, Hkv,
    Dh) cache. This is the speculative-rewind cost fix: a rewind
    touches exactly the rows the round wrote, O(W·k), while rows
    outside the range are either bitwise-equal in ``new`` and ``old``
    (below the round's start) or stale-but-unreadable (above it).

    ``start`` is a per-slot (S,) row offset (dynamic); ``width`` is
    static. Starts are clamped to ``max_len - width`` — value-safe,
    because rows below a slot's true start are bitwise-equal in both
    states. Non-KV leaves (the fixed-size linear/recurrent states) take
    the plain full select, same as :func:`where_state`."""
    from repro.models.attention import AttnState

    start = jnp.asarray(start, jnp.int32)

    def sel(n, o, axis):
        shape = [1] * n.ndim
        shape[axis] = active.shape[0]
        return jnp.where(active.reshape(shape), n, o)

    def rows(n, o, axis):
        # slot axis → 0; the time axis is then ndim-3 for both layouts
        nm = jnp.moveaxis(n, axis, 0)
        om = jnp.moveaxis(o, axis, 0)

        def one(nx, ox, st, act):
            t = nx.ndim - 3
            lo = jnp.clip(st, 0, nx.shape[t] - width)
            sl_n = jax.lax.dynamic_slice_in_dim(nx, lo, width, axis=t)
            sl_o = jax.lax.dynamic_slice_in_dim(ox, lo, width, axis=t)
            merged = jnp.where(act, sl_n, sl_o)
            return jax.lax.dynamic_update_slice_in_dim(
                ox, merged, lo, axis=t)

        return jnp.moveaxis(jax.vmap(one)(nm, om, start, active), 0, axis)

    def merge(n, o, axis):
        if isinstance(n, AttnState):
            f = (lambda a, b: None if a is None else sel(a, b, axis))
            if n.k_cache is None:
                return AttnState(k_cache=None, v_cache=None,
                                 s=f(n.s, o.s), z=f(n.z, o.z))
            return AttnState(k_cache=rows(n.k_cache, o.k_cache, axis),
                             v_cache=rows(n.v_cache, o.v_cache, axis),
                             s=f(n.s, o.s), z=f(n.z, o.z))
        return sel(n, o, axis)

    leaf = lambda x: isinstance(x, AttnState)
    stack = tuple(
        jax.tree.map(lambda x, y: merge(x, y, 1), sa, sb, is_leaf=leaf)
        for sa, sb in zip(new["stack"], old["stack"]))
    tail = tuple(
        jax.tree.map(lambda x, y: merge(x, y, 0), ta, tb, is_leaf=leaf)
        for ta, tb in zip(new["tail"], old["tail"]))
    return {"stack": stack, "tail": tail}


def generate_segment(
    params: Params,
    state: Any,
    tok: Array,
    pos: Array,
    active: Array,
    remaining: Array,
    n_steps: int,
    cfg: ModelConfig,
    rules: Rules,
    *,
    eos_id: Optional[int] = None,
    temperature: float = 0.0,
    key: Optional[Array] = None,
    pad_id: int = -1,
) -> Tuple[Array, Dict[str, Any]]:
    """One continuous-batching segment: ``n_steps`` slot-masked decode
    steps as a single ``lax.scan`` dispatch.

    Unlike :func:`generate` (one-shot batch semantics: every row starts
    and stops together), each slot here carries its own lifecycle:
    tok (S,) is the next input token per slot, pos (S,) its per-slot
    position, active (S,) bool whether the slot holds a live request, and
    remaining (S,) int32 how many tokens the slot may still emit
    (including this step's). A slot stops *inside* the scan when its
    budget hits zero or it emits ``eos_id``; stopped/empty slots emit
    ``pad_id`` and their state is frozen bit-for-bit, so per-slot outputs
    are exactly what the request would produce running alone (greedy).

    Returns (tokens (S, n_steps), carry) where carry = {"tok", "pos",
    "active", "remaining", "state", "key"} feeds the next segment after
    the host scheduler drains finished slots and admits new requests.
    """
    greedy = not (temperature and temperature > 0.0)
    if key is None:
        if not greedy:
            raise ValueError("temperature sampling needs a PRNG key")
        key = jax.random.PRNGKey(0)  # carried but never consumed
    tok = tok.astype(jnp.int32)
    pos = jnp.asarray(pos, jnp.int32)
    active = jnp.asarray(active, jnp.bool_)
    remaining = jnp.asarray(remaining, jnp.int32)
    params = cast_params(params, _dtype(cfg.dtype))

    def step(carry, _):
        tok, st, pos, act, rem, k = carry
        # inactive-slot freezing happens at ROW granularity inside the
        # step (softmax: the one written KV-cache row is gated on act,
        # not the whole cache — the row-level slot-masking optimisation)
        logits, st = decode_step(params, st, tok, pos, cfg, rules,
                                 active=act)
        with jax.named_scope("decode.head"):
            if greedy:
                sub = None          # no PRNG consumed in the hot loop
            else:
                k, sub = jax.random.split(k)
            nxt = sample_token(logits, temperature, sub)
        emitted = jnp.where(act, nxt, pad_id)
        rem = jnp.where(act, rem - 1, rem)
        done = rem <= 0
        if eos_id is not None:
            done = done | (nxt == eos_id)
        pos = jnp.where(act, pos + 1, pos)
        tok = jnp.where(act, nxt, tok)
        return (tok, st, pos, act & ~done, rem, k), emitted

    carry0 = (tok, state, pos, active, remaining, key)
    (tok_f, st_f, pos_f, act_f, rem_f, key_f), toks = jax.lax.scan(
        step, carry0, None, length=n_steps)
    return jnp.moveaxis(toks, 0, 1), {
        "tok": tok_f, "pos": pos_f, "active": act_f,
        "remaining": rem_f, "state": st_f, "key": key_f}


def _window_forward(
    params: Params,
    state: Any,
    tokens: Array,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
    block_fn,
    **block_kw,
) -> Tuple[Array, Any]:
    """Shared driver for every W-token window pass (embed → stacked-unit
    scan → tail → final norm → lm head); ``block_fn`` is the per-block
    window primitive (``B.block_decode_window`` /
    ``B.block_ingest_window``) and ``block_kw`` its extra row-masking
    arguments. The three public windows below differ ONLY here."""
    adt = _dtype(cfg.dtype)
    pattern, reps, tail = cfg.pattern_and_repeats

    params = cast_params(params, adt)
    if rules.model_size > 1:
        # same vocab-sharded one-hot contraction as decode_step: a local
        # matmul + tiny psum instead of all-gathering the embedding
        # table every window.
        onehot = jax.nn.one_hot(tokens, cfg.vocab_size, dtype=adt)
        onehot = constrain(onehot, rules, "batch", "seq", "vocab")
        x = onehot @ params["embed"].astype(adt)            # (B, W, D)
    else:
        x = jnp.take(params["embed"], tokens, axis=0).astype(adt)
    x = constrain(x, rules, "batch", "seq", "embed")
    shared = params["shared"]

    def unit(x, scanned):
        unit_params, unit_state = scanned
        new_states = []
        for p_i, kind in enumerate(pattern):
            x, st = block_fn(
                kind, unit_params[p_i] if kind != "shared_attn" else None,
                x, unit_state[p_i], pos0, cfg, rules, shared=shared,
                **block_kw)
            new_states.append(st)
        return x, tuple(new_states)

    x, new_stack = jax.lax.scan(
        unit, x, (params["stack"], state["stack"]), length=reps)

    new_tail = []
    for i, kind in enumerate(tail):
        x, st = block_fn(
            kind, params["tail"][i] if kind != "shared_attn" else None,
            x, state["tail"][i], pos0, cfg, rules, shared=shared,
            **block_kw)
        new_tail.append(st)

    x = L.apply_norm(cfg.norm, params["final_norm"], x)
    head = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = x @ head.astype(adt)
    logits = constrain(logits, rules, "batch", "seq", "vocab")
    return logits, {"stack": new_stack, "tail": tuple(new_tail)}


def decode_window(
    params: Params,
    state: Any,
    tokens: Array,
    pos0: Array,
    cfg: ModelConfig,
    rules: Rules,
) -> Tuple[Array, Any]:
    """Advance the decode state over W KNOWN tokens in one dispatch.

    tokens: (B, W) int32; pos0: () shared position of tokens[:, 0], or
    (B,) per-sequence start positions (speculative verification in the
    slot engine: every slot verifies a draft window at its own depth).
    Returns (logits (B, W, V), new_state), where logits[:, i] is the
    model's next-token distribution after consuming tokens[:, i]. Under
    the linear backends each attention layer runs its whole window
    inside one fused recurrent kernel launch (state VMEM-resident across
    the W steps) — the building block for forced/teacher decoding,
    scoring, and speculative lookahead verification, where the tokens
    are available up front. The softmax baseline scans single-token
    decode over the window (see blocks.block_decode_window), writing its
    KV cache rows per slot position.
    """
    pos0 = jnp.asarray(pos0, jnp.int32)
    return _window_forward(params, state, tokens, pos0, cfg, rules,
                           B.block_decode_window)


def decode_window_varlen(
    params: Params,
    state: Any,
    tokens: Array,
    pos0: Array,
    lens: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    active: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """Variable-length masked window: advance each row of the decode
    state over ITS OWN number of known tokens in one dispatch.

    tokens: (B, W) int32, row b's valid tokens END-padded to W;
    pos0: (B,) per-row start positions; lens: (B,) int32 valid counts
    (0 ≤ lens ≤ W); active: optional (B,) bool (False rows behave as
    lens = 0). Row b consumes tokens[b, :lens[b]] starting at position
    pos0[b]; masked rows/steps are inert — state untouched bit-for-bit,
    zero/garbage logits the caller must ignore. Returns
    (logits (B, W, V), new_state) with logits[b, i] the next-token
    distribution after tokens[b, i] (valid for i < lens[b]).

    This is the serving engine's workhorse for everything that advances
    *different slots by different amounts* in one launch: bucket-padded
    chunked prompt ingestion interleaved with decode, and batched
    speculative rewind (re-advancing accepted prefixes of differing
    lengths). Linear backends run the masked fused recurrent kernels
    (per-row valid-length masking inside the VMEM-resident W-step scan);
    the softmax baseline scans single-token decode with a per-step
    ``w < lens`` row mask gating its one written KV-cache row.
    """
    w = tokens.shape[1]
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32),
                            (tokens.shape[0],))
    lens = jnp.clip(jnp.asarray(lens, jnp.int32), 0, w)
    if active is not None:
        lens = jnp.where(jnp.asarray(active, jnp.bool_), lens, 0)
    return _window_forward(params, state, tokens, pos0, cfg, rules,
                           B.block_decode_window, lens=lens)


def ingest_window_varlen(
    params: Params,
    state: Any,
    tokens: Array,
    pos0: Array,
    lens: Array,
    cfg: ModelConfig,
    rules: Rules,
) -> Tuple[Array, Any]:
    """Chunk-parallel sibling of :func:`decode_window_varlen` for prompt
    INGESTION: same signature and row-masking semantics, but attention
    blocks under the linear backends continue their fixed-size state
    through the chunk-parallel prefill kernels (with carried
    state/normaliser) instead of the sequential recurrence — ingesting a
    W-token chunk costs prefill FLOPs, not W decode steps. The softmax
    baseline (and any non-attention kind) keeps the masked per-step
    path, which is what its KV cache needs anyway. Used by the serving
    engine for prompts longer than ``prefill_chunk``; returns
    (logits (B, W, V), new_state) with valid logits at i < lens[b].
    """
    w = tokens.shape[1]
    pos0 = jnp.broadcast_to(jnp.asarray(pos0, jnp.int32),
                            (tokens.shape[0],))
    lens = jnp.clip(jnp.asarray(lens, jnp.int32), 0, w)
    return _window_forward(params, state, tokens, pos0, cfg, rules,
                           B.block_ingest_window, lens=lens)


def pad_decode_state(states: Any, cfg: ModelConfig, max_len: int) -> Any:
    """Grow prefill KV caches to ``max_len`` (softmax backend only — the
    linear-family states are already fixed-size, nothing to pad).

    Prefill returns caches of the prompt length; decode wants room for
    generated tokens. Cache layout (B, S, Hkv, Dh), stacked variants have
    a leading repeat dim.
    """
    from repro.models.attention import AttnState

    def fix(st):
        if not isinstance(st, AttnState) or st.k_cache is None:
            return st
        axis = st.k_cache.ndim - 3  # the S dim of (..., S, Hkv, Dh)
        pad = max_len - st.k_cache.shape[axis]
        if pad <= 0:
            return st
        widths = [(0, 0)] * st.k_cache.ndim
        widths[axis] = (0, pad)
        return AttnState(
            k_cache=jnp.pad(st.k_cache, widths),
            v_cache=jnp.pad(st.v_cache, widths),
            s=st.s, z=st.z)

    return jax.tree.map(fix, states,
                        is_leaf=lambda x: isinstance(x, AttnState))


def prefill(
    params: Params,
    tokens: Array,
    cfg: ModelConfig,
    rules: Rules,
    *,
    memory: Optional[Array] = None,
) -> Tuple[Array, Any]:
    """Encode a prompt, returning (last-position logits, decode states).

    This is the paper's encode-once phase: for the linear backends the
    whole prompt is compressed into fixed-size per-layer states.
    """
    logits, _, states = forward(
        params, tokens, cfg, rules, memory=memory, want_state=True)
    return logits[:, -1], states


def supports_varlen_prefill(cfg: ModelConfig) -> bool:
    """True when every block kind masks correctly under per-row varlen
    prefill (attention-family blocks only; the Mamba/RWKV recurrences
    and cross-memory encode have no varlen masking yet)."""
    pattern, _, tail = cfg.pattern_and_repeats
    return set(pattern) | set(tail) <= {"attn", "shared_attn"}


def prefill_varlen(
    params: Params,
    tokens: Array,
    lens: Array,
    cfg: ModelConfig,
    rules: Rules,
) -> Tuple[Array, Any]:
    """Batched bucket-padded prefill: encode B prompts of DIFFERENT
    lengths in one dispatch.

    tokens: (B, W) int32, row b's prompt END-padded to the bucket width
    W; lens: (B,) int32 true prompt lengths (lens = 0 rows are dummies —
    zero linear states, garbage caches). Returns (last-valid logits
    (B, V), decode states).

    Pad positions are inert in every state accumulation (zero key/value
    terms, exp(0) = 1 decay, causally-masked softmax), so each row's
    states and its lens-1 logits are BIT-IDENTICAL to prefilling that
    row alone unpadded — which is what lets a serving engine admit a
    whole admission batch with one program compiled per power-of-2
    bucket width instead of one ``lm.prefill`` compile per distinct
    prompt length. Requires an attention-only layer pattern
    (:func:`supports_varlen_prefill`).

    (Caveat, pinned by tests/test_decode_parity.py: the math is exact,
    but bitwise equality additionally needs the backend to lower the
    padded and unpadded projections to the same matmul kernel — true on
    CPU for every row length except 1, where XLA picks gemv for the
    unpadded call. Length-1 rows agree to ~1e-6 instead.)
    """
    assert supports_varlen_prefill(cfg), (
        "varlen prefill needs an attention-only layer pattern; "
        f"got {cfg.layer_pattern} + {cfg.tail}")
    lens = jnp.asarray(lens, jnp.int32)
    logits, _, states = forward(
        params, tokens, cfg, rules, want_state=True, varlen=lens)
    last = jnp.take_along_axis(
        logits, jnp.maximum(lens - 1, 0)[:, None, None], axis=1)[:, 0]
    return last, states
