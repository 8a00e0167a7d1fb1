"""GPipe pipeline parallelism as a partial-auto shard_map.

Mesh: (stage=S, data, model). The ``stage`` axis is MANUAL (this module
moves activations between stages with ``ppermute`` on the GPipe
schedule); ``data`` and ``model`` stay AUTO, so the existing
tensor/sequence/data-parallel layer code — sharding constraints, flash
attention, MoE dispatch — runs unchanged inside each stage. That
composition (PP outermost over TP/SP/DP) is exactly the production
layering of Megatron/MaxText-scale systems.

Schedule: M microbatches, S stages, M + S − 1 ticks. At tick t, stage s
processes microbatch (t − s) when 0 ≤ t − s < M; stage 0 injects
microbatch t; the last stage computes the (masked) loss; after every
tick activations ppermute one stage forward. Bubble fraction is the
usual (S − 1)/(M + S − 1). The tick body is rematerialised
(``jax.checkpoint``) so in-flight activation memory is one buffer per
stage, not one per tick.

No parameter restructuring: the layer-scan's stacked leaves (R, …)
simply get ``P('stage')`` on their leading dim — R/S layers land on each
stage, contiguous by construction.

Correctness: ``gpipe_loss_fn`` equals the plain ``lm_loss`` on the same
params/batch (tests/test_multidevice.py::test_gpipe_matches_plain).
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.launch.mesh import make_mesh
from repro.models import blocks as B
from repro.models import layers as L
from repro.models.lm import _dtype, cast_params, cross_entropy
from repro.sharding import Rules, constrain

Array = jax.Array


def make_pipeline_mesh(stages: int = 4, data: int = 4,
                       model: int = 16) -> Mesh:
    """(stage, data, model) — stages×data×model chips (4×4×16 = one pod)."""
    return make_mesh((stages, data, model), ("stage", "data", "model"))


def pipeline_compatible(cfg: ModelConfig, n_stages: int) -> bool:
    """PP needs a homogeneous repeating unit divisible across stages."""
    pattern, reps, tail = cfg.pattern_and_repeats
    return (not tail and "shared_attn" not in pattern
            and reps % n_stages == 0)


def _shard_map(f, mesh, in_specs, out_specs):
    # "stage" is the only MANUAL axis; data/model stay auto (GSPMD
    # keeps managing TP/SP/DP inside the stage body).
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         axis_names=frozenset({"stage"}), check_vma=False)


def gpipe_loss_fn(
    cfg: ModelConfig,
    rules: Rules,
    mesh: Mesh,
    *,
    n_micro: int = 8,
):
    """Build loss(params, batch) with a GPipe schedule over ``stage``.

    batch: {"tokens": (B, T), "labels": (B, T)}; B % n_micro == 0.
    Returns mean token cross-entropy (identical to ``lm.lm_loss`` up to
    microbatch-mean association).
    """
    n_stages = mesh.shape["stage"]
    pattern, reps, tail = cfg.pattern_and_repeats
    assert pipeline_compatible(cfg, n_stages), (
        f"{cfg.name}: pattern {pattern}×{reps}+{tail} not divisible "
        f"into {n_stages} pipeline stages")
    adt = _dtype(cfg.dtype)

    def stage_body(params_stack, shared, x):
        """Run this stage's layers on x (B_mb, T, D)."""
        def unit(carry, unit_params):
            h = carry
            for pos, kind in enumerate(pattern):
                h, _, _ = B.block_apply(
                    kind, unit_params[pos], h, cfg, rules, shared=shared)
                h = constrain(h, rules, "batch", "seq_sp", "embed")
            return h, None

        body = jax.checkpoint(
            unit, policy=jax.checkpoint_policies.nothing_saveable)
        x, _ = jax.lax.scan(body, x, params_stack)
        return x

    def pipelined(params, tokens_mb, labels_mb):
        """Per-stage-shard program. params stacked leaves: (R/S, …);
        tokens_mb/labels_mb: (M, B_mb, T) replicated over stage."""
        stage = jax.lax.axis_index("stage")
        m = tokens_mb.shape[0]
        b_mb, t = tokens_mb.shape[1:]
        params_c = cast_params(params, adt)
        # drop the stage-sharded leading dim shard_map leaves as size-R/S
        stack = params_c["stack"]
        shared = params_c["shared"]
        embed = params_c["embed"]
        head = (embed.T if cfg.tie_embeddings else params_c["lm_head"])

        def tick(buf, tick_idx):
            mb_in = jnp.clip(tick_idx, 0, m - 1)
            mb_here = tick_idx - stage
            active = (mb_here >= 0) & (mb_here < m)
            mb_safe = jnp.clip(mb_here, 0, m - 1)

            # stage 0 injects the embedded microbatch tick_idx
            toks = jax.lax.dynamic_index_in_dim(tokens_mb, mb_in, 0,
                                                keepdims=False)
            inject = jnp.take(embed, toks, axis=0).astype(adt)
            inject = constrain(inject, rules, "batch", "seq_sp", "embed")
            buf = jnp.where((stage == 0) & (tick_idx < m), inject, buf)

            out = stage_body(stack, shared, buf)
            out = jnp.where(active, out, buf)

            # last stage: loss for microbatch (tick − S + 1)
            h = L.apply_norm(cfg.norm, params_c["final_norm"], out)
            logits = h.astype(adt) @ head.astype(adt)
            logits = constrain(logits, rules, "batch", "seq_sp", None)
            labs = jax.lax.dynamic_index_in_dim(labels_mb, mb_safe, 0,
                                                keepdims=False)
            nll = cross_entropy(logits, labs, rules)
            is_last = stage == n_stages - 1
            loss_t = jnp.where(active & is_last, nll, 0.0)

            # advance the pipe: stage s → s + 1 (last wraps to 0, whose
            # buffer is overwritten by the next injection)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(out, "stage", perm)
            return buf, loss_t

        buf0 = jnp.zeros((b_mb, t, cfg.d_model), adt)
        _, losses = jax.lax.scan(tick, buf0,
                                 jnp.arange(m + n_stages - 1))
        # every stage returns the same psum'd mean loss
        total = jax.lax.psum(jnp.sum(losses), "stage") / m
        return total

    # stacked layer params get P('stage') on the leading (repeat) dim;
    # everything else is replicated across stages (auto axes still shard
    # them over data/model as usual).
    def param_pp_specs(params):
        def leaf_spec(path, x):
            if path and getattr(path[0], "key", None) == "stack":
                return P("stage")
            return P()
        return jax.tree_util.tree_map_with_path(leaf_spec, params)

    def loss_fn(params, batch):
        tokens, labels = batch["tokens"], batch["labels"]
        b = tokens.shape[0]
        assert b % n_micro == 0, (b, n_micro)
        tokens_mb = tokens.reshape(n_micro, b // n_micro, -1)
        labels_mb = labels.reshape(n_micro, b // n_micro, -1)
        f = _shard_map(
            pipelined, mesh,
            in_specs=(param_pp_specs(params), P(), P()),
            out_specs=P(),
        )
        return f(params, tokens_mb, labels_mb)

    return loss_fn
