"""Pallas TPU kernels for fused multi-step recurrent decode.

The serving hot path used to dispatch one kernel per generated token and
round-trip the (Dk, Dv) state through HBM every step. These kernels run
``W`` decode steps over a block of heads in ONE launch:

* grid = (N // block_bh, W) with the token axis minor, so TPU iterates
  the W steps sequentially per head-block program — the same
  sequential-grid carry trick as the chunked prefill kernels, at token
  granularity;
* the (block_bh, Dk, Dv) state lives in a VMEM scratch for the whole
  launch: it is read from HBM once (w == 0) and written back once
  (w == W−1), so HBM state traffic is O(Dk·Dv) per head per W tokens
  instead of per token;
* the HBM state buffer is updated in place via input/output aliasing —
  the W-step generalisation of the ``kernels/lookup`` decode trick,
  extended from one head to the full (N,) extent;
* the state may be a stack of L layers, (L, N, Dk, Dv), with the layer
  to advance passed as a scalar-prefetch index: the state blocks' index
  map selects that layer, so the kernel reads and writes only its
  blocks and the rest of the stack stays where it is, untouched. A layer
  scan can then carry the whole stack and hand it to every layer's
  launch, with no per-layer slice or write-back around the kernel. An
  unstacked state is the stack of one.

Token rows are laid out token-major, (W, N, D): the block a grid step
reads is a (block_bh, D) slab of one token, whose last two dimensions
meet the TPU's (8, 128) tiling. A head-major (N, W, D) layout would need
a (block_bh, 1, D) block, which Mosaic refuses (a 1 in the second-minor
position). ``ops.py`` keeps the (B, H, W, D) interface and transposes.

Heads are blocked rather than one-per-program because a decode step is a
rank-1 update — an M=1 matmul that would waste the 128×128 MXU — so the
update runs as batched VPU outer-products/reductions over ``block_bh``
heads at once, and the grid stays small (which also keeps the
interpret-mode CPU fallback cheap: kernel-body executions scale with
W · N/block_bh, not W · N).

Three recurrences share one kernel body:

  linear              S ← S + k vᵀ ;               o = Sᵀ q
  linear (normalize)  additionally z ← z + k ;     o /= q·z
  gated               S ← diag(exp(g)) S + k vᵀ ;  o = Sᵀ q

Every variant also has a **variable-length masked** form (``lens=...``):
each of the N rows carries its own valid length, and at window step w a
row with ``w >= lens`` is inert — no state update, no normaliser update,
zero output. That per-row masking inside the VMEM-resident scan is what
lets ONE launch advance a batch of slots by *different* numbers of
tokens (bucket-padded chunked prefill, batched speculative rewind),
instead of one launch per distinct window length.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.linear_attention import safe_denom

# block_bh is sized from a stated budget, and the scoped-VMEM limit is
# passed to Mosaic explicitly (not left to the compiler's default).
# v5e has 128 MiB of VMEM per core; the kernel asks for 32 MiB and gives
# half of it to the f32 (block_bh, Dk, Dv)-sized buffers, which are live
# at once: the state block double-buffered in and out (4), the resident
# scratch (1), and the body's temporaries — decay product, outer
# product, update, masked select, lookup product (5). The other half
# holds the double-buffered token rows and Mosaic's internal scratch.
_VMEM_LIMIT_BYTES = 32 * 2**20
_STATE_BUFFERS = 10
_STATE_BUDGET_BYTES = _VMEM_LIMIT_BYTES // 2
# second-minor tile of the (block_bh, D) token rows: 8 sublanes in f32,
# 16 for packed 16-bit rows
_ROW_TILE = 16


def _block_bh(n: int, dk: int, dv: int) -> int:
    """Largest divisor of n that is a multiple of the row tile and whose
    state-sized buffers fit the budget; n itself when n fits or has no
    such divisor (a block equal to the whole axis is always legal)."""
    cap = max(1, _STATE_BUDGET_BYTES // (_STATE_BUFFERS * dk * dv * 4))
    if n <= cap:
        return n
    for b in range(cap - cap % _ROW_TILE, 0, -_ROW_TILE):
        if n % b == 0:
            return b
    return n


def _kernel(layer_ref, *refs, varlen, normalize, gated, eps):
    """One decode step of one head block: ``layer_ref`` is the scalar-
    prefetched layer index (read by the index maps only); refs are, in
    order, [lens], s, [z], q, k, v, [g] (inputs), o, s_out, [z_out]
    (outputs), and the s [, z] VMEM scratch — bracketed ones only for
    the variant that uses them."""
    del layer_ref
    refs = list(refs)
    lens_ref = refs.pop(0) if varlen else None
    s_ref = refs.pop(0)
    z_ref = refs.pop(0) if normalize else None
    q_ref, k_ref, v_ref = refs.pop(0), refs.pop(0), refs.pop(0)
    g_ref = refs.pop(0) if gated else None
    o_ref, s_out_ref = refs.pop(0), refs.pop(0)
    z_out_ref = refs.pop(0) if normalize else None
    s_scratch = refs.pop(0)
    z_scratch = refs.pop(0) if normalize else None
    w = pl.program_id(1)

    @pl.when(w == 0)
    def _load():
        s_scratch[...] = s_ref[...].astype(jnp.float32)
        if normalize:
            z_scratch[...] = z_ref[...].astype(jnp.float32)

    q = q_ref[...].astype(jnp.float32)           # (bn, Dk)
    k = k_ref[...].astype(jnp.float32)           # (bn, Dk)
    v = v_ref[...].astype(jnp.float32)           # (bn, Dv)
    s_prev = s_scratch[...]                      # (bn, Dk, Dv)
    decayed = s_prev
    if gated:
        a = jnp.exp(g_ref[...].astype(jnp.float32))      # (bn, Dk)
        decayed = a[:, :, None] * s_prev
    s = decayed + k[:, :, None] * v[:, None, :]
    valid = lens_ref[...] > w if varlen else None        # (bn, 1) bool
    if varlen:
        s = jnp.where(valid[:, :, None], s, s_prev)
    s_scratch[...] = s
    o = jnp.sum(s * q[:, :, None], axis=1)               # (bn, Dv)
    if normalize:
        z_prev = z_scratch[...]
        z = z_prev + k
        if varlen:
            z = jnp.where(valid, z, z_prev)
        z_scratch[...] = z
        # shared sign-preserving clamp: kernel-vs-reference equality is
        # the acceptance check, so the denominators must be the same
        # formula
        o = o / safe_denom(jnp.sum(q * z, axis=1), eps)[:, None]
    if varlen:
        o = jnp.where(valid, o, 0.0)
    o_ref[...] = o.astype(o_ref.dtype)

    @pl.when(w == pl.num_programs(1) - 1)
    def _store():
        s_out_ref[...] = s_scratch[...].astype(s_out_ref.dtype)
        if normalize:
            z_out_ref[...] = z_scratch[...].astype(z_out_ref.dtype)


def _decode(s, q, k, v, *, z=None, g=None, lens=None, layer=None,
            eps=1e-6, interpret=False):
    """Shared launcher. s: (N, Dk, Dv), or with ``layer`` the stacked
    (L, N, Dk, Dv) states of L layers, of which only layer ``layer`` (a
    traced int32 scalar) is read and advanced; q, k, g: (W, N, Dk);
    v: (W, N, Dv); z: (N, Dk), or (L, N, Dk) with ``layer``, or None;
    lens: (N,) or None. Returns (o: (W, N, Dv), s_new, z_new) with s
    (and z) aliased in place: with ``layer``, the whole stack comes back
    with that layer's blocks rewritten and every other byte untouched."""
    stacked = layer is not None
    if not stacked:
        s = s[None]
        z = None if z is None else z[None]
        layer = 0
    _, n, dk, dv = s.shape
    w_steps = q.shape[0]
    bn = _block_bh(n, dk, dv)
    varlen, normalize, gated = lens is not None, z is not None, \
        g is not None

    def row(dim):
        return pl.BlockSpec((None, bn, dim), lambda b, w, l: (w, b, 0))

    state = pl.BlockSpec((None, bn, dk, dv),
                         lambda b, w, l: (l[0], b, 0, 0))
    vec = pl.BlockSpec((None, bn, dk), lambda b, w, l: (l[0], b, 0))
    # operand 0 is the scalar-prefetched layer index; aliases count it
    args, in_specs = [], []
    if varlen:
        args.append(lens.astype(jnp.int32).reshape(n, 1))
        in_specs.append(pl.BlockSpec((bn, 1), lambda b, w, l: (b, 0)))
    aliases = {1 + len(args): 1}
    args.append(s)
    in_specs.append(state)
    if normalize:
        aliases[1 + len(args)] = 2
        args.append(z)
        in_specs.append(vec)
    args += [q, k, v]
    in_specs += [row(dk), row(dk), row(dv)]
    if gated:
        args.append(g)
        in_specs.append(row(dk))
    out_specs = [row(dv), state]
    out_shape = [jax.ShapeDtypeStruct((w_steps, n, dv), v.dtype),
                 jax.ShapeDtypeStruct(s.shape, s.dtype)]
    scratch = [pltpu.VMEM((bn, dk, dv), jnp.float32)]
    if normalize:
        out_specs.append(vec)
        out_shape.append(jax.ShapeDtypeStruct(z.shape, z.dtype))
        scratch.append(pltpu.VMEM((bn, dk), jnp.float32))
    outs = pl.pallas_call(
        functools.partial(_kernel, varlen=varlen, normalize=normalize,
                          gated=gated, eps=eps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(n // bn, w_steps),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch),
        out_shape=out_shape,
        input_output_aliases=aliases,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(jnp.asarray(layer, jnp.int32).reshape(1), *args)
    o, s_new = outs[0], outs[1]
    z_new = outs[2] if normalize else None
    if not stacked:
        s_new = s_new[0]
        z_new = None if z_new is None else z_new[0]
    return o, s_new, z_new


def decode_linear(s, q, k, v, *, z=None, normalize=False,
                  eps: float = 1e-6, lens=None, layer=None,
                  interpret: bool = False):
    """W fused decode steps of the plain linear recurrence.

    s: (N, Dk, Dv); q, k: (W, N, Dk); v: (W, N, Dv); z: (N, Dk) or None.
    ``lens``: (N,) int32 per-row valid lengths — row n consumes only its
    first lens[n] window tokens (masked steps are inert; lens=0 rows are
    untouched bit-for-bit). ``layer``: an int32 scalar, with s (and z)
    the stacked (L, N, Dk, Dv) and (L, N, Dk) states — only that layer
    is read and advanced. Returns (o: (W, N, Dv), s_new, z_new) with s
    (and z) updated in place via input/output aliasing.
    """
    assert not normalize or z is not None, \
        "normalize=True needs the key-sum normaliser z"
    return _decode(s, q, k, v, z=z if normalize else None, lens=lens,
                   layer=layer, eps=eps, interpret=interpret)


def decode_gated(s, q, k, v, g, *, lens=None, layer=None,
                 interpret: bool = False):
    """W fused decode steps of the gated recurrence (inclusive form).

    s: (N, Dk, Dv); q, k, g: (W, N, Dk); v: (W, N, Dv). g is the
    per-token log-decay (a = exp(g)); pass a broadcasted row for scalar
    per-head decay. ``lens``: (N,) int32 per-row valid lengths (masked
    steps are inert — no decay, no update). ``layer``: as in
    :func:`decode_linear`, with s stacked (L, N, Dk, Dv). Returns
    (o: (W, N, Dv), s_new) with s updated in place via input/output
    aliasing.
    """
    o, s_new, _ = _decode(s, q, k, v, g=g, lens=lens, layer=layer,
                          interpret=interpret)
    return o, s_new
