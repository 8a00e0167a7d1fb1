"""Jit'd public wrappers for the fused W-step recurrent decode kernels.

Handles the (B, H, W, D) ↔ token-major (W, B·H, D) layout change and
the interpret-mode fallback used for CPU validation (the deployment
target is TPU; on CPU the kernels run through the Pallas interpreter,
so tests exercise the exact kernel code path).

``lens`` (a (B,) int32 vector of per-row valid window lengths) selects
the variable-length masked kernels: row b advances only its first
lens[b] tokens, masked steps are inert, and lens[b] = 0 leaves the row's
state untouched bit-for-bit — ONE launch serves a batch of slots at
different depths consuming different numbers of tokens.

``layer`` (an int32 scalar) selects the stacked entry: ``s`` (and ``z``)
then hold every layer's state, (L, B, H, Dk, Dv), and only that layer is
read and advanced, in place in the stack — a layer scan carries the
stack and hands it to each layer's launch whole.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.fused_recurrent import kernel as _k

Array = jax.Array


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


def _tokens_major(x: Array) -> Array:
    """(B, H, W, D) → the kernels' token-major (W, B·H, D) layout."""
    b, h, w, d = x.shape
    return jnp.transpose(x, (2, 0, 1, 3)).reshape(w, b * h, d)


def _heads_major(o: Array, b: int, h: int) -> Array:
    """(W, B·H, D) kernel output → (B, H, W, D)."""
    w, _, d = o.shape
    return jnp.transpose(o.reshape(w, b, h, d), (1, 2, 0, 3))


def _lens_bh(lens: Optional[Array], b: int, h: int) -> Optional[Array]:
    """Broadcast a per-batch (B,) length vector over heads → (B·H,)."""
    if lens is None:
        return None
    lens = jnp.asarray(lens, jnp.int32)
    return jnp.broadcast_to(lens[:, None], (b, h)).reshape(b * h)


def fused_recurrent_linear(
    s: Array,
    q: Array,
    k: Array,
    v: Array,
    *,
    z: Optional[Array] = None,
    normalize: bool = False,
    eps: float = 1e-6,
    lens: Optional[Array] = None,
    layer: Optional[Array] = None,
    interpret: bool | None = None,
) -> Tuple[Array, Array, Optional[Array]]:
    """W fused decode steps, plain linear recurrence.

    s: (B, H, Dk, Dv); q, k: (B, H, W, Dk); v: (B, H, W, Dv);
    z: (B, H, Dk) or None; lens: (B,) int32 per-row valid lengths or
    None (full window everywhere); layer: None, or an int32 scalar with
    s (L, B, H, Dk, Dv) and z (L, B, H, Dk) stacked. Returns
    (o: (B, H, W, Dv), s_new, z_new) with the state updated in place
    (input/output aliased) — one kernel launch and one HBM state
    round-trip for the whole window.
    """
    if interpret is None:
        interpret = _on_cpu()
    b, h, w, dk = q.shape
    dv = v.shape[-1]
    lead = s.shape[:-4]            # (L,) when stacked, else ()
    o, s_new, z_new = _k.decode_linear(
        s.reshape(lead + (b * h, dk, dv)),
        _tokens_major(q), _tokens_major(k), _tokens_major(v),
        z=None if z is None else z.reshape(lead + (b * h, dk)),
        normalize=normalize, eps=eps, lens=_lens_bh(lens, b, h),
        layer=layer, interpret=interpret,
    )
    return (
        _heads_major(o, b, h),
        s_new.reshape(s.shape),
        None if z_new is None else z_new.reshape(z.shape),
    )


def fused_recurrent_gated(
    s: Array,
    q: Array,
    k: Array,
    v: Array,
    g: Array,
    *,
    lens: Optional[Array] = None,
    layer: Optional[Array] = None,
    interpret: bool | None = None,
) -> Tuple[Array, Array]:
    """W fused decode steps, gated (decay) recurrence, inclusive form.

    s: (B, H, Dk, Dv); q, k, g: (B, H, W, Dk); v: (B, H, W, Dv).
    g is the log-decay (state is scaled by exp(g) each step); lens:
    (B,) int32 per-row valid lengths or None; layer: None, or an int32
    scalar with s stacked (L, B, H, Dk, Dv). Returns
    (o: (B, H, W, Dv), s_new) with the state updated in place.
    """
    if interpret is None:
        interpret = _on_cpu()
    b, h, w, dk = q.shape
    dv = v.shape[-1]
    o, s_new = _k.decode_gated(
        s.reshape(s.shape[:-4] + (b * h, dk, dv)),
        _tokens_major(q), _tokens_major(k), _tokens_major(v),
        _tokens_major(g),
        lens=_lens_bh(lens, b, h),
        layer=layer, interpret=interpret,
    )
    return _heads_major(o, b, h), s_new.reshape(s.shape)
