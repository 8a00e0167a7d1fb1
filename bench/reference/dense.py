"""Plain float32 reference of a Qwen3-style dense decoder, in jax.numpy.

It imports nothing of the program. It reads the configuration file's
published keys and the weights the benchmark made, and recomputes one
sequence at a time, layer by layer, at the highest matmul precision:

- token embedding; per layer, RMSNorm (eps from the file), q/k/v
  projections, per-head RMSNorm of q and k (Qwen3's qk-norm), rotary
  embedding (rotate-half, ``rope_theta``), grouped-query attention (query
  head ``j`` reads key/value head ``j % num_key_value_heads``, the flat
  head index running over (group, kv head)), output projection and
  residual; RMSNorm, SwiGLU MLP and residual; final RMSNorm; the head
  (the embedding, transposed, when tied).
- ``attention_backend`` "softmax": causal softmax attention scaled by
  head_dim**-0.5. "linear": the paper's causal linear attention with
  ``linear_feature_map`` elu+1 applied to q and k after the rotary
  embedding, and the key-sum normaliser,
  o_t = sum_{s<=t} (phi(q_t).phi(k_s)) v_s / max(phi(q_t).sum_{s<=t} phi(k_s), 1e-6),
  computed block by block: each block's own causal scores, plus its
  queries against the state and key sum of all earlier blocks.

``quant="fp8"`` is the control: every matmul that takes a weight rounds
both its operands to float8 e4m3 with a per-tensor scale, the precision
one step below the configuration's bfloat16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
BLOCK = 256           # attention block (queries, and linear chunks)
HEAD_ROWS = 256       # positions per block of the output head


def _q8(x):
    """Round to float8 e4m3 under a per-tensor scale, back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a, w, quant):
    if quant == "fp8":
        a, w = _q8(a), _q8(w)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, g, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * g


def _rope(x, pos, theta):
    """x: (T, H, D); rotate-half rotary embedding."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs          # (T, D/2)
    c, s = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x1 * s + x2 * c], axis=-1)


def _softmax_attention(q, k, v):
    """q: (H, T, D); k, v: (H, T, D) already expanded to query heads."""
    h, t, d = q.shape
    nb = t // BLOCK
    kpos = jnp.arange(t)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * BLOCK, BLOCK, axis=1)
        s = jnp.einsum("hqd,hkd->hqk", qi, k, precision=HIGHEST) * d ** -0.5
        qpos = i * BLOCK + jnp.arange(BLOCK)
        s = jnp.where((kpos[None, :] <= qpos[:, None])[None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,hkd->hqd", p, v, precision=HIGHEST)

    out = jax.lax.map(block, jnp.arange(nb))                 # (nb,H,B,D)
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(h, t, d)


def _linear_attention(q, k, v):
    """Causal normalised linear attention over phi(q), phi(k)."""
    h, t, d = q.shape
    nb = t // BLOCK
    mask = jnp.tril(jnp.ones((BLOCK, BLOCK), jnp.float32))

    def blocks(x):
        return jnp.transpose(x.reshape(h, nb, BLOCK, -1), (1, 0, 2, 3))

    def step(carry, qkv):
        s, z = carry                                         # (H,D,D),(H,D)
        qi, ki, vi = qkv
        scores = jnp.einsum("hqd,hkd->hqk", qi, ki, precision=HIGHEST) * mask
        num = (jnp.einsum("hqk,hkv->hqv", scores, vi, precision=HIGHEST)
               + jnp.einsum("hqd,hdv->hqv", qi, s, precision=HIGHEST))
        den = jnp.sum(scores, axis=-1) + jnp.einsum(
            "hqd,hd->hq", qi, z, precision=HIGHEST)
        out = num / jnp.maximum(den, 1e-6)[..., None]
        s = s + jnp.einsum("hkd,hkv->hdv", ki, vi, precision=HIGHEST)
        z = z + jnp.sum(ki, axis=1)
        return (s, z), out

    init = (jnp.zeros((h, d, d), jnp.float32), jnp.zeros((h, d), jnp.float32))
    _, out = jax.lax.scan(step, init, (blocks(q), blocks(k), blocks(v)))
    return jnp.transpose(out, (1, 0, 2, 3)).reshape(h, t, d)


@functools.partial(jax.jit, static_argnames=("conf_key", "quant"))
def _layer(x, lp, *, conf_key, quant):
    conf = dict(conf_key)
    t = x.shape[0]
    h, hkv = conf["num_attention_heads"], conf["num_key_value_heads"]
    dh, eps = conf["head_dim"], conf["rms_norm_eps"]
    a = lp["attn"]
    hn = _rms(x, lp["norm1"]["scale"], eps)
    q = _mm(hn, a["wq"], quant).reshape(t, h, dh)
    k = _mm(hn, a["wk"], quant).reshape(t, hkv, dh)
    v = _mm(hn, a["wv"], quant).reshape(t, hkv, dh)
    q = _rms(q, a["q_norm"], eps)
    k = _rms(k, a["k_norm"], eps)
    pos = jnp.arange(t)
    q = _rope(q, pos, conf["rope_theta"])
    k = _rope(k, pos, conf["rope_theta"])
    kv_of = jnp.arange(h) % hkv
    q = jnp.transpose(q, (1, 0, 2))                          # (H, T, D)
    k = jnp.transpose(k[:, kv_of], (1, 0, 2))
    v = jnp.transpose(v[:, kv_of], (1, 0, 2))
    if conf["attention_backend"] == "softmax":
        o = _softmax_attention(q, k, v)
    else:
        o = _linear_attention(jax.nn.elu(q) + 1.0, jax.nn.elu(k) + 1.0, v)
    o = jnp.transpose(o, (1, 0, 2)).reshape(t, h * dh)
    x = x + _mm(o, a["wo"], quant)
    m = lp["mlp"]
    hn = _rms(x, lp["norm2"]["scale"], eps)
    gate = _mm(hn, m["w_gate"], quant)
    up = _mm(hn, m["w_up"], quant)
    return x + _mm(jax.nn.silu(gate) * up, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _head_block(x, gain, head, *, eps, quant):
    """Final norm and logits of a block of positions: (R, V)."""
    hn = _rms(x, gain, eps)
    return _mm(hn, head, quant)


def _conf_key(conf: Dict[str, Any]) -> tuple:
    keys = ("num_attention_heads", "num_key_value_heads", "head_dim",
            "rms_norm_eps", "rope_theta", "attention_backend")
    out = {k: conf[k] for k in keys}
    out["rms_norm_eps"] = float(out["rms_norm_eps"])
    out["rope_theta"] = float(out["rope_theta"])
    if conf["attention_backend"] == "linear":
        if conf.get("linear_feature_map", "elu1") != "elu1" or not conf.get(
                "linear_normalize", True):
            raise ValueError("the reference computes elu+1 features with "
                             "the key-sum normaliser only")
    return tuple(sorted(out.items()))


def _padded(n: int) -> int:
    """Sequence lengths are padded to a power of two of at least one
    block, and past 4096 to a multiple of 4096, so a few compiled
    programs serve every request. Padding sits after the sequence:
    causal attention never reads it."""
    if n > 4096:
        return -(-n // 4096) * 4096
    p = BLOCK
    while p < n:
        p *= 2
    return p


def hidden_states(params: Dict[str, Any], conf: Dict[str, Any],
                  tokens: np.ndarray, quant: Optional[str] = None):
    """Residual stream after the last layer, (T_padded, D) float32."""
    n = len(tokens)
    toks = np.zeros(_padded(n), np.int32)
    toks[:n] = tokens
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)
    x = params["embed"][jnp.asarray(toks)].astype(jnp.float32)
    stack = params["stack"][0]
    key = _conf_key(conf)
    for i in range(conf["num_hidden_layers"]):
        lp = f32(jax.tree.map(lambda a: a[i], stack))
        x = _layer(x, lp, conf_key=key, quant=quant)
    return x


def _head_matrix(params, conf):
    if conf["tie_word_embeddings"]:
        return params["embed"].astype(jnp.float32).T
    return params["lm_head"].astype(jnp.float32)


def served_gaps(params: Dict[str, Any], conf: Dict[str, Any],
                prompt: np.ndarray, served: np.ndarray,
                control: bool = False) -> np.ndarray:
    """For each served token, how far its logit lies below the best
    logit of the float32 reference at that position (0 where it is the
    reference's own choice). With ``control``, the token at each
    position is instead the one the fp8 control ranks first."""
    seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
    p = len(prompt)
    eps = float(conf["rms_norm_eps"])
    gain = params["final_norm"]["scale"].astype(jnp.float32)
    head = _head_matrix(params, conf)
    x = hidden_states(params, conf, seq)
    x8 = hidden_states(params, conf, seq, quant="fp8") if control else None
    gaps = []
    for r0 in range(p - 1, len(seq), HEAD_ROWS):
        rows = np.arange(r0, min(r0 + HEAD_ROWS, len(seq)))
        pad = np.zeros(HEAD_ROWS, np.int32)
        pad[:len(rows)] = rows
        logits = _head_block(x[pad], gain, head, eps=eps, quant=None)
        if control:
            pick = jnp.argmax(_head_block(x8[pad], gain, head, eps=eps,
                                          quant="fp8"), axis=-1)
        else:
            tgt = np.zeros(HEAD_ROWS, np.int32)
            tgt[:len(rows)] = served[rows - (p - 1)]
            pick = jnp.asarray(tgt)
        best = jnp.max(logits, axis=-1)
        chosen = jnp.take_along_axis(logits, pick[:, None], axis=-1)[:, 0]
        gaps.append(np.asarray(best - chosen)[:len(rows)])
    return np.concatenate(gaps)
