"""Fused recurrent decode engine: W-step kernels + single-dispatch
generation.

Acceptance contract of the decode engine:
  * the fused W-step Pallas kernels (interpret=True on CPU — the exact
    kernel code path) match W sequential single-token ``decode_step`` /
    ``gated_decode_step`` calls to ≤ 1e-4;
  * ``lm.decode_window`` (one launch per layer for W known tokens)
    matches W sequential ``lm.decode_step`` calls;
  * ``lm.generate`` (one dispatch for the whole generation) reproduces
    the token sequence of the pre-fusion per-token Python loop on the
    yi-34b smoke config.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke_config
from repro.core.gated import gated_decode_step
from repro.core.linear_attention import decode_step
from repro.kernels.fused_recurrent import ops as fr_ops
from repro.models import lm
from repro.sharding import Rules

RULES = Rules.null()
TOL = 1e-4


def _qkv(key, b, h, w, dk, dv, positive=False):
    ks = jax.random.split(key, 5)
    q = jax.random.normal(ks[0], (b, h, w, dk))
    k = jax.random.normal(ks[1], (b, h, w, dk))
    if positive:  # elu1-style features: the normaliser's operating regime
        q = jax.nn.elu(q) + 1.0
        k = jax.nn.elu(k) + 1.0
    v = jax.random.normal(ks[2], (b, h, w, dv))
    s = jax.random.normal(ks[3], (b, h, dk, dv))
    z = jnp.abs(jax.random.normal(ks[4], (b, h, dk)))
    return q, k, v, s, z


class TestFusedKernelMatchesSequential:
    """Fused W steps == W single-step core calls (the pre-fusion path)."""

    @pytest.mark.parametrize("b,h,w,dk,dv", [
        (2, 4, 1, 16, 16),      # W=1: the lm.generate hot path
        (2, 4, 8, 16, 16),
        (1, 3, 5, 32, 32),      # BH not a power of two
    ])
    def test_linear(self, key, b, h, w, dk, dv):
        q, k, v, s, _ = _qkv(key, b, h, w, dk, dv)
        o_f, s_f, _ = fr_ops.fused_recurrent_linear(
            s, q, k, v, interpret=True)
        s_ref = s
        for i in range(w):
            o_ref, s_ref, _ = decode_step(
                s_ref, q[:, :, i], k[:, :, i], v[:, :, i])
            np.testing.assert_allclose(o_f[:, :, i], o_ref,
                                       rtol=TOL, atol=TOL)
        np.testing.assert_allclose(s_f, s_ref, rtol=TOL, atol=TOL)

    @pytest.mark.parametrize("w", [1, 8])
    def test_linear_normalized(self, key, w):
        b, h, dk = 2, 4, 16
        q, k, v, s, z = _qkv(key, b, h, w, dk, dk, positive=True)
        o_f, s_f, z_f = fr_ops.fused_recurrent_linear(
            s, q, k, v, z=z, normalize=True, interpret=True)
        s_ref, z_ref = s, z
        for i in range(w):
            o_ref, s_ref, z_ref = decode_step(
                s_ref, q[:, :, i], k[:, :, i], v[:, :, i],
                z=z_ref, normalize=True)
            np.testing.assert_allclose(o_f[:, :, i], o_ref,
                                       rtol=TOL, atol=TOL)
        np.testing.assert_allclose(s_f, s_ref, rtol=TOL, atol=TOL)
        np.testing.assert_allclose(z_f, z_ref, rtol=TOL, atol=TOL)

    @pytest.mark.parametrize("w", [1, 8])
    def test_gated(self, key, w):
        b, h, dk = 2, 4, 16
        q, k, v, s, _ = _qkv(key, b, h, w, dk, dk)
        g = -jax.nn.softplus(
            jax.random.normal(jax.random.fold_in(key, 9), (b, h, w, dk)))
        o_f, s_f = fr_ops.fused_recurrent_gated(s, q, k, v, g,
                                                interpret=True)
        s_ref = s
        for i in range(w):
            o_ref, s_ref = gated_decode_step(
                s_ref, q[:, :, i], k[:, :, i], v[:, :, i], g[:, :, i])
            np.testing.assert_allclose(o_f[:, :, i], o_ref,
                                       rtol=TOL, atol=TOL)
        np.testing.assert_allclose(s_f, s_ref, rtol=TOL, atol=TOL)

    def test_state_dtype_and_shape_preserved(self, key):
        """In-place aliasing contract: s_new has s's dtype and shape."""
        q, k, v, s, _ = _qkv(key, 2, 4, 3, 16, 16)
        _, s_f, _ = fr_ops.fused_recurrent_linear(s, q, k, v,
                                                  interpret=True)
        assert s_f.shape == s.shape and s_f.dtype == s.dtype


class TestStackedEntry:
    """The stacked entry — the whole (L, B, H, Dk, Dv) stack and a layer
    index — equals the unstacked kernel on that layer's slice, bit for
    bit, and touches nothing else: not the inactive rows (lens = 0, the
    slot freeze at W = 1), not the other layers."""

    @pytest.mark.parametrize("layer", [0, 2])
    @pytest.mark.parametrize("variant",
                             ["linear", "linear_normalize", "gated"])
    def test_matches_sliced_layer(self, key, variant, layer):
        n_layers, b, h, d = 4, 3, 4, 128
        ks = jax.random.split(key, 6)
        s = jax.random.normal(ks[0], (n_layers, b, h, d, d))
        z = jnp.abs(jax.random.normal(ks[1], (n_layers, b, h, d)))
        q, k, v, _, _ = _qkv(ks[2], b, h, 1, d, d, positive=True)
        g = -jax.nn.softplus(jax.random.normal(ks[3], (b, h, 1, d)))
        lens = jnp.asarray([1, 0, 1], jnp.int32)   # row 1 inactive

        def run(s_in, z_in, at):
            if variant == "gated":
                o, s_out = fr_ops.fused_recurrent_gated(
                    s_in, q, k, v, g, lens=lens, layer=at, interpret=True)
                return o, s_out, None
            return fr_ops.fused_recurrent_linear(
                s_in, q, k, v, z=z_in,
                normalize=variant == "linear_normalize", lens=lens,
                layer=at, interpret=True)

        o, s_new, z_new = run(s, z, jnp.int32(layer))
        o_1, s_1, z_1 = run(s[layer], z[layer], None)
        assert s_new.shape == s.shape
        np.testing.assert_array_equal(o, o_1)
        np.testing.assert_array_equal(s_new[layer], s_1)
        np.testing.assert_array_equal(s_new[layer, 1], s[layer, 1])
        assert not np.array_equal(s_new[layer, 0], s[layer, 0])
        others = np.asarray([i for i in range(n_layers) if i != layer])
        np.testing.assert_array_equal(s_new[others], s[others])
        if variant == "linear_normalize":
            np.testing.assert_array_equal(z_new[layer], z_1)
            np.testing.assert_array_equal(z_new[layer, 1], z[layer, 1])
            np.testing.assert_array_equal(z_new[others], z[others])


class TestModelWindowDecode:
    """lm.decode_window == W sequential lm.decode_step calls, with the
    Pallas kernels forced (decode_kernel="fused" → interpret on CPU)."""

    @pytest.mark.parametrize("backend", ["linear", "gated_linear"])
    def test_window_matches_steps(self, key, backend):
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend(backend),
            decode_kernel="fused")
        params = lm.init_params(key, cfg)
        b, w = 2, 6
        toks = jax.random.randint(key, (b, w), 0, cfg.vocab_size)
        state0 = lm.init_decode_state(cfg, batch=b, max_len=16)

        st = state0
        logits_seq = []
        for i in range(w):
            lg, st = lm.decode_step(params, st, toks[:, i], jnp.int32(i),
                                    cfg, RULES)
            logits_seq.append(lg)
        logits_seq = jnp.stack(logits_seq, 1)

        logits_win, st_w = lm.decode_window(params, state0, toks,
                                            jnp.int32(0), cfg, RULES)
        np.testing.assert_allclose(
            logits_win.astype(jnp.float32),
            logits_seq.astype(jnp.float32), rtol=1e-3, atol=1e-3)
        for a, b_ in zip(jax.tree.leaves(st), jax.tree.leaves(st_w)):
            np.testing.assert_allclose(
                a.astype(jnp.float32), b_.astype(jnp.float32),
                rtol=1e-3, atol=1e-3)

    @pytest.mark.parametrize("backend", ["linear", "gated_linear"])
    def test_fused_matches_reference_kernel(self, key, backend):
        """decode_kernel="fused" (Pallas) and "reference" (jnp scan)
        produce the same decode_step logits — the backend-selection
        switch must not change the math."""
        base = get_smoke_config("yi-34b").with_backend(backend)
        params = lm.init_params(key, base)
        state = lm.init_decode_state(base, batch=2, max_len=8)
        tok = jnp.zeros((2,), jnp.int32)
        outs = {}
        for kern in ("fused", "reference"):
            cfg = dataclasses.replace(base, decode_kernel=kern)
            outs[kern], _ = lm.decode_step(params, state, tok,
                                           jnp.int32(0), cfg, RULES)
        np.testing.assert_allclose(
            outs["fused"].astype(jnp.float32),
            outs["reference"].astype(jnp.float32), rtol=TOL, atol=TOL)


class TestGenerate:
    """The scan-based single-dispatch generation loop."""

    @pytest.mark.parametrize("backend",
                             ["linear", "gated_linear", "softmax"])
    def test_generate_matches_per_token_loop(self, key, backend):
        """lm.generate reproduces the pre-fusion serve driver: prefill →
        greedy argmax → per-token jitted decode_step loop."""
        cfg = get_smoke_config("yi-34b").with_backend(backend)
        params = lm.init_params(key, cfg)
        b, t_p, t_g = 2, 12, 9
        prompt = jax.random.randint(key, (b, t_p), 0, cfg.vocab_size)

        logits, states = lm.prefill(params, prompt, cfg, RULES)
        states = lm.pad_decode_state(states, cfg, max_len=t_p + t_g)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)

        # the seed per-token loop, verbatim
        old_tokens = [tok]
        st, t = states, tok
        for i in range(t_g - 1):
            lg, st = lm.decode_step(params, st, t, jnp.int32(t_p + i),
                                    cfg, RULES)
            t = jnp.argmax(lg, -1).astype(jnp.int32)
            old_tokens.append(t)
        old_tokens = jnp.stack(old_tokens, 1)

        new_toks, _ = lm.generate(params, states, tok, t_p, t_g - 1,
                                  cfg, RULES)
        new_tokens = jnp.concatenate([tok[:, None], new_toks], axis=1)
        np.testing.assert_array_equal(np.asarray(new_tokens),
                                      np.asarray(old_tokens))

    def test_generate_unnormalized_linear(self, key):
        """linear_normalize=False: the state carries z=None, which must
        stay structure-stable through the generation scan (regression:
        init_decode_state used to allocate z unconditionally while the
        decode step returned z=None, breaking the scan carry)."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            linear_normalize=False)
        params = lm.init_params(key, cfg)
        states = lm.init_decode_state(cfg, batch=2, max_len=16)
        toks, _ = lm.generate(params, states, jnp.zeros((2,), jnp.int32),
                              0, 4, cfg, RULES)
        assert toks.shape == (2, 4)
        # the W>1 window path shares the same carry structure
        logits, _ = lm.decode_window(
            params, states, jnp.zeros((2, 3), jnp.int32), jnp.int32(0),
            cfg, RULES)
        assert logits.shape == (2, 3, cfg.vocab_size)

    def test_temperature_requires_key(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        states = lm.init_decode_state(cfg, batch=2, max_len=16)
        with pytest.raises(ValueError, match="PRNG key"):
            lm.generate(params, states, jnp.zeros((2,), jnp.int32),
                        0, 3, cfg, RULES, temperature=0.7)

    def test_temperature_sampling_shape_and_validity(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        states = lm.init_decode_state(cfg, batch=2, max_len=16)
        toks, _ = lm.generate(params, states, jnp.zeros((2,), jnp.int32),
                              0, 5, cfg, RULES, temperature=0.8, key=key)
        assert toks.shape == (2, 5)
        assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size)))

    def test_generate_single_dispatch_jits(self, key):
        """The whole generation compiles as one jitted computation."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        states = lm.init_decode_state(cfg, batch=2, max_len=40)
        gen = jax.jit(lambda p, st, tok: lm.generate(
            p, st, tok, 0, 16, cfg, RULES))
        toks, st = gen(params, states, jnp.zeros((2,), jnp.int32))
        assert toks.shape == (2, 16)
        assert bool(jnp.all(jnp.isfinite(
            jax.tree.leaves(st)[0].astype(jnp.float32))))
