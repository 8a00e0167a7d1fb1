"""Continuous-batching serving engine + decode-path edge cases.

Acceptance contract of the engine (ISSUE 2):

* per-slot outputs under admission/eviction churn are BIT-IDENTICAL
  (greedy) to running each request alone — inactive slots are masked
  inside the scan, so sharing the device never changes a request's
  tokens;
* the gen_len=1 / n_steps=0 edges of ``lm.generate`` and serve.py's
  output assembly;
* ``lm.pad_decode_state`` + softmax decode past the prompt on STACKED
  states (the ``st.k_cache.ndim - 3`` axis arithmetic);
* the decode-path numerics fixes (sign-preserving normaliser clamp, the
  non-TPU fused-kernel fallback).
"""

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SSMConfig, get_smoke_config
from repro.core.linear_attention import safe_denom
from repro.models import attention as A
from repro.models import lm
from repro.serving import DecodeEngine
from repro.serving.engine import PAD_ID
from repro.sharding import Rules

RULES = Rules.null()


def _standalone(params, cfg, prompt, gen_len, max_len, eos_id=None):
    """Reference: the request running alone (prefill → greedy generate),
    truncated at the first EOS like the engine truncates."""
    logits, st = lm.prefill(params, jnp.asarray(prompt)[None], cfg, RULES)
    st = lm.pad_decode_state(st, cfg, max_len=max_len)
    tok0 = int(jnp.argmax(logits, -1)[0])
    toks = [tok0]
    if gen_len > 1 and not (eos_id is not None and tok0 == eos_id):
        more, _ = lm.generate(params, st, jnp.asarray([tok0], jnp.int32),
                              len(prompt), gen_len - 1, cfg, RULES)
        toks += [int(t) for t in np.asarray(more)[0]]
    if eos_id is not None and eos_id in toks:
        toks = toks[:toks.index(eos_id) + 1]
    return toks


def _make_workload(cfg, n=6, prompt_len=8, seed=0):
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=prompt_len,
                            dtype=np.int64).astype(np.int32)
               for _ in range(n)]
    gens = [5, 12, 3, 9, 1, 7][:n]
    return prompts, gens


class TestEngineBitIdentity:
    """Slot execution == run-alone execution, token for token."""

    @pytest.mark.parametrize("backend",
                             ["linear", "gated_linear", "softmax"])
    def test_matches_standalone(self, key, backend):
        cfg = get_smoke_config("yi-34b").with_backend(backend)
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg)
        refs = [_standalone(params, cfg, p, g, 64)
                for p, g in zip(prompts, gens)]

        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        comps = eng.run("continuous")
        assert len(comps) == len(refs)
        for c, ref in zip(comps, refs):
            np.testing.assert_array_equal(c.tokens, np.asarray(ref))
            assert c.finish_reason == "length"
        # the mixed-length workload actually exercised slot churn
        assert eng.stats.prefills == len(refs)
        assert 0.0 < eng.stats.slot_utilization < 1.0

    def test_static_policy_same_outputs(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg)
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        outs = {}
        for policy in ("continuous", "static"):
            eng.reset()
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            outs[policy] = eng.run(policy)
        for a, b in zip(outs["continuous"], outs["static"]):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        # scheduling differs even though outputs don't
        assert eng.stats.segments > 0

    def test_staggered_arrivals(self, key):
        """Arrival times delay admission but never change outputs."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg, n=4)
        refs = [_standalone(params, cfg, p, g, 64)
                for p, g in zip(prompts, gens)]
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        for i, (p, g) in enumerate(zip(prompts, gens)):
            eng.submit(p, g, arrival=6.0 * i)
        comps = eng.run("continuous")
        for c, ref in zip(comps, refs):
            np.testing.assert_array_equal(c.tokens, np.asarray(ref))

    def test_eos_stops_slot_midsegment(self, key):
        """A slot emitting EOS frees itself inside the scan; the output
        is truncated at (and includes) the EOS token."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg, n=3)
        gens = [12, 12, 12]
        plain = [_standalone(params, cfg, p, g, 64)
                 for p, g in zip(prompts, gens)]
        # pick an EOS id that actually occurs mid-generation
        eos_id = next(t for toks in plain for t in toks[1:-1])
        refs = [_standalone(params, cfg, p, g, 64, eos_id=eos_id)
                for p, g in zip(prompts, gens)]
        assert any(len(r) < g for r, g in zip(refs, gens))

        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64, eos_id=eos_id)
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        comps = eng.run("continuous")
        for c, ref in zip(comps, refs):
            np.testing.assert_array_equal(c.tokens, np.asarray(ref))
            expect = "eos" if ref[-1] == eos_id else "length"
            assert c.finish_reason == expect

    def test_instant_completions_dont_waste_slots(self, key):
        """Requests completing at admission (gen_len=1) must not consume
        a slot's admission turn: the same pass keeps feeding the slot,
        and the clock never fast-forwards past admissible work."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, _ = _make_workload(cfg, n=4)
        eng = DecodeEngine(params, cfg, n_slots=1, segment_len=4,
                           max_len=64)
        for p, g in zip(prompts, [1, 1, 1, 5]):
            eng.submit(p, g)
        comps = eng.run("continuous")
        assert len(comps) == 4
        # the real request was admitted at t=0, not after an idle skip
        assert comps[3].admitted_step == 0

    def test_out_of_order_arrivals_not_blocked(self, key):
        """An early-arriving request submitted after a far-future one is
        admitted first (queue is sorted by arrival, not submit order)."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, _ = _make_workload(cfg, n=2)
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        late = eng.submit(prompts[0], 5, arrival=100.0)
        early = eng.submit(prompts[1], 5, arrival=0.0)
        comps = {c.uid: c for c in eng.run("continuous")}
        assert comps[early].admitted_step == 0
        assert comps[late].admitted_step >= 100

    def test_gen_len_one_completes_at_admission(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, _ = _make_workload(cfg, n=2)
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        for p in prompts:
            eng.submit(p, 1)
        comps = eng.run("continuous")
        assert [len(c.tokens) for c in comps] == [1, 1]
        assert eng.stats.segments == 0      # never touched the scan
        for c, p in zip(comps, prompts):
            ref = _standalone(params, cfg, p, 1, 64)
            np.testing.assert_array_equal(c.tokens, np.asarray(ref))


class TestGenerateSegment:
    """The slot-masked scan segment in isolation."""

    def test_inactive_slots_frozen(self, key):
        """Masked slots emit PAD_ID and their state/pos/tok stay
        bit-identical through the scan."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        state = lm.init_decode_state(cfg, batch=2, max_len=16)
        tok = jnp.asarray([3, 7], jnp.int32)
        pos = jnp.asarray([0, 5], jnp.int32)
        active = jnp.asarray([True, False])
        remaining = jnp.asarray([8, 8], jnp.int32)
        toks, carry = lm.generate_segment(
            params, state, tok, pos, active, remaining, 4, cfg, RULES)
        assert toks.shape == (2, 4)
        assert bool(jnp.all(toks[1] == PAD_ID))
        assert bool(jnp.all(toks[0] != PAD_ID))
        assert int(carry["pos"][1]) == 5 and int(carry["tok"][1]) == 7
        # slot 1 frozen bit-for-bit (stack leaves: slot axis 1; tail: 0)
        for leaf_new, leaf_old in zip(
                jax.tree.leaves(carry["state"]["stack"]),
                jax.tree.leaves(state["stack"])):
            np.testing.assert_array_equal(np.asarray(leaf_new[:, 1]),
                                          np.asarray(leaf_old[:, 1]))
        for leaf_new, leaf_old in zip(
                jax.tree.leaves(carry["state"]["tail"]),
                jax.tree.leaves(state["tail"])):
            np.testing.assert_array_equal(np.asarray(leaf_new[1]),
                                          np.asarray(leaf_old[1]))

    def test_budget_stops_inside_scan(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        state = lm.init_decode_state(cfg, batch=2, max_len=16)
        tok = jnp.zeros((2,), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        active = jnp.asarray([True, True])
        remaining = jnp.asarray([2, 6], jnp.int32)
        toks, carry = lm.generate_segment(
            params, state, tok, pos, active, remaining, 6, cfg, RULES)
        row0 = np.asarray(toks[0])
        assert (row0 != PAD_ID).sum() == 2          # budget honoured
        assert bool(np.all(row0[2:] == PAD_ID))     # then padded
        assert not bool(carry["active"][0])
        assert not bool(carry["active"][1])         # 6 steps used 6 budget
        assert int(carry["pos"][0]) == 2

    def test_write_slot_state_roundtrip(self, key):
        """write_slot_state targets exactly one slot of every leaf."""
        cfg = get_smoke_config("yi-34b").with_backend("softmax")
        engine_state = lm.init_decode_state(cfg, batch=3, max_len=8)
        req_state = jax.tree.map(
            lambda x: jnp.ones_like(x),
            lm.init_decode_state(cfg, batch=1, max_len=8))
        out = lm.write_slot_state(engine_state, req_state, 1)
        for leaf in jax.tree.leaves(out["tail"]):
            assert bool(jnp.all(leaf[1] == 1))
            assert bool(jnp.all(leaf[0] == 0)) and \
                bool(jnp.all(leaf[2] == 0))
        for leaf in jax.tree.leaves(out["stack"]):
            assert bool(jnp.all(leaf[:, 1] == 1))
            assert bool(jnp.all(leaf[:, 0] == 0)) and \
                bool(jnp.all(leaf[:, 2] == 0))


class TestInPlaceSegment:
    """Under the fused kernel the layer scan carries the stacked linear
    state and each layer's kernel advances it in place, freezing
    inactive slots itself; the engine donates the slot state to the
    segment. Neither may change a token or leave a stale buffer behind."""

    @staticmethod
    def _engine(params, cfg, kernel):
        return DecodeEngine(
            params, dataclasses.replace(cfg, decode_kernel=kernel),
            n_slots=2, segment_len=4, max_len=64)

    @pytest.mark.parametrize("backend", ["linear", "gated_linear"])
    def test_fused_equals_reference(self, key, backend):
        """Admissions, slots frozen mid-segment and frees: the fused
        (Pallas interpret) engine emits the reference engine's tokens."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend(backend),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg)
        outs = {}
        for kernel in ("fused", "reference"):
            eng = self._engine(params, cfg, kernel)
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            outs[kernel] = eng.run("continuous")
            assert eng.stats.prefills == len(prompts)
            assert 0.0 < eng.stats.slot_utilization < 1.0
        for a, b in zip(outs["fused"], outs["reference"]):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_buffers_valid_after_donated_segment(self, key):
        """The pre-segment state is consumed by the segment; the probe,
        a snapshot and a suspend/resume afterwards read the new state,
        and the preempted request continues token for token."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, _ = _make_workload(cfg, n=2)
        ref = self._engine(params, cfg, "fused")
        for p in prompts:
            ref.submit(p, 14)
        want = {c.uid: c.tokens for c in ref.run("continuous")}

        eng = self._engine(params, cfg, "fused")
        for p in prompts:
            eng.submit(p, 14)
        eng.step()                      # admission, a segment, the probe
        pre = eng.state
        eng.step_segment()
        assert all(leaf.is_deleted() for leaf in jax.tree.leaves(pre))
        assert np.asarray(eng._finite(eng.state)).all()
        snap = eng._snapshot(eng.state, jnp.int32(0))
        assert all(np.isfinite(np.asarray(leaf)).all()
                   for leaf in jax.tree.leaves(snap))
        eng.preempt(1)
        comps = eng.run("continuous")
        assert eng.stats.preemptions == 1 and eng.stats.resumes == 1
        assert sorted(c.uid for c in comps) == sorted(want)
        for c in comps:
            np.testing.assert_array_equal(c.tokens, want[c.uid])


class TestSnapshotRestore:
    """snapshot_state / restore_state — the shared slot-slice primitive
    behind engine admission AND speculative rewind. Stacked leaves carry
    (reps, S, …) with the slot axis at 1; tail leaves (S, …) at 0."""

    @pytest.mark.parametrize("backend", ["linear", "softmax"])
    def test_snapshot_reads_one_slot(self, key, backend):
        cfg = get_smoke_config("yi-34b").with_backend(backend)
        state = lm.init_decode_state(cfg, batch=3, max_len=8)
        # give every slot a distinct fill value along its slot axis
        def fill(x, axis):
            shape = [1] * x.ndim
            shape[axis] = 3
            vals = jnp.arange(1, 4, dtype=x.dtype).reshape(shape)
            return jnp.broadcast_to(vals, x.shape)
        state = lm._map_slots(fill, state)
        for slot in range(3):
            snap = lm.snapshot_state(state, slot)
            for leaf in jax.tree.leaves(snap["tail"]):
                assert leaf.shape[0] == 1
                assert bool(jnp.all(leaf == slot + 1))
            for leaf in jax.tree.leaves(snap["stack"]):
                assert leaf.shape[1] == 1
                assert bool(jnp.all(leaf == slot + 1))

    @pytest.mark.parametrize("backend", ["linear", "gated_linear",
                                         "softmax"])
    def test_snapshot_restore_roundtrip(self, key, backend):
        """restore(state, snapshot(state, i), i) == state, bit for bit,
        and restoring into a DIFFERENT slot moves exactly that slot."""
        cfg = get_smoke_config("yi-34b").with_backend(backend)
        params = lm.init_params(key, cfg)
        prompt = jax.random.randint(key, (3, 6), 0, cfg.vocab_size)
        _, st = lm.prefill(params, prompt, cfg, RULES)
        st = lm.pad_decode_state(st, cfg, max_len=16)

        snap = lm.snapshot_state(st, 1)
        back = lm.restore_state(st, snap, 1)
        for a, b in zip(jax.tree.leaves(st), jax.tree.leaves(back)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        moved = lm.restore_state(st, snap, 2)
        moved_snap = lm.snapshot_state(moved, 2)
        for a, b in zip(jax.tree.leaves(snap),
                        jax.tree.leaves(moved_snap)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # slot 0 untouched
        for a, b in zip(jax.tree.leaves(lm.snapshot_state(moved, 0)),
                        jax.tree.leaves(lm.snapshot_state(st, 0))):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPadDecodeState:
    """pad_decode_state + softmax decode past the prompt on stacked
    states — the ``st.k_cache.ndim - 3`` axis arithmetic."""

    def test_stacked_pad_then_decode_matches_forward(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("softmax")
        b, t_p, extra = 2, 6, 5
        params = lm.init_params(key, cfg)
        tokens = jax.random.randint(key, (b, t_p + extra), 0,
                                    cfg.vocab_size)
        # teacher-forced reference: full forward over the whole sequence
        full_logits, _, _ = lm.forward(params, tokens, cfg, RULES)
        _, states = lm.prefill(params, tokens[:, :t_p], cfg, RULES)
        # stacked leaves are (reps, B, S, Hkv, Dh): pad must hit axis 2
        kc = states["stack"][0].k_cache
        assert kc.ndim == 5 and kc.shape[2] == t_p
        states = lm.pad_decode_state(states, cfg, max_len=t_p + extra)
        assert states["stack"][0].k_cache.shape[2] == t_p + extra

        # decode strictly past the prompt, teacher-forcing known tokens
        st = states
        for i in range(extra - 1):
            logits, st = lm.decode_step(
                params, st, tokens[:, t_p + i], jnp.int32(t_p + i),
                cfg, RULES)
            # bf16 activations; blocked-flash prefill vs cache decode
            np.testing.assert_allclose(
                np.asarray(logits, np.float32),
                np.asarray(full_logits[:, t_p + i], np.float32),
                rtol=5e-2, atol=5e-2)

    def test_pad_noop_for_linear_state(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompt = jax.random.randint(key, (1, 4), 0, cfg.vocab_size)
        _, states = lm.prefill(params, prompt, cfg, RULES)
        padded = lm.pad_decode_state(states, cfg, max_len=128)
        for a, b_ in zip(jax.tree.leaves(states), jax.tree.leaves(padded)):
            assert a.shape == b_.shape


class TestGenerateEdges:
    """gen_len=1 / n_steps=0 edges of generate + serve.py assembly."""

    def test_generate_zero_steps(self, key):
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        state = lm.init_decode_state(cfg, batch=2, max_len=16)
        toks, st = lm.generate(params, state, jnp.zeros((2,), jnp.int32),
                               0, 0, cfg, RULES)
        assert toks.shape == (2, 0)
        for a, b_ in zip(jax.tree.leaves(st), jax.tree.leaves(state)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    @pytest.mark.parametrize("backend", ["linear", "softmax"])
    def test_serve_generate_gen_len_one(self, backend):
        from repro.launch import serve
        args = argparse.Namespace(
            arch="yi-34b", smoke=True, backend=backend, batch=2,
            prompt_len=8, gen_len=1, temperature=0.0, seed=0)
        assert serve.generate(args) == 0

    def test_serve_stream_smoke(self):
        from repro.launch import serve
        args = argparse.Namespace(
            arch="yi-34b", smoke=True, backend="linear", slots=2,
            segment_len=4, n_requests=5, arrival_rate=0.4,
            prompt_len=8, gen_len=12, temperature=0.0, seed=0)
        assert serve.stream(args) == 0

    def test_serve_main_argv_hands_back_engine(self, monkeypatch):
        """serve.main runs in-process from an argv and hands back the
        engine it drove, whose segment program can be inspected."""
        from repro.launch import serve
        monkeypatch.setattr(serve, "use_compile_cache", lambda: "")
        engines = []
        assert serve.main(
            ["--mode", "stream", "--arch", "yi-34b", "--smoke",
             "--backend", "linear", "--slots", "2", "--segment-len", "4",
             "--n-requests", "3", "--prompt-len", "8", "--gen-len", "8",
             "--temperature", "0"], engines) == 0
        (engine,) = engines
        comps = engine.completions()
        assert len(comps) == 3 and {c.status for c in comps} == {"ok"}
        text = engine.segment_program_text()
        assert "HloModule" in text
        # "auto" picks the jnp reference off TPU: no Pallas kernel
        assert "tpu_custom_call" not in text


class TestMixedSpeculativePlain:
    """Mixing speculative and plain requests in ONE slot batch never
    changes anyone's tokens: plain slots advance in slot-masked segments
    with speculative slots frozen, speculative slots advance in verify
    rounds with plain slots frozen (extends the bit-identity harness)."""

    def _workload(self, cfg, n=6):
        rng = np.random.default_rng(7)
        prompts = [rng.integers(0, cfg.vocab_size, size=8,
                                dtype=np.int64).astype(np.int32)
                   for _ in range(n)]
        gens = [12, 7, 15, 5, 10, 9][:n]
        return prompts, gens

    @pytest.mark.parametrize("backend", ["linear", "softmax"])
    def test_mixed_equals_homogeneous(self, key, backend):
        from repro.serving import ModelDraft

        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend(backend),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, gens = self._workload(cfg)
        eng = DecodeEngine(
            params, cfg, n_slots=3, segment_len=4, max_len=64,
            draft=ModelDraft(params, cfg, n_slots=3, max_len=64))

        def run(ks):
            eng.reset()
            for p, g, k in zip(prompts, gens, ks):
                eng.submit(p, g, speculate_k=k)
            return eng.run("continuous")

        all_plain = run([0] * len(prompts))
        all_spec = run([3] * len(prompts))
        mixed = run([0, 3, 0, 3, 0, 3])
        segs, rounds = eng.stats.segments, eng.stats.spec_rounds

        for a, b, c in zip(all_plain, all_spec, mixed):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            np.testing.assert_array_equal(a.tokens, c.tokens)
        # the mixed run actually interleaved both phase kinds
        assert segs > 0 and rounds > 0

    def test_mixed_with_arrivals_and_eos(self, key):
        """Admission churn + EOS stops while the batch mixes kinds."""
        from repro.serving import NgramDraft

        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, gens = self._workload(cfg)
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64)
        eng.reset()
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        plain = eng.run("continuous")
        eos_id = next(int(t) for c in plain for t in c.tokens[1:-1])

        def run(draft, ks, arrivals):
            e = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                             max_len=64, eos_id=eos_id, draft=draft)
            for p, g, k, t in zip(prompts, gens, ks, arrivals):
                e.submit(p, g, speculate_k=k, arrival=t)
            return e.run("continuous")

        refs = run(None, [0] * 6, [0.0] * 6)
        mixed = run(NgramDraft(), [0, 2, 0, 4, 2, 0],
                    [0.0, 0.0, 3.0, 5.0, 9.0, 11.0])
        for a, b in zip(refs, mixed):
            np.testing.assert_array_equal(a.tokens, b.tokens)
            assert a.finish_reason == b.finish_reason


class TestBatchedAdmission:
    """The batched + chunked admission path (ISSUE 4): bucket-padded
    varlen prefill waves and chunked long-prompt ingestion must leave
    every request's tokens exactly as the per-request prefill-on-admit
    path produced them, admission order must be deterministic, and the
    engine must actually interleave long-prompt chunks with decode."""

    def _mixed_workload(self, cfg, n=8, seed=3):
        """Mixed prompt lengths incl. prompts longer than prefill_chunk
        (chunked ingestion) — lens >= 2 (see lm.prefill_varlen caveat)."""
        rng = np.random.default_rng(seed)
        p_lens = [6, 8, 21, 5, 8, 40, 7, 8][:n]
        prompts = [rng.integers(0, cfg.vocab_size, size=pl,
                                dtype=np.int64).astype(np.int32)
                   for pl in p_lens]
        gens = [5, 12, 3, 9, 6, 7, 4, 8][:n]
        return prompts, gens

    def _engine(self, params, cfg, admission, **kw):
        return DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                            max_len=96, admission=admission,
                            prefill_chunk=8, **kw)

    @pytest.mark.parametrize("backend", ["linear", "gated_linear",
                                         "softmax"])
    def test_batched_equals_per_request(self, key, backend):
        """Chunked+batched admission is token-identical to the
        per-request path on all three backends (fp32: the chunked
        continuation reassociates, argmax margins dominate)."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend(backend),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, gens = self._mixed_workload(cfg)
        outs = {}
        for adm in ("per_request", "batched"):
            eng = self._engine(params, cfg, adm)
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            outs[adm] = eng.run("continuous")
            if adm == "batched":
                st = eng.stats
                assert st.admission_batches > 0
                assert st.ingest_chunks > 0        # 21/40 > chunk of 8
                assert st.interleave_ratio > 0.0   # decode stayed live
                assert st.prefills == len(prompts)
        for a, b in zip(outs["per_request"], outs["batched"]):
            assert a.uid == b.uid
            np.testing.assert_array_equal(a.tokens, b.tokens)
            assert a.finish_reason == b.finish_reason

    def test_uniform_prompts_bit_identical_bf16(self, key):
        """Bucket-width prompts (no row padding) keep the engine's
        run-alone bit-identity contract even in bf16 — the batched wave
        is bitwise the per-request prefill."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, gens = _make_workload(cfg)   # all length 8 == bucket
        refs = [_standalone(params, cfg, p, g, 64)
                for p, g in zip(prompts, gens)]
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=64, admission="batched")
        for p, g in zip(prompts, gens):
            eng.submit(p, g)
        for c, ref in zip(eng.run("continuous"), refs):
            np.testing.assert_array_equal(c.tokens, np.asarray(ref))

    def test_admission_order_deterministic(self, key):
        """Same submissions → same slot assignment, same admitted
        steps, same tokens, run after run (the wave fill is queue-order
        over free slots in index order)."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            dtype="float32")
        params = lm.init_params(key, cfg)
        prompts, gens = self._mixed_workload(cfg)
        eng = self._engine(params, cfg, "batched")

        def go():
            eng.reset()
            for i, (p, g) in enumerate(zip(prompts, gens)):
                eng.submit(p, g, arrival=2.0 * (i // 3))
            return eng.run("continuous")

        a, b = go(), go()
        for x, y in zip(a, b):
            assert x.uid == y.uid
            assert x.admitted_step == y.admitted_step
            assert x.finished_step == y.finished_step
            np.testing.assert_array_equal(x.tokens, y.tokens)
        # equal-arrival requests are admitted in uid order
        for x, y in zip(a, a[1:]):
            if x.admitted_step == y.admitted_step:
                assert x.uid < y.uid

    @pytest.mark.parametrize("backend",
                             ["linear", "gated_linear", "softmax"])
    def test_length_one_prompt_bit_identical(self, key, backend):
        """A 1-token prompt mixed into a wider wave is carved out to
        the exact-shape batch-1 prefill (the lm.prefill_varlen gemv
        caveat), so batched admission stays bit-identical to
        per-request even in bf16 — on every backend (the softmax KV
        writes and the gated decay path mask the same way)."""
        cfg = get_smoke_config("yi-34b").with_backend(backend)
        params = lm.init_params(key, cfg)
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, cfg.vocab_size, size=pl,
                                dtype=np.int64).astype(np.int32)
                   for pl in (1, 8, 8, 1)]
        gens = [6, 9, 4, 7]
        outs = {}
        for adm in ("per_request", "batched"):
            eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                               max_len=64, admission=adm)
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            outs[adm] = eng.run("continuous")
        for a, b in zip(outs["per_request"], outs["batched"]):
            np.testing.assert_array_equal(a.tokens, b.tokens)

    def test_instant_completions_batched(self, key):
        """gen_len=1 requests complete at admission without consuming
        the slot's turn — batched path, mirroring the per-request
        behaviour the scheduler tests pin."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        params = lm.init_params(key, cfg)
        prompts, _ = _make_workload(cfg, n=4)
        eng = DecodeEngine(params, cfg, n_slots=1, segment_len=4,
                           max_len=64, admission="batched")
        for p, g in zip(prompts, [1, 1, 1, 5]):
            eng.submit(p, g)
        comps = eng.run("continuous")
        assert len(comps) == 4
        assert comps[3].admitted_step == 0

    def test_auto_falls_back_for_non_attention_patterns(self, key):
        """Layer patterns without varlen prefill masking (mamba/rwkv/
        cross) resolve admission='auto' to the per-request path, and
        forcing 'batched' on them is rejected."""
        cfg = dataclasses.replace(get_smoke_config("yi-34b"),
                                  layer_pattern=("attn", "mamba"),
                                  ssm=SSMConfig())
        assert not lm.supports_varlen_prefill(cfg)
        params = lm.init_params(key, cfg)
        eng = DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                           max_len=32)
        assert eng.admission == "per_request"
        with pytest.raises(AssertionError, match="attention-only"):
            DecodeEngine(params, cfg, n_slots=2, segment_len=4,
                         max_len=32, admission="batched")


class TestBatchedRewind:
    """Partial-acceptance speculative rewind = ONE decode_window_varlen
    dispatch per round, however many slots rewind."""

    def test_one_dispatch_per_rewinding_round(self, key):
        from repro.serving import ReplayDraft

        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            dtype="float32")
        params = lm.init_params(key, cfg)
        rng = np.random.default_rng(11)
        prompts = [rng.integers(0, cfg.vocab_size, size=8,
                                dtype=np.int64).astype(np.int32)
                   for _ in range(3)]
        gens = [10, 10, 10]
        # plain reference run for tokens + bit-identity
        eng0 = DecodeEngine(params, cfg, n_slots=3, segment_len=4,
                            max_len=64)
        for p, g in zip(prompts, gens):
            eng0.submit(p, g)
        plain = eng0.run("continuous")

        # a draft that is right for 2 tokens then wrong: every round is
        # a partial acceptance on EVERY slot — the old path would pay
        # 3 dispatches per slot per round
        class HalfWrongDraft(ReplayDraft):
            def propose(self, tok, pos, mask, k):
                out = super().propose(tok, pos, mask, k)
                out[:, 2:] = 0   # sabotage tails (token 0 ~never greedy)
                return out

        draft = HalfWrongDraft({ReplayDraft.key(p): c.tokens
                                for p, c in zip(prompts, plain)})
        eng = DecodeEngine(params, cfg, n_slots=3, segment_len=4,
                           max_len=64, draft=draft)
        for p, g in zip(prompts, gens):
            eng.submit(p, g, speculate_k=4)
        comps = eng.run("continuous")
        st = eng.stats
        for a, b in zip(plain, comps):
            np.testing.assert_array_equal(a.tokens, b.tokens)
        assert st.spec_rewind_rounds > 0
        # the batching claim: one varlen dispatch per rewinding round,
        # with MORE rewound slots than dispatches (multi-slot rounds)
        assert st.spec_rewind_dispatches == st.spec_rewind_rounds
        assert st.spec_rewinds > st.spec_rewind_dispatches


class TestDecodeNumerics:
    """The decode-path correctness sweep."""

    def test_safe_denom_sign_preserving(self):
        d = jnp.asarray([2.0, 1e-9, 0.0, -1e-9, -2.0])
        out = np.asarray(safe_denom(d, 1e-6))
        np.testing.assert_allclose(
            out, [2.0, 1e-6, 1e-6, -1e-6, -2.0])
        assert bool(np.all(np.abs(out) >= 1e-6))

    def test_identity_feature_map_normalized_decode_finite(self, key):
        """feature_map='identity' q·z can be ~0 or negative; the old
        additive eps blew the normalised output up. The clamp keeps the
        whole generation finite."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            feature_map="identity", linear_normalize=True)
        params = lm.init_params(key, cfg)
        state = lm.init_decode_state(cfg, batch=2, max_len=32)
        toks, st = lm.generate(params, state, jnp.zeros((2,), jnp.int32),
                               0, 16, cfg, RULES)
        assert bool(jnp.all((toks >= 0) & (toks < cfg.vocab_size)))
        for leaf in jax.tree.leaves(st):
            assert bool(jnp.all(jnp.isfinite(
                leaf.astype(jnp.float32))))

    def test_prefill_state_z_guarded(self, key):
        """The prefill normaliser is only computed when it is used, and
        equals the plain key sum when it is."""
        cfg = dataclasses.replace(
            get_smoke_config("yi-34b").with_backend("linear"),
            linear_normalize=False)
        params = lm.init_params(key, cfg)
        prompt = jax.random.randint(key, (2, 6), 0, cfg.vocab_size)
        _, states = lm.prefill(params, prompt, cfg, RULES)
        assert states["stack"][0].z is None

    def test_fused_fallback_warns_off_tpu(self, monkeypatch):
        """decode_kernel='fused' on a backend that cannot lower the TPU
        Pallas kernels raises a ValueError naming the platform — no
        silent switch to the reference path."""
        cfg = get_smoke_config("yi-34b").with_backend("linear")
        cfg = dataclasses.replace(cfg, decode_kernel="fused")
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(ValueError, match="'gpu'"):
            A._use_fused_decode(cfg)
        # "auto" and "reference" never raise: auto picks the reference
        # recurrence off TPU
        for kernel in ("auto", "reference"):
            assert A._use_fused_decode(
                dataclasses.replace(cfg, decode_kernel=kernel)) is False
        # cpu + tpu still take the kernel path
        monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
        assert A._use_fused_decode(cfg) is True


_FLEET_GROUPS = {}


def _fleet_groups(backends):
    """(params, cfg) per fleet backend, cached across the class — the
    demo configs share vocab/d_model so one workload feeds all groups."""
    from repro.serving import fleet_demo_config
    for i, name in enumerate(backends):
        if name not in _FLEET_GROUPS:
            cfg = fleet_demo_config(name)
            _FLEET_GROUPS[name] = (
                lm.init_params(jax.random.PRNGKey(i), cfg), cfg)
    return {name: _FLEET_GROUPS[name] for name in backends}


class TestFleet:
    """Tentpole acceptance: a heterogeneous fleet — linear + softmax +
    mamba2 slot groups behind ONE admission queue — yields tokens BIT-
    IDENTICAL to three homogeneous engines fed the same per-group
    submission sequences: in steady state, under priority preemption,
    and under deadline eviction. Each group compiles exactly one decode-
    segment program (the deterministic dispatch-count CI gates)."""

    BACKENDS = ("linear", "softmax", "mamba2")

    def _jobs(self, groups, n=9, seed=3, gens=(6, 9, 4), extra=None):
        """Round-robin jobs across backends; ``extra[i]`` merges into
        job i's submit kwargs."""
        rng = np.random.default_rng(seed)
        names = list(groups)
        jobs = []
        for i in range(n):
            name = names[i % len(names)]
            vocab = groups[name][1].vocab_size
            prompt = rng.integers(0, vocab, size=6,
                                  dtype=np.int64).astype(np.int32)
            kw = dict(arrival=float(i) * 0.5)
            kw.update((extra or {}).get(i, {}))
            jobs.append((name, prompt, gens[i % len(gens)], kw))
        return jobs

    def _run_fleet_and_homogeneous(self, groups, jobs, n_slots=2,
                                   **fleet_kw):
        from repro.serving import FleetEngine
        fleet = FleetEngine(groups, n_slots=n_slots, segment_len=4,
                            max_len=48, **fleet_kw)
        for name, prompt, gen, kw in jobs:
            fleet.submit(prompt, gen, backend=name, **kw)
        fleet_comps = fleet.run("continuous")
        assert len(fleet_comps) == len(jobs)

        homogeneous = {}
        for name in groups:
            params, cfg = groups[name]
            eng = DecodeEngine(params, cfg, n_slots=n_slots,
                               segment_len=4, max_len=48)
            for jname, prompt, gen, kw in jobs:
                if jname == name:
                    eng.submit(prompt, gen, **kw)
            homogeneous[name] = (eng, eng.run("continuous"))

        # fleet uids are submission-ordered, so per-group order matches
        per_group = {name: [c for (jname, *_), c in zip(jobs,
                                                        fleet_comps)
                            if jname == name] for name in groups}
        for name in groups:
            solo = homogeneous[name][1]
            assert len(solo) == len(per_group[name])
            for cf, ch in zip(per_group[name], solo):
                assert cf.status == ch.status, (name, cf, ch)
                np.testing.assert_array_equal(cf.tokens, ch.tokens)
        return fleet, homogeneous

    def test_mixed_equals_homogeneous(self):
        groups = _fleet_groups(self.BACKENDS)
        jobs = self._jobs(groups)
        fleet, _ = self._run_fleet_and_homogeneous(groups, jobs)
        assert all(c.status == "ok" for c in fleet.completions())
        # one compiled decode-segment program per backend — serving a
        # mix never cross-compiles another family's program
        assert fleet.compiled_segment_programs() == {
            name: 1 for name in self.BACKENDS}
        stats = fleet.stats()
        assert stats["fleet_shed"] == 0
        assert not stats["groups"]["mamba2"]["fixed_size_state"] \
            is stats["groups"]["softmax"]["fixed_size_state"]

    def test_mixed_under_preemption(self):
        """A saturated pool in every group + a late high-priority
        arrival per group: the preempt/resume dance happens inside each
        group exactly as it would homogeneously."""
        groups = _fleet_groups(self.BACKENDS)
        # jobs 0-5 saturate (2 slots/group); 6-8 arrive late at high
        # priority, one per group
        extra = {i: dict(arrival=8.0, priority=5) for i in (6, 7, 8)}
        jobs = self._jobs(groups, n=9, gens=(12, 12, 8), extra=extra)
        fleet, homogeneous = self._run_fleet_and_homogeneous(groups,
                                                             jobs)
        for name, (eng, _) in homogeneous.items():
            grp = fleet.groups[name]
            assert grp.stats.preemptions == eng.stats.preemptions
            assert grp.stats.resumes == grp.stats.preemptions
        assert sum(g.stats.preemptions
                   for g in fleet.groups.values()) >= 1

    def test_mixed_under_deadline_eviction(self):
        """Per-group single slot: job 0 of each group hogs it, jobs 3-5
        carry queue deadlines that trip — same completions (status
        'deadline', same partial tokens) as the homogeneous engines."""
        groups = _fleet_groups(self.BACKENDS)
        extra = {i: dict(arrival=0.0, deadline_s=4.0) for i in (3, 4, 5)}
        jobs = self._jobs(groups, n=6, gens=(20, 20, 20), extra=extra)
        for i in range(3):
            jobs[i][3]["arrival"] = 0.0
        fleet, _ = self._run_fleet_and_homogeneous(groups, jobs,
                                                   n_slots=1)
        statuses = [c.status for c in fleet.completions()]
        assert statuses[:3] == ["ok"] * 3
        assert statuses[3:] == ["deadline"] * 3
        assert sum(g.stats.deadline_evictions
                   for g in fleet.groups.values()) == 3

    def test_fleet_queue_cross_group_shed(self):
        """The FLEET-level bounded queue: under evict_lowest a high-
        priority arrival in one group evicts the lowest-priority queued
        request from ANOTHER group; under reject_new the arrival itself
        is shed into its own group's completions."""
        from repro.serving import FleetEngine
        groups = _fleet_groups(self.BACKENDS)
        jobs = self._jobs(groups, n=2)          # linear + softmax
        for policy, shed_idx in (("evict_lowest", 1), ("reject_new", 2)):
            fleet = FleetEngine(groups, n_slots=1, segment_len=4,
                                max_len=48, max_queue=2,
                                shed_policy=policy)
            for name, prompt, gen, kw in jobs:
                fleet.submit(prompt, gen, backend=name, **kw)
            u = fleet.submit(jobs[0][1], 4, backend="mamba2",
                             priority=3, arrival=1.0)
            comps = fleet.run("continuous")
            assert fleet.fleet_shed == 1
            assert [c.status for c in comps].count("shed") == 1
            assert comps[shed_idx].status == "shed"
            if policy == "evict_lowest":
                # the high-priority arrival displaced a queued request
                # from a DIFFERENT group and itself ran to completion
                assert fleet.backend_of(u) == "mamba2"
                assert comps[2].status == "ok"

    def test_unknown_backend_rejected_atomically(self):
        from repro.serving import FleetEngine
        groups = _fleet_groups(("linear",))
        fleet = FleetEngine(groups, n_slots=1, segment_len=4,
                            max_len=48)
        with pytest.raises(KeyError, match="unknown backend"):
            fleet.submit(np.array([1, 2, 3], np.int32), 4,
                         backend="softmax")
        assert fleet._next_uid == 0 and not fleet.has_work()
