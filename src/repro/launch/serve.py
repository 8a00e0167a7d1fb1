"""Serving driver — the paper's deployment story.

Three modes:

* ``generate`` — autoregressive generation with one static batch of
  requests: prefill once, then O(k²)-per-token decode under the linear
  backends (no KV cache; the 500k-context state is the same size as the
  1-token state). ``--backend softmax`` serves the KV-cache baseline.

  The generation loop is FUSED: the whole decode phase is one
  ``lm.generate`` dispatch (a ``lax.scan`` over decode steps with
  greedy/temperature sampling folded in), and inside each step the
  linear-family state update runs through the fused recurrent Pallas
  kernels (``kernels/fused_recurrent``) — state resident in VMEM,
  updated in place in HBM via input/output aliasing. Per-token cost is
  therefore FLOPs-dominated instead of dispatch/HBM-traffic-dominated:
  the pre-fusion driver paid one jitted dispatch + a full decode-state
  HBM round-trip per token.

* ``stream`` — continuous batching under a synthetic Poisson request
  stream (the paper's §2.2 "extreme query loads" as a scheduling
  problem): requests with exponential inter-arrival times and a skewed
  generation-length mix are driven through the fixed-slot
  :class:`repro.serving.DecodeEngine`. Freed slots are refilled between
  scan segments by bucket-padded BATCHED varlen prefill (one dispatch
  per admission wave, O(log prefill_chunk) compiled programs total);
  prompts longer than ``--prefill-chunk`` are ingested in masked
  varlen-window chunks interleaved with decode segments, so neither a
  long straggler nor a long prompt idles the rest of the batch
  (``--admission per_request`` selects the PR-2 host-blocking
  prefill-on-admit baseline). Reports aggregate tokens/s, slot
  utilization and admission stats (batch sizes, jit misses,
  chunk-interleave ratio). ``--backends linear,softmax,mamba2``
  serves a HETEROGENEOUS FLEET instead: one slot group per backend
  family behind a single admission queue
  (:class:`repro.serving.FleetEngine`), requests round-robined across
  groups, one compiled segment program per backend.

* ``spec`` — speculative lookahead decoding through the slot engine: a
  draft provider proposes K tokens per round and ONE ``lm.decode_window``
  launch verifies the whole window per slot (the paper's fixed-size
  state makes verify/rewind an O(k²) copy instead of a KV-cache replay).
  Greedy outputs are exactly the plain-greedy tokens — the mode runs the
  same workload plain first and asserts token equality, then reports the
  acceptance rate and the speculative/plain tokens/s ratio.
  ``--draft ngram`` (default) drafts by prompt-lookup suffix matching at
  zero device cost; ``--draft model`` drafts with a second (here:
  same-config) LM through its own fixed-size slot states.

* ``retrieve`` — the §2.2 mass-query scenario: encode documents into the
  fixed-size DocumentStore once, then answer query streams at O(k²) each.

* ``lookup`` — the memory-serving engine
  (:class:`repro.serving.LookupEngine`): documents are GRU-encoded ONCE
  in varlen batched ingest waves, pinned resident as one stacked
  (N, k, k) store, and a query storm against arbitrary different
  memories is served in bucket-padded waves — each wave ONE
  ``mass_lookup_indexed`` kernel dispatch. ``--lookup-backend softmax``
  serves the honest baseline (full hidden states resident, per-query
  cost grows with --doc-len); ``--load PATH`` pins a persisted
  DocumentStore instead of synthesising documents. Reuses the
  bounded-queue knobs (``--max-queue``/``--shed-policy``).

  PYTHONPATH=src python -m repro.launch.serve --mode lookup \
      --n-docs 256 --doc-len 64 --n-queries 2048 --lookup-backend linear

  PYTHONPATH=src python -m repro.launch.serve --arch yi-34b --smoke \
      --backend linear --prompt-len 64 --gen-len 32 --batch 4
  PYTHONPATH=src python -m repro.launch.serve --mode stream --smoke \
      --backend linear --slots 4 --n-requests 16 --arrival-rate 0.5
  PYTHONPATH=src python -m repro.launch.serve --mode stream \
      --backends linear,softmax,mamba2 --slots 2 --n-requests 9
  PYTHONPATH=src python -m repro.launch.serve --mode spec --smoke \
      --backend linear --slots 4 --n-requests 8 --speculate-k 6
"""

from __future__ import annotations

import argparse
import signal
import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import lm
from repro.sharding import Rules


def generate(args) -> int:
    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.backend:
        cfg = cfg.with_backend(args.backend)
    rules = Rules.null()
    # independent PRNG streams — params/prompt/memory/sampling must not
    # share a key (identical draws correlate weights with data)
    root = jax.random.PRNGKey(args.seed)
    k_params, k_prompt, k_memory, k_sample = (
        jax.random.fold_in(root, i) for i in range(4))
    params = lm.init_params(k_params, cfg)

    b, t_p, t_g = args.batch, args.prompt_len, args.gen_len
    prompt = jax.random.randint(k_prompt, (b, t_p), 0, cfg.vocab_size)
    memory = (jax.random.normal(k_memory,
                                (b, cfg.n_img_tokens, cfg.d_model),
                                jnp.bfloat16)
              if cfg.n_img_tokens else None)

    prefill = jax.jit(lambda p, toks: lm.prefill(p, toks, cfg, rules,
                                                 memory=memory))
    # ONE dispatch for the whole generation: scan + fused kernels inside
    gen = jax.jit(lambda p, st, tok, key: lm.generate(
        p, st, tok, t_p, t_g - 1, cfg, rules,
        temperature=args.temperature, key=key))

    t0 = time.perf_counter()
    logits, states = prefill(params, prompt)
    states = lm.pad_decode_state(states, cfg, max_len=t_p + t_g)
    jax.block_until_ready(logits)
    t_prefill = time.perf_counter() - t0

    k_first, k_rest = jax.random.split(k_sample)
    tok0 = lm.sample_token(logits, args.temperature, k_first)
    jax.block_until_ready(gen(params, states, tok0, k_rest)[0])  # compile
    t0 = time.perf_counter()
    toks, states = gen(params, states, tok0, k_rest)
    jax.block_until_ready(toks)
    t_decode = time.perf_counter() - t0
    out = jnp.concatenate([tok0[:, None], toks], axis=1)
    assert out.shape == (b, t_g)

    state_bytes = sum(x.nbytes for x in jax.tree.leaves(states))
    n_dec = max(t_g - 1, 1)
    print(f"arch={cfg.name} backend={cfg.attention_backend} "
          f"decode_kernel={cfg.decode_kernel}")
    print(f"prefill {t_p} toks x{b}: {t_prefill*1e3:.0f} ms")
    print(f"decode  {t_g} toks x{b}: {t_decode/n_dec*1e3:.2f} ms/tok "
          f"({b*n_dec/t_decode:.0f} tok/s, single dispatch)")
    print(f"decode state: {state_bytes/2**20:.1f} MiB "
          f"({'O(1) in context' if cfg.fixed_state_decode else 'KV cache'})")
    return 0


def make_request_mix(rng: np.random.Generator, n_requests: int,
                     prompt_len: int, gen_len: int, vocab_size: int,
                     arrival_rate: float):
    """Synthetic workload: Poisson arrivals (exponential inter-arrival
    times, ``arrival_rate`` requests per decode step; 0 = all at once)
    and a skewed generation-length mix — most requests are short,
    every 4th runs ``gen_len`` tokens (the straggler pattern continuous
    batching exists for)."""
    t = 0.0
    out = []
    for i in range(n_requests):
        if arrival_rate > 0:
            t += rng.exponential(1.0 / arrival_rate)
        prompt = rng.integers(0, vocab_size, size=prompt_len,
                              dtype=np.int64).astype(np.int32)
        g = gen_len if i % 4 == 0 else max(1, gen_len // 8)
        out.append((prompt, g, t))
    return out


class _Drainer:
    """SIGINT/SIGTERM → finish the in-flight segment, drain, exit 0.

    The handler only sets a flag; the serving loop checks it between
    scheduler events, so a signal never tears a segment (or a
    checkpoint write) in half. A second signal falls back to the
    default handler — the escape hatch if draining itself wedges."""

    def __init__(self):
        self.requested = False
        self._prev = {}

    def __enter__(self):
        for sig in (signal.SIGINT, signal.SIGTERM):
            try:
                self._prev[sig] = signal.signal(sig, self._on_signal)
            except ValueError:          # non-main thread (tests)
                pass
        return self

    def __exit__(self, *exc):
        for sig, prev in self._prev.items():
            signal.signal(sig, prev)

    def _on_signal(self, sig, frame):
        self.requested = True
        signal.signal(sig, self._prev.get(sig, signal.SIG_DFL))


def stream_fleet(args) -> int:
    """Heterogeneous fleet streaming: the Poisson workload round-robins
    across N backend slot groups behind ONE admission queue
    (``--backends linear,softmax,mamba2``; smoke-scale fleet demo
    configs — they share the vocab, so one request mix feeds every
    architecture family at once). ``--replicas N`` runs every group as
    N replicas behind the same queue (heartbeat + breaker failover)."""
    from repro.serving import FleetEngine, fleet_demo_config

    names = [b.strip() for b in args.backends.split(",") if b.strip()]
    root = jax.random.PRNGKey(args.seed)
    groups = {}
    for i, name in enumerate(names):
        cfg = fleet_demo_config(name)
        groups[name] = (lm.init_params(jax.random.fold_in(root, i), cfg),
                        cfg)
    max_len = args.prompt_len + args.gen_len + args.segment_len
    fleet = FleetEngine(
        groups, n_slots=args.slots, segment_len=args.segment_len,
        max_len=max_len, temperature=args.temperature, seed=args.seed,
        max_queue=getattr(args, "max_queue", None),
        shed_policy=getattr(args, "shed_policy", "reject_new"),
        replicas=getattr(args, "replicas", 1),
        journal_dir=getattr(args, "journal_dir", None),
        checkpoint_dir=getattr(args, "checkpoint_dir", None),
        prefix_cache={"off": None, "auto": "auto", "on": True}[
            getattr(args, "prefix_cache", "off")],
        cache_bytes=getattr(args, "cache_bytes", 64 << 20))
    vocab = min(cfg.vocab_size for _, cfg in groups.values())
    rng = np.random.default_rng(args.seed)
    requests = make_request_mix(rng, args.n_requests, args.prompt_len,
                                args.gen_len, vocab, args.arrival_rate)
    routed = {}
    for i, (prompt, g, arrival) in enumerate(requests):
        uid = fleet.submit(prompt, g, backend=names[i % len(names)],
                           arrival=arrival)
        routed[uid] = names[i % len(names)]

    t0 = time.perf_counter()
    completions = fleet.run("continuous")
    dt = time.perf_counter() - t0

    total = sum(len(c.tokens) for c in completions)
    print(f"fleet backends={','.join(names)} slots={args.slots}/group "
          f"segment={args.segment_len}")
    print(f"stream: {len(completions)} requests, {total} tokens in "
          f"{dt:.2f} s ({total/dt:.0f} tok/s incl. compile)")
    stats = fleet.stats()
    for name in names:
        g = stats["groups"][name]
        toks = sum(len(c.tokens) for c in completions
                   if routed.get(c.uid) == name)
        print(f"  {name}: {toks} toks, backend={g['backend']} "
              f"fixed_state={g['fixed_size_state']} "
              f"state/slot={g['state_bytes_per_slot']/1024:.1f} KiB, "
              f"{g['compiled_segment_programs']} segment program(s), "
              f"slot util {g['stats']['slot_utilization']:.2f}")
    programs = fleet.compiled_segment_programs()
    print(f"compiled segment programs: {programs} "
          f"(one per backend: {all(v == 1 for v in programs.values())})")
    if getattr(args, "replicas", 1) > 1:
        print(f"replicas={args.replicas}/group "
              f"failovers={stats['failovers']} "
              f"readmitted={stats['readmitted']}")
    assert len(completions) == args.n_requests
    return 0


class _Latency:
    """Time to first token and inter-token gaps, from
    ``DecodeEngine.progress()`` after each step, on the engine's clock
    (time.perf_counter readings taken where tokens reached the host)."""

    def __init__(self):
        self.ttft: List[float] = []      # seconds, one per request
        self.gaps: List[float] = []      # seconds per token, weighted by
        self.counts: List[int] = []      # the tokens one read brought
        self._last: Dict[int, tuple] = {}  # uid -> (tokens, t_tokens)

    def observe(self, progress) -> None:
        for uid, rp in progress.requests.items():
            n, t = self._last.get(uid, (0, None))
            if rp.tokens <= n or rp.t_tokens is None:
                continue
            if n == 0:
                if rp.t_first_token is None or rp.t_submit is None:
                    # restored by --recover: its earlier times are gone
                    self._last[uid] = (rp.tokens, rp.t_tokens)
                    continue
                self.ttft.append(rp.t_first_token - rp.t_submit)
                n, t = 1, rp.t_first_token
            if rp.tokens > n:
                self.gaps.append((rp.t_tokens - t) / (rp.tokens - n))
                self.counts.append(rp.tokens - n)
            self._last[uid] = (rp.tokens, rp.t_tokens)
        for c in progress.done:
            self._last.pop(c.uid, None)

    def report(self) -> str:
        if not self.ttft:
            return "no request was served"
        ttft = np.asarray(self.ttft) * 1e3
        line = (f"TTFT p50 {np.percentile(ttft, 50):.1f} ms, "
                f"p99 {np.percentile(ttft, 99):.1f} ms")
        if self.gaps:
            itl = np.repeat(np.asarray(self.gaps) * 1e3, self.counts)
            line += f"; inter-token p99 {np.percentile(itl, 99):.2f} ms"
        return line + " (engine clock, compiles included)"


def stream(args, engines: Optional[list] = None) -> int:
    """Continuous batching under a synthetic Poisson request stream.
    ``engines``, when given, receives the engine this run drives."""
    from repro.serving import DecodeEngine

    if getattr(args, "replicas", 1) > 1 and not getattr(
            args, "backends", None):
        args.backends = args.backend or "linear"
    if getattr(args, "backends", None):
        return stream_fleet(args)

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.backend:
        cfg = cfg.with_backend(args.backend)
    rules = Rules.null()
    root = jax.random.PRNGKey(args.seed)
    params = lm.init_params(jax.random.fold_in(root, 0), cfg)

    from repro.serving import FaultInjector, InjectedCrash

    crash_at = getattr(args, "crash_at_event", None)
    injector = (FaultInjector(crash=(crash_at,))
                if crash_at is not None else None)
    max_len = args.prompt_len + args.gen_len + args.segment_len
    engine = DecodeEngine(
        params, cfg, rules, n_slots=args.slots,
        segment_len=args.segment_len, max_len=max_len,
        temperature=args.temperature, seed=args.seed,
        admission=getattr(args, "admission", "auto"),
        prefill_chunk=getattr(args, "prefill_chunk", 64),
        max_queue=getattr(args, "max_queue", None),
        shed_policy=getattr(args, "shed_policy", "reject_new"),
        degrade_threshold=getattr(args, "degrade_threshold", None),
        journal=getattr(args, "journal", None),
        checkpoint_dir=getattr(args, "checkpoint_dir", None),
        checkpoint_every=getattr(args, "checkpoint_every", 0),
        prefix_cache={"off": None, "auto": "auto", "on": True}[
            getattr(args, "prefix_cache", "off")],
        cache_bytes=getattr(args, "cache_bytes", 64 << 20),
        injector=injector)
    if engines is not None:
        engines.append(engine)

    if getattr(args, "recover", False):
        if engine.journal is None and engine._ckpt_mgr is None:
            raise SystemExit(
                "--recover needs --journal and/or --checkpoint-dir")
        n_journaled = len(engine.journal.unacked_submits()) \
            if engine.journal is not None else 0
        engine.recover_in_place()
        print(f"recover: {n_journaled} unacked request(s) replayed "
              f"from the journal")
    else:
        rng = np.random.default_rng(args.seed)
        requests = make_request_mix(rng, args.n_requests,
                                    args.prompt_len, args.gen_len,
                                    cfg.vocab_size, args.arrival_rate)
        fork = getattr(args, "fork", 1)
        for prompt, g, arrival in requests:
            engine.submit(prompt, g, arrival=arrival, fork=fork)

    t0 = time.perf_counter()
    lat = _Latency()
    with _Drainer() as drain:
        try:
            while engine.has_work() and not drain.requested:
                engine.step("continuous")
                lat.observe(engine.progress())
        except InjectedCrash as e:
            # simulated hard kill: NO drain, NO final checkpoint — the
            # journal + last periodic checkpoint are all recovery gets
            print(f"crash: injected at event {e.event_idx} "
                  f"(journal/checkpoint left as-is; restart with "
                  f"--recover)")
            return 3
    completions = engine.completions()
    dt = time.perf_counter() - t0

    if drain.requested:
        in_flight = sum(1 for s in engine._slot_req if s is not None) \
            + len(engine._queue) + len(engine._suspended)
        if engine._ckpt_mgr is not None:
            engine.save_checkpoint()
        print(f"graceful shutdown: segment finished, {in_flight} "
              f"in-flight request(s) "
              + ("journaled + checkpointed for --recover"
                 if engine.journal is not None
                 or engine._ckpt_mgr is not None else "dropped"))
        if getattr(args, "stats_json", None):
            with open(args.stats_json, "w") as f:
                f.write(engine.stats.to_json())
            print(f"stats written to {args.stats_json}")
        return 0

    if engine.journal is not None:
        acks = engine.journal.acked()
        uids = {c.uid for c in completions}
        lost = sorted(uids - set(acks))
        zero_loss = not lost and len(acks) == len(uids)
        print(f"durability: acks={len(acks)} completions={len(uids)} "
              f"lost={len(lost)} "
              f"zero_loss={'PASS' if zero_loss else 'FAIL'}")

    total = sum(len(c.tokens) for c in completions)
    statuses = {}
    for c in completions:
        statuses[c.status] = statuses.get(c.status, 0) + 1
    print(f"arch={cfg.name} backend={cfg.attention_backend} "
          f"slots={args.slots} segment={args.segment_len}")
    print(f"stream: {len(completions)} requests, {total} tokens in "
          f"{dt:.2f} s ({total/dt:.0f} tok/s incl. compile)")
    st = engine.stats
    print(f"slot utilization {st.slot_utilization:.2f} over "
          f"{st.segments} segments")
    print(lat.report())
    print("status: " + " ".join(
        f"{k}={v}" for k, v in sorted(statuses.items())))
    if st.shed or st.preemptions or st.quarantined or st.degrade_transitions:
        print(f"lifecycle: shed={st.shed} preempt={st.preemptions} "
              f"resume={st.resumes} quarantine={st.quarantined} "
              f"retries={st.retries} failed={st.failed} "
              f"degrade_flips={st.degrade_transitions}")
    print(f"admission={engine.admission} chunk={engine.prefill_chunk}: "
          f"{st.prefills} prompts in {st.admission_batches} batched "
          f"waves (mean batch {st.mean_admission_batch:.1f}), "
          f"{st.ingest_chunks} ingest chunks "
          f"(interleave {st.interleave_ratio:.2f}), "
          f"{st.prefill_jit_misses} admission jit misses")
    if engine.cache is not None:
        c = engine.cache.counters()
        print(f"prefix cache ({engine.cache.name}): "
              f"hits={st.cache_hits} misses={st.cache_misses} "
              f"cached_prefix_tokens={st.cached_prefix_tokens} "
              f"forks={st.forks} evictions={st.cache_evictions} "
              f"bytes={c['bytes_used']}/{engine.cache.max_bytes}")
    if getattr(args, "stats_json", None):
        with open(args.stats_json, "w") as f:
            f.write(engine.stats.to_json())
        print(f"stats written to {args.stats_json}")
    # every submitted request resolves to a completion — shed/deadline
    # ones included (that's the bounded-queue contract); a recovered
    # run's request count comes from the journal, not --n-requests.
    # fork=N submissions resolve to N completions each.
    if not getattr(args, "recover", False):
        assert len(completions) == args.n_requests * getattr(
            args, "fork", 1)
    return 0


def spec(args) -> int:
    """Speculative lookahead vs plain continuous batching, same workload."""
    import dataclasses

    from repro.serving import DecodeEngine, ModelDraft, NgramDraft

    cfg = (get_smoke_config(args.arch) if args.smoke
           else get_config(args.arch))
    if args.backend:
        cfg = cfg.with_backend(args.backend)
    # fp32 activations: the mode ASSERTS spec == plain greedy, and the
    # windowed verify accumulates in a different association order than
    # the sequential step — fp32 keeps argmax margins above that noise
    # (bf16 could flip a near-tie and fail the assert spuriously)
    cfg = dataclasses.replace(cfg, dtype="float32")
    rules = Rules.null()
    root = jax.random.PRNGKey(args.seed)
    params = lm.init_params(jax.random.fold_in(root, 0), cfg)

    k = args.speculate_k
    max_len = args.prompt_len + args.gen_len + max(args.segment_len, k) + 1
    if args.draft == "ngram":
        draft = NgramDraft()
    else:
        dparams = lm.init_params(jax.random.fold_in(root, 1), cfg)
        draft = ModelDraft(dparams, cfg, rules, n_slots=args.slots,
                           max_len=max_len)
    engine = DecodeEngine(
        params, cfg, rules, n_slots=args.slots,
        segment_len=args.segment_len, max_len=max_len, seed=args.seed,
        draft=draft, admission=getattr(args, "admission", "auto"),
        prefill_chunk=getattr(args, "prefill_chunk", 64))
    rng = np.random.default_rng(args.seed)
    requests = [(rng.integers(0, cfg.vocab_size, size=args.prompt_len,
                              dtype=np.int64).astype(np.int32),
                 args.gen_len) for _ in range(args.n_requests)]

    def run_once(speculate_k):
        engine.reset()
        for prompt, g in requests:
            engine.submit(prompt, g, speculate_k=speculate_k)
        t0 = time.perf_counter()
        comps = engine.run("continuous")
        return comps, time.perf_counter() - t0

    run_once(k)                                   # compile both paths
    run_once(0)
    comps_plain, t_plain = run_once(0)
    comps_spec, t_spec = run_once(k)
    for a, b in zip(comps_plain, comps_spec):
        assert np.array_equal(a.tokens, b.tokens), \
            f"speculative decode diverged from plain greedy on {a.uid}"

    total = sum(len(c.tokens) for c in comps_spec)
    st = engine.stats
    print(f"arch={cfg.name} backend={cfg.attention_backend} "
          f"slots={args.slots} speculate_k={k} draft={args.draft}")
    print(f"spec:  {total} tokens in {t_spec:.2f} s "
          f"({total/t_spec:.0f} tok/s) — acceptance "
          f"{st.acceptance_rate:.2f}, {st.spec_rounds} rounds, "
          f"{st.spec_rewinds} rewinds in "
          f"{st.spec_rewind_dispatches} varlen dispatches")
    print(f"plain: {total} tokens in {t_plain:.2f} s "
          f"({total/t_plain:.0f} tok/s) — speculative speedup "
          f"{t_plain/t_spec:.2f}x, outputs bit-identical")
    if getattr(args, "stats_json", None):
        with open(args.stats_json, "w") as f:
            f.write(engine.stats.to_json())
        print(f"stats written to {args.stats_json}")
    return 0


def retrieve(args) -> int:
    """Encode-once / query-many with the DocumentStore."""
    from repro.core import DocumentState, DocumentStore
    key = jax.random.PRNGKey(args.seed)
    k_dim, n, docs = 100, 750, args.batch
    store = DocumentStore()
    h = jax.random.normal(key, (docs, n, k_dim))
    for i in range(docs):
        store.add(f"doc{i}", DocumentState.from_hidden_states(h[i]))
    q = jax.random.normal(jax.random.fold_in(key, 1),
                          (docs, k_dim))
    ids = [f"doc{i}" for i in range(docs)]
    store.batched_lookup(ids, q).block_until_ready()
    t0 = time.perf_counter()
    iters = 100
    for _ in range(iters):
        out = store.batched_lookup(ids, q)
    out.block_until_ready()
    dt = (time.perf_counter() - t0) / iters
    print(f"store: {len(store)} docs, {store.nbytes/2**20:.1f} MiB "
          f"(raw hidden states would be {h.nbytes/2**20:.1f} MiB)")
    print(f"lookup: {docs/dt:.0f} queries/s, O(k²) each")
    return 0


def lookup(args, engines: Optional[list] = None) -> int:
    """Memory-serving: ingest once, pin resident, serve query waves.
    ``engines``, when given, receives the engine this run drives."""
    from repro.qa.gru import gru_params
    from repro.serving import LookupEngine

    k_dim, vocab, d_embed = 64, 1000, 32
    root = jax.random.PRNGKey(args.seed)
    k_embed, k_gru, k_query = (jax.random.fold_in(root, i)
                               for i in range(3))
    encoder = {"embed": jax.random.normal(k_embed, (vocab, d_embed)) * 0.1,
               "gru": gru_params(k_gru, d_embed, k_dim)}
    engine = LookupEngine(
        encoder, backend=args.lookup_backend, wave_size=args.wave_size,
        max_queue=getattr(args, "max_queue", None),
        shed_policy=getattr(args, "shed_policy", "reject_new"))
    if engines is not None:
        engines.append(engine)

    rng = np.random.default_rng(args.seed)
    if args.load:
        from repro.core import DocumentStore
        store = DocumentStore.load(args.load)
        for doc_id in store.ids():
            engine.pin(doc_id, store.get(doc_id))
        print(f"pinned {len(engine)} persisted memories from {args.load}")
    else:
        for i in range(args.n_docs):
            engine.ingest(f"doc{i}", rng.integers(0, vocab,
                                                  size=args.doc_len))
        engine.flush()
    doc_ids = list(engine.rows())

    queries = np.asarray(jax.random.normal(
        k_query, (args.n_queries, k_dim), jnp.float32))
    for i in range(args.n_queries):           # warm the wave programs
        engine.submit(doc_ids[i % len(doc_ids)], queries[i])
    engine.run()
    warm = engine.stats.queries
    for i in range(args.n_queries):
        engine.submit(doc_ids[(i * 7) % len(doc_ids)], queries[i],
                      priority=i % 3)
    t0 = time.perf_counter()
    engine.run()
    dt = time.perf_counter() - t0

    st = engine.stats
    served = st.queries - warm
    print(f"lookup backend={st.backend} "
          f"fixed_size_memory={engine.backend.fixed_size_memory}")
    print(f"memories: {st.documents} resident "
          f"({st.ingest_waves} varlen ingest waves = "
          f"{st.ingest_dispatches} dispatches, {st.pinned} pinned), "
          f"{engine.resident_bytes/2**20:.2f} MiB")
    print(f"serve: {served} queries in {dt:.3f} s "
          f"({served/max(dt, 1e-9):.0f} lookups/s) — "
          f"{st.waves} waves = {st.lookup_dispatches} dispatches "
          f"({st.queries_per_wave:.1f} queries/wave, "
          f"{st.multi_memory_waves} mixed-memory waves)")
    if st.shed:
        print(f"shed: {st.shed} (policy={engine.shed_policy})")
    if getattr(args, "stats_json", None):
        with open(args.stats_json, "w") as f:
            f.write(st.to_json())
        print(f"stats written to {args.stats_json}")
    assert st.lookup_dispatches == st.waves, "one dispatch per wave"
    return 0


def main(argv: Optional[Sequence[str]] = None,
         engines: Optional[list] = None) -> int:
    """Parse ``argv`` (default: the command line) and run the mode.
    ``engines``, when given, receives the engine a stream or lookup run
    drives, so an in-process caller can inspect its programs and
    counters afterwards."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", default="generate",
                    choices=["generate", "stream", "spec", "retrieve",
                             "lookup"])
    ap.add_argument("--arch", default="yi-34b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--backend", default=None,
                    choices=[None, "softmax", "linear", "gated_linear"])
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--gen-len", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 = categorical sampling")
    ap.add_argument("--seed", type=int, default=0)
    # stream mode (continuous batching)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--segment-len", type=int, default=8)
    ap.add_argument("--n-requests", type=int, default=16)
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="requests per decode step (0 = all at t=0)")
    ap.add_argument("--backends", default=None, metavar="A,B,...",
                    help="serve a heterogeneous fleet (stream mode): "
                         "comma-separated backend groups, e.g. "
                         "linear,softmax,mamba2 — one slot group per "
                         "backend behind a single admission queue "
                         "(smoke-scale fleet demo configs)")
    ap.add_argument("--admission", default="auto",
                    choices=["auto", "batched", "per_request"],
                    help="prompt ingestion: bucket-padded batched varlen"
                         " prefill + chunked ingest (batched) vs the"
                         " host-blocking prefill-on-admit baseline")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="max prompt tokens per ingest dispatch (rounded"
                         " up to a power of two); longer prompts are"
                         " chunked and interleaved with decode segments")
    # prefix caching (stream mode)
    ap.add_argument("--prefix-cache", default="off",
                    choices=["off", "auto", "on"],
                    help="content-hash prefix cache: shared prompt"
                         " prefixes admit as ONE state copy + suffix-"
                         "only prefill (fixed-size states) or reuse"
                         " refcounted KV blocks (softmax); 'on' errors"
                         " if the backend can't cache, 'auto' degrades"
                         " to off")
    ap.add_argument("--cache-bytes", type=int, default=64 << 20,
                    help="prefix-cache byte budget (LRU eviction)")
    ap.add_argument("--fork", type=int, default=1, metavar="N",
                    help="n-best: admit each prompt once and fork N"
                         " continuation slots off the shared prefill"
                         " (uids uid..uid+N-1)")
    # robustness knobs (stream mode)
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bound the admission queue; a full queue sheds"
                         " per --shed-policy (status='shed')")
    ap.add_argument("--shed-policy", default="reject_new",
                    choices=["reject_new", "evict_lowest"])
    ap.add_argument("--degrade-threshold", type=float, default=None,
                    help="waiting requests per slot beyond which the"
                         " engine degrades (spec off, smaller ingest"
                         " chunks); None disables")
    ap.add_argument("--stats-json", default=None, metavar="PATH",
                    help="write EngineStats (counters + lifecycle/chaos"
                         " fields) to PATH as JSON")
    # durability (stream mode)
    ap.add_argument("--journal", default=None, metavar="PATH",
                    help="write-ahead request journal: every submit/"
                         "cancel/ack is fsync'd to PATH before it takes"
                         " effect; a restarted engine replays it")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="fleet mode: per-replica journals under DIR")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="engine checkpoints (slot states + scheduler)"
                         " under DIR; atomic, keep-N retention")
    ap.add_argument("--checkpoint-every", type=int, default=4,
                    metavar="N", help="checkpoint every N scheduler"
                    " events (0 = only on graceful shutdown)")
    ap.add_argument("--recover", action="store_true",
                    help="restore the newest checkpoint, replay the"
                         " journal past it, and finish the stranded"
                         " work instead of submitting new requests")
    ap.add_argument("--replicas", type=int, default=1,
                    help="fleet mode: replicas per backend group"
                         " (heartbeat + circuit-breaker failover)")
    ap.add_argument("--crash-at-event", type=int, default=None,
                    metavar="N", help="chaos: hard-kill the engine at"
                    " scheduler event N (exit 3; restart with"
                    " --recover)")
    # lookup mode (memory serving)
    ap.add_argument("--n-docs", type=int, default=128,
                    help="lookup mode: memories to ingest")
    ap.add_argument("--doc-len", type=int, default=64,
                    help="lookup mode: tokens per synthetic document")
    ap.add_argument("--n-queries", type=int, default=1024,
                    help="lookup mode: queries in the storm")
    ap.add_argument("--wave-size", type=int, default=64,
                    help="lookup mode: max requests per query wave")
    ap.add_argument("--lookup-backend", default="linear",
                    choices=["linear", "softmax"],
                    help="fixed-size k×k memories through the indexed "
                         "Pallas kernel vs the full-hidden-state "
                         "softmax baseline")
    ap.add_argument("--load", default=None, metavar="PATH",
                    help="lookup mode: pin a persisted DocumentStore "
                         "(.npz) instead of synthesising documents")
    # spec mode (speculative lookahead)
    ap.add_argument("--speculate-k", type=int, default=6,
                    help="draft tokens per verify round")
    ap.add_argument("--draft", default="ngram",
                    choices=["ngram", "model"],
                    help="draft provider: prompt-lookup n-grams (free) "
                         "or a second LM with its own slot states")
    args = ap.parse_args(argv)
    if args.mode == "lookup" and args.load and \
            args.lookup_backend != "linear":
        ap.error(
            f"--load pins a persisted compressed (k×k) DocumentStore, "
            f"which only the fixed-size linear backend can serve; "
            f"--lookup-backend {args.lookup_backend} keeps full "
            f"hidden states resident and cannot pin compressed "
            f"memories (drop --load and ingest documents instead)")
    if args.mode == "stream":
        return stream(args, engines)
    if args.mode == "spec":
        return spec(args)
    if args.mode == "lookup":
        return lookup(args, engines)
    return generate(args) if args.mode == "generate" else retrieve(args)


if __name__ == "__main__":
    raise SystemExit(main())
