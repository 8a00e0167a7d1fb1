"""Fixed-slot continuous-batching decode engine.

The paper's serving claim — a fixed-size O(k²) state with constant-time
lookups — pays off at scale when many concurrent requests share the
device. This engine turns the PR-1 fused generation loop into a
multi-tenant system:

Every state operation routes through a
:class:`~repro.serving.backends.DecodeBackend` — the seam that keeps
this module a pure scheduler while the backend owns the state layout
(fixed-size linear/gated/mamba2/rwkv6 states vs. the growing softmax
KV cache).

* **Slots.** The device holds ONE whole-stack decode state of batch size
  ``n_slots``; each slot is (at most) one live request. Decode runs in
  fixed ``segment_len``-step segments via the backend's
  ``generate_segment`` —
  one ``lax.scan`` dispatch per segment, with per-slot positions,
  per-slot active masks, and per-slot stop conditions (EOS / token
  budget) resolved *inside* the scan, so a slot can finish mid-segment
  without holding the others up.

* **Scheduler.** Between segments a host-side scheduler drains finished
  slots and admits queued requests into the freed ones. The default
  ``admission="batched"`` path admits ALL queue-head requests at once:
  prompts are END-padded to a power-of-2 bucket width (bounding jit
  recompiles to log₂(prefill_chunk) programs instead of one per
  distinct prompt length) and encoded by ONE
  :func:`lm.prefill_varlen` dispatch whose per-row length masking makes
  every row bit-identical to prefilling it alone; one masked select
  swaps the whole admission batch into its slots. Prompts longer than
  ``prefill_chunk`` are ingested chunk-by-chunk through
  :func:`lm.decode_window_varlen` — the variable-length masked window
  primitive — with chunk dispatches INTERLEAVED with decode segments,
  so a long prompt never stalls tokens streaming from live slots.
  (``admission="per_request"`` keeps the PR-2 host-blocking
  prefill-on-admit path: one :func:`lm.prefill` + one
  :func:`lm.write_slot_state` per request — the benchmark baseline, and
  the fallback for layer patterns without varlen prefill support.)
  For the linear family the swap-in cost is an O(k²)-per-layer copy
  regardless of prompt length (the paper's fixed-size representation);
  only the softmax baseline pays O(T·k) KV-cache bytes.

* **Isolation.** Inactive slots are masked bit-for-bit inside the scan
  (state frozen, outputs padded), so per-slot outputs under greedy
  decoding are exactly what each request would produce running alone —
  the engine's correctness contract, enforced by
  ``tests/test_serving.py``.

Time is *logical*: the clock advances ``segment_len`` decode steps per
segment, and request ``arrival`` times are expressed in decode steps —
which keeps synthetic Poisson request streams (``serve.py --mode
stream``) deterministic and testable.

Admission policies:

* ``continuous`` — admit into any freed slot between segments (the
  engine's point).
* ``static``     — admit only when ALL slots are free (batch-synchronous
  baseline: the whole batch runs until its longest request finishes).
  Same compiled segment program, so benchmarks isolate scheduling.

* **Lifecycle & fault tolerance.** The fixed-size representation makes
  a request *portable*: any active slot can be suspended into a host-
  side :class:`~repro.serving.lifecycle.SuspendedRequest` (one O(k²)
  ``snapshot_state`` copy + scalar bookkeeping) and re-admitted later
  with bit-identical greedy continuation — the primitive behind
  priority preemption (a high-priority arrival preempts the lowest-
  progress lower-priority slot when the queue is saturated) and
  deadline eviction. Requests carry ``priority`` and ``deadline_s``
  (logical decode steps), can be ``cancel()``-ed, and the admission
  queue can be bounded with an explicit shed policy (reject-new vs
  evict-lowest-priority). Under overload the engine degrades
  gracefully: speculative decoding auto-disables and prefill chunks
  shrink once queue pressure crosses ``degrade_threshold``, with every
  transition recorded in :class:`EngineStats`. A per-segment fused
  ``jnp.isfinite`` probe (``lm.slot_state_finite``) detects numeric
  faults; a poisoned slot is quarantined (its NaNs are frozen by the
  same row masking that isolates inactive slots, so neighbours stay
  bit-identical) and its request retried once from its last good
  checkpoint on a fresh slot, or surfaced as
  ``Completion(status="failed")``. A deterministic
  :class:`~repro.serving.lifecycle.FaultInjector` drives the chaos
  suite (``tests/test_lifecycle.py``, ``benchmarks/chaos_serving.py``).

Speculative lookahead (per-request policy, ``speculate_k`` on submit):

A speculative request advances through draft/verify ROUNDS instead of
one-token segment steps. Per round, batched across every speculative
slot: a draft provider proposes K tokens, ONE ``lm.decode_window``
launch verifies all K+1 window positions at every slot's own depth
(per-slot positions), and the longest matching greedy prefix plus the
target's own next token are emitted — between 1 and K+1 tokens of the
EXACT plain-greedy sequence per round. Slots that accepted the whole
window commit the verify state with one masked select; slots that
rejected mid-window (accepted prefixes of DIFFERING lengths) rewind
together — ONE ``lm.decode_window_varlen`` dispatch re-advances every
rewinding slot's accepted prefix from the pre-round state under per-row
length masks, then one masked select lands the rows — cheap because the
state is the paper's fixed-size representation, not a KV cache. Plain
and speculative requests share the slot batch: plain slots advance in
slot-masked segments with speculative slots frozen, and vice versa, so
mixing them never changes anyone's tokens.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools
import itertools
import json
import time
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.manager import CheckpointManager
from repro.configs.base import ModelConfig
from repro.serving.backends import DecodeBackend, backend_for_config
from repro.serving.journal import (
    REC_ACK,
    REC_CANCEL,
    REC_SUBMIT,
    Journal,
    ack_record,
    cancel_record,
    completion_from_ack,
    submit_record,
)
from repro.serving.lifecycle import (
    SHED_POLICIES,
    STATUS_CANCELLED,
    STATUS_DEADLINE,
    STATUS_FAILED,
    STATUS_OK,
    STATUS_SHED,
    Checkpoint,
    FaultInjector,
    InjectedCrash,
    SuspendedRequest,
    poison_snapshot,
)
from repro.serving.tracing import (
    ADMIT,
    INGEST,
    LIFECYCLE,
    POST,
    SEGMENT,
    WAIT,
)
from repro.sharding import Rules

PAD_ID = -1  # emitted by masked slots; never a vocabulary id
_span = jax.profiler.TraceAnnotation   # see repro.serving.tracing


def _time_field():
    """A host time: not compared, printed or journaled."""
    return dataclasses.field(default=None, compare=False, repr=False)


def _pow2_ceil(n: int) -> int:
    """Smallest power of two >= n (bucket widths for padded admission)."""
    return 1 << (int(n) - 1).bit_length()


@dataclasses.dataclass
class Request:
    """One generation request. ``arrival`` and ``deadline_s`` are in
    logical decode steps (``deadline_s`` is an absolute completion
    deadline; a request past it is shed from the queue or evicted from
    its slot with its partial output). ``priority`` orders admission
    (higher first) and arms preemption; ``speculate_k`` > 0 decodes
    through draft/verify rounds (greedy only) instead of one-token
    segment steps. ``fork`` > 1 asks for N independent continuations of
    one prompt: the prompt is admitted (prefilled) ONCE, and the N-1
    extra continuations spawn as suspended requests sharing the
    prefilled state snapshot — uids uid..uid+fork-1."""
    uid: int
    prompt: np.ndarray            # (P,) int32
    max_new_tokens: int
    arrival: float = 0.0
    speculate_k: int = 0
    priority: int = 0
    deadline_s: Optional[float] = None
    fork: int = 1
    # engine-clock (time.perf_counter) times, see DecodeEngine.progress
    t_submit: Optional[float] = _time_field()
    t_first_chunk: Optional[float] = _time_field()
    t_first_token: Optional[float] = _time_field()
    t_tokens: Optional[float] = _time_field()


@dataclasses.dataclass
class Completion:
    uid: int
    prompt_len: int
    tokens: np.ndarray            # generated tokens (incl. EOS if hit)
    finish_reason: str            # "eos" | "length" | lifecycle status
    admitted_step: int            # -1 if never admitted (shed/deadline)
    finished_step: int
    status: str = STATUS_OK       # ok|cancelled|deadline|shed|failed
    retries: int = 0              # numeric-fault retries consumed
    # the request's engine-clock times (None after journal recovery)
    t_submit: Optional[float] = _time_field()
    t_first_chunk: Optional[float] = _time_field()
    t_first_token: Optional[float] = _time_field()
    t_tokens: Optional[float] = _time_field()


@dataclasses.dataclass
class RequestProgress:
    """One request as :meth:`DecodeEngine.progress` sees it: output
    tokens so far, prompt tokens consumed, and engine-clock times
    (``time.perf_counter``): submitted, first prompt chunk read back,
    first token read back, latest tokens read back (None until then)."""
    tokens: int
    prompt_done: int
    t_submit: Optional[float]
    t_first_chunk: Optional[float]
    t_first_token: Optional[float]
    t_tokens: Optional[float]

    @classmethod
    def of(cls, r, tokens: int, prompt_done: int) -> "RequestProgress":
        """From a :class:`Request` or a :class:`Completion`."""
        return cls(tokens, prompt_done, r.t_submit, r.t_first_chunk,
                   r.t_first_token, r.t_tokens)


class Progress(NamedTuple):
    requests: Dict[int, RequestProgress]
    done: List[Completion]


@dataclasses.dataclass
class EngineStats:
    segments: int = 0
    emitted_tokens: int = 0       # scan-emitted (excludes prefill-sampled)
    prefills: int = 0             # admitted (prompt-encoded) requests
    n_slots: int = 0
    segment_len: int = 0
    # admission (batched/chunked path)
    admission_batches: int = 0    # batched-admission waves
    prefill_dispatches: int = 0   # lm.prefill_varlen launches
    ingest_chunks: int = 0        # decode_window_varlen ingest launches
    ingest_interleaved: int = 0   # ...issued while decode slots were live
    admission_dispatches: int = 0  # total admission-path device calls
    # token positions the prefill and ingest dispatches compute (rows x
    # bucket width: n_slots rows pool-wide, 1 for the batch-1 programs)
    # and the prompt tokens they consume; cache-hit landings add neither
    admission_token_slots: int = 0
    admission_tokens: int = 0
    prefill_jit_misses: int = 0   # new admission program shapes compiled
    # speculative rounds
    spec_rounds: int = 0          # batched draft/verify rounds
    spec_drafted: int = 0         # draft tokens proposed to the verifier
    spec_accepted: int = 0        # draft tokens the target agreed with
    spec_emitted: int = 0         # tokens emitted by rounds (incl. bonus)
    spec_rewinds: int = 0         # partial-acceptance slot re-advances
    spec_rewind_rounds: int = 0   # rounds that had >= 1 partial acceptor
    spec_rewind_dispatches: int = 0  # varlen rewind launches (1 per round)
    # lifecycle & fault tolerance
    preemptions: int = 0          # active slots suspended mid-generation
    resumes: int = 0              # suspended requests re-admitted
    cancelled: int = 0            # cancel() completions
    deadline_evictions: int = 0   # requests past deadline (queued/active)
    shed: int = 0                 # bounded-queue rejections
    quarantined: int = 0          # slots poisoned by a numeric fault
    retries: int = 0              # snapshot-retries after a fault
    failed: int = 0               # requests with retries exhausted
    checkpoints: int = 0          # last-good snapshots taken
    finite_checks: int = 0        # fused isfinite probes run
    degrade_transitions: int = 0  # overload degradation flips (both ways)
    spec_disables: int = 0        # spec requests forced plain (degraded)
    # prefix cache & fork/n-best
    cache_hits: int = 0           # admissions served from the cache
    cache_misses: int = 0         # cacheable prompts with no entry
    cache_evictions: int = 0      # entries/blocks dropped (byte budget)
    cached_prefix_tokens: int = 0  # prompt tokens NOT re-encoded on hits
    forks: int = 0                # extra continuations spawned (fork-1)
    degrade_events: List[Dict] = dataclasses.field(default_factory=list)

    def to_dict(self) -> Dict:
        """Counters + derived ratios as one JSON-able dict (the machine-
        readable form benchmarks and CI gates consume)."""
        d = dataclasses.asdict(self)
        for name in ("slot_utilization", "acceptance_rate",
                     "tokens_per_round", "mean_admission_batch",
                     "interleave_ratio"):
            d[name] = getattr(self, name)
        return d

    def to_json(self, indent: int = 2) -> str:
        return json.dumps(self.to_dict(), indent=indent)

    @property
    def slot_utilization(self) -> float:
        """Fraction of scanned slot-steps that emitted a real token."""
        total = self.segments * self.n_slots * self.segment_len
        return self.emitted_tokens / total if total else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of drafted tokens the verifier accepted."""
        return (self.spec_accepted / self.spec_drafted
                if self.spec_drafted else 0.0)

    @property
    def tokens_per_round(self) -> float:
        """Mean emitted tokens per batched speculative round (summed
        over speculative slots); the deterministic form of the
        speculative speedup — plain segments emit n_active per step."""
        return (self.spec_emitted / self.spec_rounds
                if self.spec_rounds else 0.0)

    @property
    def mean_admission_batch(self) -> float:
        """Requests admitted per batched-admission wave."""
        return (self.prefills / self.admission_batches
                if self.admission_batches else 0.0)

    @property
    def interleave_ratio(self) -> float:
        """Fraction of chunked-prefill ingest dispatches issued while at
        least one decode slot was live — 1.0 means long-prompt ingestion
        never ran with the decode loop idle."""
        return (self.ingest_interleaved / self.ingest_chunks
                if self.ingest_chunks else 0.0)


class DecodeEngine:
    """Continuous-batching decode over a fixed number of state slots.

    The engine is a backend-agnostic scheduler: every state operation
    (prefill, windows, snapshot/restore, masking, the finite probe)
    routes through a :class:`~repro.serving.backends.DecodeBackend`,
    resolved from the config by the backend registry unless an explicit
    ``backend=`` instance is passed. The engine never inspects the
    attention family — capability questions (varlen prefill? fixed-size
    state?) are answered by the backend's flags.

    One engine owns its jitted programs (prefill / admit / segment), so
    reuse the instance — ``reset()`` clears request bookkeeping without
    recompiling — when timing static vs. continuous admission.

    ``max_len`` bounds position (prompt + generated + draft lookahead)
    per request; the softmax baseline sizes its KV caches to it, the
    linear family's state is O(1) in it.

    ``draft`` enables speculative requests: any
    :class:`repro.serving.speculative.DraftProvider` (NgramDraft /
    ModelDraft / ReplayDraft). Requests opt in per-submit with
    ``speculate_k``.

    ``admission`` selects the prompt-ingestion path: "batched" (bucket-
    padded varlen prefill of the whole admission wave in one dispatch,
    long prompts chunked through ``decode_window_varlen`` interleaved
    with decode segments), "per_request" (the PR-2 host-blocking
    prefill-on-admit baseline), or "auto" (batched when the layer
    pattern supports varlen prefill). ``prefill_chunk`` (rounded up to a
    power of two) bounds both the ingest chunk size and the bucket
    widths — so admission compiles O(log prefill_chunk) programs total
    instead of one per distinct prompt length. ``ingest`` picks the
    continuation-chunk program: "parallel" (chunk-parallel prefill
    kernels continuing from carried state — MXU-shaped), "recurrent"
    (the masked fused-recurrent window), or "auto" (parallel on TPU,
    recurrent elsewhere — the decode_kernel="auto" idiom).

    Robustness knobs (PR 6):

    ``max_queue`` bounds the admission queue; when full, ``shed_policy``
    decides between "reject_new" (the arriving request completes
    immediately with ``status="shed"``) and "evict_lowest" (the lowest-
    priority queued request is shed instead, if strictly lower-priority
    than the arrival). ``degrade_threshold`` (waiting requests per
    slot; None disables) arms graceful overload degradation:
    speculative decoding auto-disables and the live prefill chunk
    halves while pressure stays above it, restoring below half the
    threshold (hysteresis), every flip recorded in ``EngineStats``.
    ``finite_check`` runs the fused per-slot ``jnp.isfinite`` probe at
    every segment/round boundary; a non-finite slot is quarantined for
    the rest of the run and its request retried up to ``max_retries``
    times from its last good checkpoint on a fresh slot (checkpoints
    are taken at activation, and every ``checkpoint_interval`` events
    when > 0), else completed with ``status="failed"``. ``injector``
    accepts a :class:`~repro.serving.lifecycle.FaultInjector` driving
    deterministic chaos (tests/benchmarks only).
    """

    def __init__(
        self,
        params: Any,
        cfg: ModelConfig,
        rules: Optional[Rules] = None,
        *,
        backend: Optional[DecodeBackend] = None,
        n_slots: int = 4,
        segment_len: int = 8,
        max_len: int = 512,
        eos_id: Optional[int] = None,
        temperature: float = 0.0,
        seed: int = 0,
        draft: Optional[Any] = None,
        admission: str = "auto",
        prefill_chunk: int = 64,
        ingest: str = "auto",
        max_queue: Optional[int] = None,
        shed_policy: str = "reject_new",
        degrade_threshold: Optional[float] = None,
        finite_check: bool = True,
        max_retries: int = 1,
        checkpoint_interval: int = 0,
        injector: Optional[FaultInjector] = None,
        journal: Optional[Any] = None,
        checkpoint_dir: Optional[str] = None,
        checkpoint_every: int = 0,
        checkpoint_keep: int = 2,
        prefix_cache: Any = None,
        cache_bytes: int = 64 << 20,
    ):
        self.params = params
        self.cfg = cfg
        self.rules = rules if rules is not None else Rules.null()
        self.backend = (backend if backend is not None
                        else backend_for_config(cfg, self.rules))
        self.n_slots = n_slots
        self.segment_len = segment_len
        self.max_len = max_len
        self.eos_id = eos_id
        self.temperature = temperature
        self._seed = seed
        self.draft = draft
        assert shed_policy in SHED_POLICIES, shed_policy
        assert max_queue is None or max_queue >= 1, max_queue
        assert max_retries >= 0 and checkpoint_interval >= 0
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        self.degrade_threshold = degrade_threshold
        self.finite_check = finite_check
        self.max_retries = max_retries
        self.checkpoint_interval = checkpoint_interval
        self.injector = injector
        # durability: write-ahead journal + durable engine checkpoints.
        # A path string is convenient at the CLI; tests/fleets pass a
        # Journal instance (possibly in-memory).
        self.journal: Optional[Journal] = (
            Journal(journal) if isinstance(journal, str) else journal)
        assert checkpoint_every >= 0 and checkpoint_keep >= 1
        self.checkpoint_every = checkpoint_every
        self._ckpt_mgr: Optional[CheckpointManager] = (
            CheckpointManager(checkpoint_dir, keep=checkpoint_keep)
            if checkpoint_dir is not None else None)
        # ONE capability-driven decision on the backend object resolves
        # both "auto" knobs (previously two near-identical string-check
        # branches here); unsupported modes raise naming the backend
        # and the missing capability
        self.admission, self.ingest = self.backend.resolve_modes(
            admission, ingest)
        # power-of-2 chunk so every bucket width is a power of two too
        self.prefill_chunk = min(_pow2_ceil(max(1, prefill_chunk)),
                                 max_len)
        # prefix caching: content-hash → state reuse at admission.
        # None/False = off; "auto" = on iff the backend supports it and
        # admission resolved to batched (cache hits must land the
        # suffix on the batched path's chunk grid); True = required
        # (raises when unsupported); a PrefixCache instance is used
        # as-is (fleets share or scope caches this way).
        self.cache = None
        if prefix_cache not in (None, False):
            if isinstance(prefix_cache, str):
                assert prefix_cache == "auto", prefix_cache
                if (self.backend.supports_prefix_cache
                        and self.admission == "batched"):
                    self.cache = self.backend.make_prefix_cache(
                        cache_bytes, self.prefill_chunk)
            elif prefix_cache is True:
                if self.admission != "batched":
                    raise ValueError(
                        "prefix caching requires batched admission; "
                        f"backend {self.backend.name!r} resolved "
                        f"admission={self.admission!r}")
                self.cache = self.backend.make_prefix_cache(
                    cache_bytes, self.prefill_chunk)
            else:
                if prefix_cache.chunk % self.prefill_chunk != 0:
                    raise ValueError(
                        f"prefix cache chunk {prefix_cache.chunk} is "
                        f"not a multiple of the engine's prefill_chunk "
                        f"{self.prefill_chunk}: hit suffixes would "
                        f"leave the cold-admission chunk grid")
                self.cache = prefix_cache
        self.cache_bytes = cache_bytes

        be = self.backend

        @jax.jit
        def _prefill(params, prompt):
            # one compile per distinct prompt length; prompts are NOT
            # padded — pad tokens would pollute the fixed-size state and
            # break the run-alone equivalence contract
            logits, st = be.prefill(params, prompt)
            return logits, be.pad_decode_state(st, max_len=max_len)

        @jax.jit
        def _prefill_varlen(params, state, tokens, lens, mask):
            # one compile per power-of-2 bucket width; per-row length
            # masking keeps each row bit-identical to an unpadded
            # batch-1 prefill, so bucket padding is free of the state
            # pollution the per-request path avoided by not padding.
            # The admitted rows are selected into the engine state
            # INSIDE the program — one dispatch admits the whole wave.
            last, st = be.prefill_varlen(params, tokens, lens)
            st = be.pad_decode_state(st, max_len=max_len)
            return last, be.where_state(mask, st, state)

        @jax.jit
        def _prefill_varlen_one(params, state, tokens, lens, slot):
            # the steady-state wave of ONE: a freed slot refills from a
            # compact batch-1 bucket-padded prefill + slot write, so a
            # single admission never pays n_slots× padded FLOPs
            last, st = be.prefill_varlen(params, tokens, lens)
            st = be.pad_decode_state(st, max_len=max_len)
            return last, be.restore_state(state, st, slot)

        @jax.jit
        def _window_varlen(params, state, tokens, pos0, lens):
            # the variable-length masked RECURRENT window: batched
            # speculative rewind (re-advance must follow the exact
            # decode-step chain the plain greedy path runs)
            logits, st = be.decode_window_varlen(
                params, state, tokens, pos0, lens)
            last = jnp.take_along_axis(
                logits, jnp.maximum(lens - 1, 0)[:, None, None],
                axis=1)[:, 0]
            return last, st

        @jax.jit
        def _ingest_varlen(params, state, tokens, pos0, lens):
            # chunked-prefill continuation: same masking semantics, but
            # the linear family continues through the chunk-PARALLEL
            # prefill kernels (prefill FLOPs per chunk, not W decode
            # steps); softmax falls back to the per-step cache writes
            logits, st = be.ingest_window_varlen(
                params, state, tokens, pos0, lens)
            last = jnp.take_along_axis(
                logits, jnp.maximum(lens - 1, 0)[:, None, None],
                axis=1)[:, 0]
            return last, st

        @jax.jit
        def _admit(engine_state, request_state, slot):
            return be.write_slot_state(engine_state, request_state, slot)

        # the slot state is donated: the segment's loop updates it in
        # place instead of copying it into its carry first, and
        # step_segment replaces self.state with the carry it returns
        @functools.partial(jax.jit, donate_argnums=(1,))
        def _segment(params, state, tok, pos, active, remaining, key):
            return be.generate_segment(
                params, state, tok, pos, active, remaining, segment_len,
                eos_id=eos_id, temperature=temperature,
                key=key, pad_id=PAD_ID)

        @jax.jit
        def _verify(params, state, window, pos):
            # greedy verify: one decode_window launch per layer, every
            # slot at its own depth; only the argmax tokens leave the
            # device (the (S, W, V) logits never transfer)
            logits, st = be.decode_window(params, state, window, pos)
            return jnp.argmax(logits, -1).astype(jnp.int32), st

        @jax.jit
        def _select(mask, new, old):
            return be.where_state(mask, new, old)

        @jax.jit
        def _snapshot(state, slot):
            return be.snapshot_state(state, slot)

        @functools.partial(jax.jit, static_argnums=(2,))
        def _snapshot_rows(state, slot, n_rows):
            # row-ranged snapshot: the softmax KV copy shrinks to the
            # W written rows (O(W·k) instead of O(max_len·k)); static
            # width → one compiled program per bucket
            return be.snapshot_state_rows(state, slot, n_rows)

        @functools.partial(jax.jit, static_argnums=(4,))
        def _select_rows(mask, new, old, start, width):
            # row-ranged merge: speculative rewind touches exactly the
            # rows the round wrote instead of selecting over the whole
            # (S, max_len, Hkv, Dh) caches
            return be.where_state_rows(mask, new, old, start, width)

        @jax.jit
        def _finite(state):
            # ONE fused reduction over every float leaf → (S,) bool;
            # the numeric-fault detector, amortised per segment
            return be.slot_state_finite(state)

        @jax.jit
        def _poison(state, slot):
            # chaos-harness only: NaN-fill exactly one slot's state
            bad = poison_snapshot(be.snapshot_state(state, slot))
            return be.restore_state(state, bad, slot)

        self._prefill = _prefill
        self._prefill_varlen = _prefill_varlen
        self._prefill_varlen_one = _prefill_varlen_one
        self._window_varlen = _window_varlen
        self._ingest_varlen = _ingest_varlen
        self._admit = _admit
        self._segment = _segment
        self._verify = _verify
        self._select = _select
        self._snapshot = _snapshot
        self._snapshot_rows = _snapshot_rows
        self._select_rows = _select_rows
        self._finite = _finite
        self._poison = _poison
        # admission program shapes seen — the host-side mirror of the
        # jit cache, so EngineStats can report compile (miss) counts
        self._seen_shapes: set = set()
        self.reset()

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def reset(self) -> None:
        """Clear all requests/slots/stats; keep compiled programs."""
        self.state = self.backend.init_slots(
            batch=self.n_slots, max_len=self.max_len)
        s = self.n_slots
        self._tok = np.zeros((s,), np.int32)
        self._pos = np.zeros((s,), np.int32)
        self._active = np.zeros((s,), bool)
        self._remaining = np.zeros((s,), np.int32)
        self._spec_k = np.zeros((s,), np.int32)
        self._slot_req: List[Optional[Request]] = [None] * s
        self._slot_toks: List[List[int]] = [[] for _ in range(s)]
        self._slot_admitted: List[int] = [0] * s
        # chunked-ingestion bookkeeping: a slot holding a request whose
        # prompt is still being consumed (cursor < len(prompt)) is
        # occupied but not yet decode-active
        self._ingest_req: List[Optional[Request]] = [None] * s
        self._ingest_cursor = np.zeros((s,), np.int64)
        self._queue: List[Request] = []   # kept sorted by (arrival, uid)
        self._completions: Dict[int, Completion] = {}
        self._n_progressed = 0    # completions progress() has handed out
        self._clock = 0
        self._next_uid = 0
        self._key = jax.random.PRNGKey(self._seed)
        # lifecycle & fault-tolerance bookkeeping
        self._suspended: List[SuspendedRequest] = []
        self._quarantined = np.zeros((s,), bool)
        self._retry_count: Dict[int, int] = {}   # uid → retries consumed
        self._ckpt: Dict[int, Checkpoint] = {}
        self._last_ckpt_event = np.zeros((s,), np.int64)
        self._cancel_uids: set = set()
        self._degraded = False
        self._events = 0          # segment/round boundaries elapsed
        self._admit_passes = 0    # admission passes attempted
        # durability bookkeeping: uids whose ack is already in the
        # journal (delivered in a previous incarnation — never re-acked)
        # and the replay flag that suppresses re-journaling journaled
        # submits/cancels while recovery re-applies them
        self._journal_acked: Dict[int, Completion] = {}
        self._replaying = False
        # prefix-cache bookkeeping: the cache itself SURVIVES reset
        # (like compiled programs — reset clears requests, not learned
        # artifacts); stats report counter deltas since this reset.
        # _cache_hold pins the cache entries/blocks each slot was
        # admitted from until the slot is torn down.
        self._cache_hold: List[Optional[Any]] = [None] * s
        self._cache_base = (self.cache.counters()
                            if self.cache is not None else None)
        if self.draft is not None:
            self.draft.reset()
        self.stats = EngineStats(n_slots=self.n_slots,
                                 segment_len=self.segment_len)

    def submit(self, prompt, max_new_tokens: int,
               arrival: float = 0.0, speculate_k: int = 0,
               priority: int = 0,
               deadline_s: Optional[float] = None,
               uid: Optional[int] = None, fork: int = 1) -> int:
        """Queue a request; returns its uid. ``arrival`` is in logical
        decode steps (0 = available immediately); ``deadline_s`` an
        absolute logical-step completion deadline; ``priority`` orders
        admission (higher first, FIFO within a priority) and arms
        preemption of lower-priority slots. ``speculate_k`` > 0 decodes
        through draft/verify rounds of K proposals (requires the engine
        to hold a draft provider and greedy decoding — verified
        speculation preserves the greedy sequence exactly; stochastic
        sampling would need rejection-sampling machinery).

        Validation is ATOMIC: every check runs before any engine state
        is touched, so a raising submit leaves the queue, uid counter
        and stats exactly as they were (tests/test_lifecycle.py pins
        this). If the queue is bounded and full, the shed policy
        resolves synchronously — the shed request (the arrival, or a
        strictly lower-priority queued victim under "evict_lowest")
        completes immediately with ``status="shed"``.

        ``fork`` > 1 requests N continuations of the one prompt: uids
        uid..uid+fork-1 are allocated, the prompt is encoded ONCE, and
        at activation the N-1 extra continuations spawn as suspended
        requests sharing the prefilled state snapshot — each then
        decodes independently, bit-identical (greedy) to N separate
        submits. Returns the FIRST uid.

        ``uid`` lets a fleet scheduler assign globally-unique ids across
        slot groups; it must be monotone (>= the engine's next uid)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if fork < 1:
            raise ValueError(f"fork must be >= 1, got {fork}")
        if uid is not None and uid < self._next_uid:
            raise ValueError(
                f"uid {uid} is not monotone (engine next uid is "
                f"{self._next_uid})")
        if max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        if speculate_k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        if speculate_k > 0 and self.draft is None:
            raise ValueError(
                "speculate_k > 0 needs a draft provider on the engine")
        if speculate_k > 0 and self.temperature > 0.0:
            raise ValueError(
                "speculative decoding is greedy-only (temperature=0)")
        if deadline_s is not None and deadline_s <= arrival:
            raise ValueError(
                f"deadline_s ({deadline_s}) must be after arrival "
                f"({arrival})")
        # speculative verify probes up to speculate_k tokens past the
        # last emitted one; the softmax KV caches must have room for it
        if len(prompt) + max_new_tokens + speculate_k > self.max_len + 1:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens "
                f"({max_new_tokens}) + speculate_k ({speculate_k}) "
                f"exceeds engine max_len {self.max_len} + 1")
        # ---- validation complete; engine state mutations start here --
        if uid is None:
            uid = self._next_uid
        # write-ahead: the request is durable before ANY engine state
        # changes, so a crash after submit() returns can never lose it
        # (replay suppressed: recovery re-applies journaled submits)
        if self.journal is not None and not self._replaying:
            self.journal.append(submit_record(
                uid, prompt, max_new_tokens, arrival, speculate_k,
                priority, deadline_s, fork=fork))
        self._next_uid = uid + fork
        req = Request(uid=uid, prompt=prompt,
                      max_new_tokens=max_new_tokens, arrival=arrival,
                      speculate_k=speculate_k, priority=priority,
                      deadline_s=deadline_s, fork=fork,
                      t_submit=time.perf_counter())
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            victim = self._pick_shed_victim(req)
            self._shed(victim)
            if victim is req:
                return uid
        # sorted insertion: an early-arriving request submitted late must
        # not be head-of-line blocked behind a far-future one
        bisect.insort(self._queue, req,
                      key=lambda r: (r.arrival, r.uid))
        return uid

    def _pick_shed_victim(self, incoming: Request) -> Request:
        """Full queue: who gets shed? "reject_new" always sheds the
        arrival; "evict_lowest" sheds the lowest-priority queued request
        instead, provided it is STRICTLY lower-priority than the
        arrival (newest of the lowest tier goes first), else the
        arrival."""
        if self.shed_policy == "reject_new":
            return incoming
        victim = min(self._queue,
                     key=lambda r: (r.priority, -r.arrival, -r.uid))
        if victim.priority < incoming.priority:
            self._queue.remove(victim)
            return victim
        return incoming

    def _shed(self, req: Request) -> None:
        self.stats.shed += 1
        self._complete(req, [], admitted_step=-1, status=STATUS_SHED)

    def cancel(self, uid: int) -> bool:
        """Cancel a request by uid. Queued/suspended requests complete
        immediately with ``status="cancelled"`` (suspended ones keep
        their partial tokens); an active/ingesting request is marked and
        evicted at the next scheduling boundary. Returns False if the
        uid is unknown or already completed."""
        # write-ahead: the intent is durable before it takes effect (a
        # replayed no-op cancel is still a no-op)
        if self.journal is not None and not self._replaying:
            self.journal.append(cancel_record(uid))
        for i, r in enumerate(self._queue):
            if r.uid == uid:
                self._queue.pop(i)
                self.stats.cancelled += 1
                self._complete(r, [], admitted_step=-1,
                               status=STATUS_CANCELLED)
                return True
        for i, s in enumerate(self._suspended):
            if s.req.uid == uid:
                self._suspended.pop(i)
                self.stats.cancelled += 1
                self._complete(s.req, s.toks,
                               admitted_step=s.admitted_step,
                               status=STATUS_CANCELLED,
                               retries=s.retries)
                return True
        for slot in range(self.n_slots):
            req = self._slot_req[slot] or self._ingest_req[slot]
            if req is not None and req.uid == uid:
                self._cancel_uids.add(uid)
                return True
        return False

    # ------------------------------------------------------------------
    # scheduling
    # ------------------------------------------------------------------

    def _complete(self, req: Request, tokens: List[int],
                  admitted_step: int, status: str = STATUS_OK,
                  retries: int = 0) -> None:
        # a fork primary that terminates BEFORE activation (shed,
        # deadline, cancel, instant-EOS, budget-1) never spawned its
        # members — fan their completions out here with the same
        # outcome, exactly as N independent submits would resolve.
        # (Post-activation, members live as their own requests and the
        # primary carries fork=1.) Each member passes the journal-acked
        # check itself, so replay stays exactly-once per uid.
        members: List[Request] = []
        if req.fork > 1:
            members = [dataclasses.replace(req, uid=req.uid + i, fork=1)
                       for i in range(1, req.fork)]
            req = dataclasses.replace(req, fork=1)
        prior = self._journal_acked.get(req.uid)
        if members:
            for m in members:
                self._complete(m, list(tokens), admitted_step,
                               status=status, retries=retries)
        if prior is not None:
            # already delivered by a previous incarnation: the
            # journaled ack is the authoritative result (exactly-once
            # semantics) — serve it, never ack twice
            self._completions[req.uid] = prior
            return
        last = tokens[-1] if tokens else None
        if status == STATUS_OK:
            reason = ("eos" if self.eos_id is not None
                      and last == self.eos_id else "length")
        else:
            reason = status
        completion = Completion(
            uid=req.uid, prompt_len=len(req.prompt),
            tokens=np.asarray(tokens, np.int32), finish_reason=reason,
            admitted_step=admitted_step, finished_step=self._clock,
            status=status, retries=retries, t_submit=req.t_submit,
            t_first_chunk=req.t_first_chunk,
            t_first_token=req.t_first_token, t_tokens=req.t_tokens)
        if self.journal is not None:
            # ack-ahead: the delivery record hits stable storage before
            # the completion becomes observable; a crash between the
            # two re-delivers the journaled ack on recovery
            self.journal.append(ack_record(completion))
            self._journal_acked[req.uid] = completion
        self._completions[req.uid] = completion

    def _release_hold(self, slot: int) -> None:
        """Drop the cache pins (paged-KV refcounts) the slot's request
        acquired at hit admission — called on every slot teardown."""
        hold = self._cache_hold[slot]
        if hold is not None:
            self._cache_hold[slot] = None
            self.cache.release(hold)

    def _sync_cache_stats(self) -> None:
        """Mirror cache counters into EngineStats as deltas since the
        last reset (the cache itself survives reset)."""
        if self.cache is None:
            return
        c, b = self.cache.counters(), self._cache_base
        self.stats.cache_hits = c["hits"] - b["hits"]
        self.stats.cache_misses = c["misses"] - b["misses"]
        self.stats.cache_evictions = c["evictions"] - b["evictions"]

    def _miss(self, kind: str, width: int) -> None:
        """Count an admission-program compile the jit cache hasn't seen."""
        key = (kind, width)
        if key not in self._seen_shapes:
            self._seen_shapes.add(key)
            self.stats.prefill_jit_misses += 1

    def _admit_one(self, slot: int, req: Request) -> None:
        """Admit ``req`` into ``slot``: prefill, sample the first
        token, swap the state in. Requests whose budget is a single
        token (or whose first token is EOS) complete at admission and
        never occupy the slot. (The ``admission="per_request"`` path:
        one host-blocking batch-1 prefill — and one jit compile per
        DISTINCT prompt length — plus one slot write per request.)"""
        self._miss("prefill_raw", len(req.prompt))
        logits, st_req = self._prefill(
            self.params, jnp.asarray(req.prompt)[None, :])
        self.stats.prefills += 1
        self.stats.admission_dispatches += 1
        self.stats.admission_token_slots += len(req.prompt)
        self.stats.admission_tokens += len(req.prompt)
        self._key, sub = jax.random.split(self._key)
        with _span(WAIT):
            tok0 = int(self.backend.sample_token(
                logits, self.temperature, sub)[0])
        req.t_first_chunk = req.t_first_token = req.t_tokens = \
            time.perf_counter()
        hit_eos = self.eos_id is not None and tok0 == self.eos_id
        if req.max_new_tokens <= 1 or hit_eos:
            self._complete(req, [tok0], admitted_step=self._clock,
                           retries=self._retry_count.pop(req.uid, 0))
            return
        self.state = self._admit(self.state, st_req, slot)
        self.stats.admission_dispatches += 1
        self._activate_slot(slot, req, tok0)

    def _activate_slot(self, slot: int, req: Request, tok0: int) -> None:
        """Flip a slot whose prompt is fully encoded to decode-active.

        Fork/n-best spawns here: the prompt was encoded ONCE; the N-1
        extra continuations become suspended requests SHARING the one
        post-prefill snapshot (zero-copy on the host — each resume pays
        only its own ``write_slot_state``), then decode independently.
        Greedy decode depends only on (state, tok, pos), so every
        member's token stream is bit-identical to an independent
        submit's. The slot's primary drops to fork=1 so a later
        requeue (quarantine retry) can never re-spawn members."""
        members: List[Request] = []
        if req.fork > 1:
            members = [dataclasses.replace(req, uid=req.uid + i, fork=1)
                       for i in range(1, req.fork)]
            req = dataclasses.replace(req, fork=1)
        spec_k = req.speculate_k
        if spec_k > 0 and self._degraded:
            spec_k = 0               # overload: lookahead disabled; the
            self.stats.spec_disables += 1  # greedy tokens are unchanged
        self._tok[slot] = tok0
        self._pos[slot] = len(req.prompt)
        self._active[slot] = True
        self._remaining[slot] = req.max_new_tokens - 1
        self._spec_k[slot] = spec_k
        self._slot_req[slot] = req
        self._slot_toks[slot] = [tok0]
        self._slot_admitted[slot] = self._clock
        if spec_k > 0:
            self.draft.admit(
                slot, np.concatenate([req.prompt, [tok0]]).astype(np.int32))
        if members:
            snap = self._slot_snapshot(
                slot, self._bucket(int(self._pos[slot])))
            for m in members:
                self._suspended.append(SuspendedRequest(
                    req=m, state=snap, tok=tok0,
                    pos=len(req.prompt),
                    remaining=req.max_new_tokens - 1, toks=[tok0],
                    admitted_step=self._clock, retries=0))
                self.stats.forks += 1
        if self.finite_check and self.max_retries > 0:
            # activation checkpoint: the last-known-good restore point a
            # later numeric fault retries from (one O(k²) snapshot copy)
            self._checkpoint_slot(slot)

    def _merge_rows(self, mask, new, old, start, width: int):
        """Masked state merge, row-ranged when the backend has growing
        KV caches (see step_spec_round); the plain whole-state select
        otherwise — ONE program either way per static width."""
        if self.backend.fixed_size_state:
            return self._select(jnp.asarray(mask), new, old)
        return self._select_rows(jnp.asarray(mask), new, old,
                                 jnp.asarray(start, jnp.int32),
                                 int(width))

    def _slot_snapshot(self, slot: int, rows: int):
        """Per-slot snapshot, row-ranged for the softmax baseline:
        only ``rows`` KV rows are copied (O(W·k) instead of
        O(max_len·k)). Fixed-size-state backends pin the static width
        to ``max_len`` — the slicing is a no-op for them, and a single
        jit program serves every call."""
        w = (self.max_len if self.backend.fixed_size_state
             else min(int(rows), self.max_len))
        return self._snapshot_rows(self.state, jnp.int32(slot), w)

    def _checkpoint_slot(self, slot: int) -> None:
        self._ckpt[slot] = Checkpoint(
            state=self._slot_snapshot(slot,
                                      self._bucket(int(self._pos[slot]))),
            tok=int(self._tok[slot]), pos=int(self._pos[slot]),
            remaining=int(self._remaining[slot]),
            toks=list(self._slot_toks[slot]))
        self._last_ckpt_event[slot] = self._events
        self.stats.checkpoints += 1

    def _admissible(self) -> bool:
        return bool(self._queue) and self._queue[0].arrival <= self._clock

    def _work_waiting(self) -> bool:
        return bool(self._suspended) or self._admissible()

    def _any_ingesting(self) -> bool:
        return any(r is not None for r in self._ingest_req)

    def _slot_free(self, slot: int) -> bool:
        return (not self._active[slot]
                and self._ingest_req[slot] is None
                and not self._quarantined[slot])

    # -- admission ordering: priority first, FIFO within a priority ----

    def _best_queued_idx(self) -> Optional[int]:
        """Index of the best admissible queued request by
        (-priority, arrival, uid); the queue is arrival-sorted so the
        admissible candidates are a prefix."""
        best, best_key = None, None
        for i, r in enumerate(self._queue):
            if r.arrival > self._clock:
                break
            key = (-r.priority, r.arrival, r.uid)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def _pop_admission(self) -> Tuple[str, Any]:
        """Pop the next item to admit — the highest-priority admissible
        request across the queue AND the suspended pool (suspended wins
        ties: it has already paid its prefill). Returns ("resume",
        SuspendedRequest) or ("new", Request)."""
        qi = self._best_queued_idx()
        si, si_key = None, None
        for i, s in enumerate(self._suspended):
            key = (-s.req.priority, s.req.arrival, s.req.uid)
            if si_key is None or key < si_key:
                si, si_key = i, key
        if si is not None and (qi is None or si_key <= (
                -self._queue[qi].priority, self._queue[qi].arrival,
                self._queue[qi].uid)):
            return "resume", self._suspended.pop(si)
        assert qi is not None, "_pop_admission with nothing waiting"
        return "new", self._queue.pop(qi)

    def _resume_into(self, slot: int, susp: SuspendedRequest) -> None:
        """Re-admit a suspended request: ONE ``write_slot_state`` copy
        of its O(k²) snapshot plus scalar bookkeeping. Greedy decode
        depends only on (state, tok, pos), so the continuation is
        bit-identical to never having been suspended."""
        req = susp.req
        self.state = self._admit(self.state, susp.state, slot)
        spec_k = req.speculate_k
        if spec_k > 0 and self._degraded:
            spec_k = 0
            self.stats.spec_disables += 1
        self._tok[slot] = susp.tok
        self._pos[slot] = susp.pos
        self._active[slot] = True
        self._remaining[slot] = susp.remaining
        self._spec_k[slot] = spec_k
        self._slot_req[slot] = req
        self._slot_toks[slot] = list(susp.toks)
        self._slot_admitted[slot] = susp.admitted_step
        self._retry_count[req.uid] = susp.retries
        if spec_k > 0:
            self.draft.admit(slot, np.concatenate(
                [req.prompt, susp.toks]).astype(np.int32))
        if self.finite_check and self.max_retries > 0:
            # the incoming snapshot IS the slot's last-known-good state
            self._ckpt[slot] = Checkpoint(
                state=susp.state, tok=susp.tok, pos=susp.pos,
                remaining=susp.remaining, toks=list(susp.toks))
            self._last_ckpt_event[slot] = self._events
        self.stats.resumes += 1

    def preempt(self, slot: int) -> SuspendedRequest:
        """Swap the active request out of ``slot`` into a host-side
        :class:`SuspendedRequest` — one O(k²) ``snapshot_state`` copy
        plus scalar bookkeeping (the paper's fixed-size representation
        is what makes this a few-KB move instead of a KV-cache
        migration). The slot frees immediately; the suspended request
        rejoins the admission pool and continues bit-identically."""
        req = self._slot_req[slot]
        assert self._active[slot] and req is not None, slot
        susp = SuspendedRequest(
            req=req,
            state=self._slot_snapshot(slot,
                                      self._bucket(int(self._pos[slot]))),
            tok=int(self._tok[slot]), pos=int(self._pos[slot]),
            remaining=int(self._remaining[slot]),
            toks=list(self._slot_toks[slot]),
            admitted_step=self._slot_admitted[slot],
            retries=self._retry_count.get(req.uid, 0))
        if self._spec_k[slot] > 0:
            self.draft.release(slot)
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self._spec_k[slot] = 0
        self._active[slot] = False
        self._ckpt.pop(slot, None)
        self._release_hold(slot)   # the snapshot owns its own rows now
        self._suspended.append(susp)
        self.stats.preemptions += 1
        return susp

    def _peek_waiting_priority(self) -> Optional[int]:
        best = None
        for r in self._queue:
            if r.arrival > self._clock:
                break
            if best is None or r.priority > best:
                best = r.priority
        for s in self._suspended:
            if best is None or s.req.priority > best:
                best = s.req.priority
        return best

    def _preempt_pass(self) -> None:
        """Priority preemption: when the pool is saturated and a waiting
        item outranks a running one, suspend victims — lowest (priority,
        progress) decode-active slots first — until every strictly-
        higher-priority waiting item has a slot to land in."""
        waiting = sorted(
            [r.priority for r in self._queue if r.arrival <= self._clock]
            + [s.req.priority for s in self._suspended], reverse=True)
        idx = sum(self._slot_free(s) for s in range(self.n_slots))
        while idx < len(waiting):
            victims = [s for s in range(self.n_slots)
                       if self._active[s] and self._slot_req[s] is not None]
            if not victims:
                return
            victim = min(victims, key=lambda s: (
                self._slot_req[s].priority, len(self._slot_toks[s]), s))
            if self._slot_req[victim].priority >= waiting[idx]:
                return
            self.preempt(victim)
            idx += 1

    def _admit_pass(self, policy: str) -> None:
        if policy == "static":
            # batch-synchronous baseline: wait for the whole batch
            if self.admission == "per_request" and self._active.any():
                return
            if self.admission != "per_request" and (
                    self._active.any() or self._any_ingesting()):
                return
        if not self._work_waiting():
            return
        pass_idx = self._admit_passes
        self._admit_passes += 1
        if (self.injector is not None
                and self.injector.drops_admission(pass_idx)):
            return                    # chaos: this wave never happens
        if policy == "continuous":
            self._preempt_pass()
        if self.admission == "per_request":
            for slot in range(self.n_slots):
                # keep feeding the same slot while requests complete at
                # admission (gen_len=1 / instant EOS never occupy it)
                while self._slot_free(slot) and self._work_waiting():
                    kind, item = self._pop_admission()
                    if kind == "resume":
                        self._resume_into(slot, item)
                    else:
                        self._admit_one(slot, item)
            return

        # batched admission: fill EVERY free slot from the admission
        # pool (resumes land directly; new requests join the ingest
        # wave), then encode the wave's first chunks in ONE bucket-
        # padded varlen prefill dispatch. Loop because requests
        # completing at admission (gen_len=1 / instant EOS) free their
        # slot within the same pass at the same logical clock.
        while self._work_waiting():
            newly, resumed, cache_hits = [], 0, 0
            for slot in range(self.n_slots):
                if not self._slot_free(slot) or not self._work_waiting():
                    continue
                kind, item = self._pop_admission()
                if kind == "resume":
                    self._resume_into(slot, item)
                    resumed += 1
                    continue
                self._ingest_req[slot] = item
                self._ingest_cursor[slot] = 0
                hit = None
                if (self.cache is not None
                        and len(item.prompt) > self.cache.chunk):
                    hit = self.cache.match(item.prompt)
                if hit is None:
                    newly.append(slot)
                    continue
                # cache-hit admission: ONE slot write lands the whole
                # cached prefix (O(k²) for fixed-size states, O(W·k)
                # block rows for paged softmax) and the cursor jumps to
                # the matched boundary — only the SUFFIX is ever
                # encoded, on the same chunk grid a cold admission
                # would have used, so the tokens are identical (greedy)
                self.state = self._admit(self.state, hit.state,
                                         jnp.int32(slot))
                self._ingest_cursor[slot] = hit.n_tokens
                self._cache_hold[slot] = hit
                self.stats.admission_dispatches += 1
                self.stats.cached_prefix_tokens += hit.n_tokens
                cache_hits += 1
            if newly:
                self._ingest_chunk(newly, first=True)
            elif not (resumed or cache_hits):
                break
        self._sync_cache_stats()

    def _bucket(self, n: int) -> int:
        return min(_pow2_ceil(max(1, n)), self.max_len)

    def _live_chunk(self) -> int:
        """Ingest chunk under load: halves while degraded, so prompt
        ingestion yields the device back to decode segments sooner
        (still a power of two — bucket widths stay on the compiled
        grid)."""
        if not self._degraded:
            return self.prefill_chunk
        return max(min(8, self.prefill_chunk), self.prefill_chunk // 2)

    def _ingest_chunk(self, slots: List[int], *, first: bool) -> None:
        """Consume the next ≤ ``prefill_chunk`` prompt tokens of every
        ingesting slot in ``slots`` with ONE device dispatch.

        ``first=True`` rows start from nothing: the wave is encoded by
        ``lm.prefill_varlen`` (bucket-padded, per-row masked, bit-exact
        per row) and landed with one masked select. Continuation rows
        advance the live engine state in place through
        ``lm.decode_window_varlen`` — masked rows (every slot NOT in
        this chunk) are inert by construction, so no select is needed.

        Length-1 prompts are carved out of the wave and encoded by the
        exact-shape batch-1 prefill: a single-token forward is the one
        shape where XLA lowers the unpadded projections differently
        (gemv) from the padded bucket (gemm), so padding it would break
        the bit-identity contract with the per-request path (the
        lm.prefill_varlen caveat, pinned by tests/test_decode_parity).
        """
        if first:
            ones = [s for s in slots
                    if len(self._ingest_req[s].prompt) == 1]
            for slot in ones:
                req = self._ingest_req[slot]
                self._miss("prefill_raw", 1)
                logits, st_req = self._prefill(
                    self.params, jnp.asarray(req.prompt)[None, :])
                self.state = self._admit(self.state, st_req, slot)
                self.stats.prefills += 1
                self.stats.admission_dispatches += 2
                self.stats.admission_token_slots += 1
                self.stats.admission_tokens += 1
                self._ingest_cursor[slot] = 1
                with _span(WAIT):
                    logits = np.asarray(logits)
                req.t_first_chunk = time.perf_counter()
                self._finish_ingest(slot, logits[0])
            slots = [s for s in slots if s not in ones]
            if not slots:
                return
        counts = {}
        for slot in slots:
            req = self._ingest_req[slot]
            cur = int(self._ingest_cursor[slot])
            counts[slot] = min(len(req.prompt) - cur, self._live_chunk())
        width = self._bucket(max(counts.values()))
        tokens = np.zeros((self.n_slots, width), np.int32)
        lens = np.zeros((self.n_slots,), np.int32)
        pos0 = np.zeros((self.n_slots,), np.int32)
        for slot in slots:
            req = self._ingest_req[slot]
            cur = int(self._ingest_cursor[slot])
            c = min(counts[slot], width)
            tokens[slot, :c] = req.prompt[cur:cur + c]
            lens[slot] = c
            pos0[slot] = cur

        if first:
            if len(slots) == 1:
                # steady-state: one freed slot refills compactly
                slot = slots[0]
                self._miss("prefill_varlen_one", width)
                last1, self.state = self._prefill_varlen_one(
                    self.params, self.state,
                    jnp.asarray(tokens[slot:slot + 1]),
                    jnp.asarray(lens[slot:slot + 1]), jnp.int32(slot))
                with _span(WAIT):
                    last1 = np.asarray(last1)
                last = np.zeros((self.n_slots,) + last1.shape[1:],
                                last1.dtype)
                last[slot] = last1[0]
                rows = 1
            else:
                self._miss("prefill_varlen", width)
                mask = np.zeros((self.n_slots,), bool)
                mask[slots] = True
                last, self.state = self._prefill_varlen(
                    self.params, self.state, jnp.asarray(tokens),
                    jnp.asarray(lens), jnp.asarray(mask))
                with _span(WAIT):
                    last = np.asarray(last)
                rows = self.n_slots
            self.stats.admission_batches += 1
            self.stats.prefills += len(slots)
            self.stats.prefill_dispatches += 1
            self.stats.admission_dispatches += 1
        else:
            # miss keys name the underlying jit program: recurrent
            # ingest and speculative rewind share _window_varlen, so a
            # width compiled by one is a cache hit for the other
            program = (self._ingest_varlen if self.ingest == "parallel"
                       else self._window_varlen)
            self._miss("ingest_varlen" if self.ingest == "parallel"
                       else "window_varlen", width)
            last, self.state = program(
                self.params, self.state, jnp.asarray(tokens),
                jnp.asarray(pos0), jnp.asarray(lens))
            with _span(WAIT):
                last = np.asarray(last)
            rows = self.n_slots
            self.stats.ingest_chunks += 1
            self.stats.admission_dispatches += 1
            if self._active.any():
                self.stats.ingest_interleaved += 1
        self.stats.admission_token_slots += rows * width
        self.stats.admission_tokens += int(lens.sum())

        t_read = time.perf_counter()
        for slot in slots:
            self._ingest_cursor[slot] += int(lens[slot])
            req = self._ingest_req[slot]
            if req.t_first_chunk is None:
                req.t_first_chunk = t_read
            cur = int(self._ingest_cursor[slot])
            # populate the prefix cache at every full-chunk boundary
            # the ingest crosses (degraded half-chunks land on these
            # boundaries too — _live_chunk stays a divisor). The
            # snapshot is row-ranged to exactly `cur` rows, which is
            # what lets the paged cache split it into content-hashed
            # blocks; `wants` gates the device copy on novelty.
            if (self.cache is not None and cur % self.cache.chunk == 0
                    and self.cache.wants(req.prompt, cur)):
                self.cache.insert(req.prompt, cur,
                                  self._slot_snapshot(slot, cur))
            if cur >= len(req.prompt):
                self._finish_ingest(slot, last[slot])
        self._sync_cache_stats()

    def _ingest_step(self) -> None:
        """One continuation-chunk dispatch across every mid-prompt slot.
        Called once per outer ``run`` iteration, BEFORE the decode
        segment — long-prompt ingestion therefore interleaves with
        decode instead of stalling it."""
        rows = [s for s in range(self.n_slots)
                if self._ingest_req[s] is not None]
        if rows:
            self._ingest_chunk(rows, first=False)

    def _finish_ingest(self, slot: int, logits_row: np.ndarray) -> None:
        """The slot's whole prompt is consumed: sample the first token
        and activate (or complete instantly on budget-1 / EOS)."""
        req = self._ingest_req[slot]
        self._ingest_req[slot] = None
        self._ingest_cursor[slot] = 0
        self._key, sub = jax.random.split(self._key)
        with _span(WAIT):
            tok0 = int(self.backend.sample_token(
                jnp.asarray(logits_row)[None], self.temperature, sub)[0])
        req.t_first_token = req.t_tokens = time.perf_counter()
        hit_eos = self.eos_id is not None and tok0 == self.eos_id
        if req.max_new_tokens <= 1 or hit_eos:
            self._complete(req, [tok0], admitted_step=self._clock,
                           retries=self._retry_count.pop(req.uid, 0))
            self._release_hold(slot)
            return
        self._activate_slot(slot, req, tok0)

    def step_segment(self) -> None:
        """Run one ``segment_len``-step scan segment over the PLAIN
        (non-speculative) slots and drain finished ones. Speculative
        slots ride along frozen bit-for-bit (the scan's inactive-slot
        masking) — they advance in :meth:`step_spec_round` instead.
        One device dispatch + one host sync."""
        run_active = self._active & (self._spec_k == 0)
        toks, carry = self._segment(*self._segment_args(run_active))
        # np.array (copy): views of device arrays are read-only and the
        # scheduler mutates these per-slot on admission. Slots masked out
        # of this segment (speculative ones) come back with tok/pos/
        # remaining untouched, but their `active` flag must be restored.
        with _span(WAIT):
            emitted = np.asarray(toks)                  # (S, W)
            self._tok = np.array(carry["tok"])
            self._pos = np.array(carry["pos"])
            self._remaining = np.array(carry["remaining"])
            carried = np.array(carry["active"])
        t_read = time.perf_counter()
        self.state = carry["state"]
        self._active = np.where(run_active, carried, self._active)
        self._key = carry["key"]
        self._clock += self.segment_len
        self.stats.segments += 1
        self.stats.emitted_tokens += int((emitted != PAD_ID).sum())

        for slot in range(self.n_slots):
            if not run_active[slot]:
                continue
            row = emitted[slot]
            self._slot_toks[slot].extend(int(t) for t in row[row != PAD_ID])
            self._slot_req[slot].t_tokens = t_read
            if not self._active[slot]:                  # finished mid-segment
                self._free_slot(slot)

    def _segment_args(self, run_active: np.ndarray) -> tuple:
        return (self.params, self.state, jnp.asarray(self._tok),
                jnp.asarray(self._pos), jnp.asarray(run_active),
                jnp.asarray(self._remaining), self._key)

    def segment_program_text(self) -> str:
        """Optimised HLO text of the decode-segment program that
        :meth:`step_segment` dispatches, compiled for the engine's
        current shapes (shows, e.g., whether the Pallas decode kernels
        are in it)."""
        return self._segment.lower(
            *self._segment_args(self._active)).compile().as_text()

    def _free_slot(self, slot: int) -> None:
        req = self._slot_req[slot]
        self._complete(req, self._slot_toks[slot],
                       admitted_step=self._slot_admitted[slot],
                       retries=self._retry_count.pop(req.uid, 0))
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        if self._spec_k[slot] > 0:
            self.draft.release(slot)
        self._spec_k[slot] = 0
        self._active[slot] = False
        self._ckpt.pop(slot, None)
        self._release_hold(slot)

    # ------------------------------------------------------------------
    # lifecycle & fault tolerance
    # ------------------------------------------------------------------

    def _evict(self, slot: int, status: str) -> None:
        """Complete a slot's request NOW with its partial tokens and
        free the slot. The state row is simply abandoned — inactive
        rows are masked bit-for-bit inside every program, so no device
        work is needed to reclaim it."""
        req = self._slot_req[slot] or self._ingest_req[slot]
        toks = (list(self._slot_toks[slot])
                if self._slot_req[slot] is not None else [])
        admitted = (self._slot_admitted[slot]
                    if self._slot_req[slot] is not None else -1)
        self._complete(req, toks, admitted_step=admitted, status=status,
                       retries=self._retry_count.pop(req.uid, 0))
        if self._slot_req[slot] is not None and self._spec_k[slot] > 0:
            self.draft.release(slot)
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self._spec_k[slot] = 0
        self._active[slot] = False
        self._ingest_req[slot] = None
        self._ingest_cursor[slot] = 0
        self._ckpt.pop(slot, None)
        self._release_hold(slot)

    def _set_degraded(self, on: bool, pressure: float) -> None:
        self._degraded = on
        self.stats.degrade_transitions += 1
        self.stats.degrade_events.append({
            "clock": self._clock, "degraded": on,
            "pressure": round(pressure, 3)})
        if on:
            # live speculative slots convert to plain greedy decode —
            # speculation emits the exact plain-greedy sequence, so
            # dropping it sheds lookahead FLOPs, never tokens
            for slot in range(self.n_slots):
                if self._active[slot] and self._spec_k[slot] > 0:
                    self.draft.release(slot)
                    self._spec_k[slot] = 0
                    self.stats.spec_disables += 1

    def _lifecycle_pass(self) -> None:
        """Scheduling-boundary housekeeping: drain cancellations,
        enforce deadlines everywhere a request can wait or run, and
        flip overload degradation (with hysteresis)."""
        if self._cancel_uids:
            for slot in range(self.n_slots):
                req = self._slot_req[slot] or self._ingest_req[slot]
                if req is not None and req.uid in self._cancel_uids:
                    self._cancel_uids.discard(req.uid)
                    self.stats.cancelled += 1
                    self._evict(slot, STATUS_CANCELLED)
        for r in [r for r in self._queue if r.deadline_s is not None
                  and r.deadline_s <= self._clock]:
            self._queue.remove(r)
            self.stats.deadline_evictions += 1
            self._complete(r, [], admitted_step=-1,
                           status=STATUS_DEADLINE)
        for s in [s for s in self._suspended
                  if s.req.deadline_s is not None
                  and s.req.deadline_s <= self._clock]:
            self._suspended.remove(s)
            self.stats.deadline_evictions += 1
            self._complete(s.req, s.toks, admitted_step=s.admitted_step,
                           status=STATUS_DEADLINE, retries=s.retries)
        for slot in range(self.n_slots):
            req = self._slot_req[slot] or self._ingest_req[slot]
            if (req is not None and req.deadline_s is not None
                    and req.deadline_s <= self._clock):
                self.stats.deadline_evictions += 1
                self._evict(slot, STATUS_DEADLINE)
        if self.degrade_threshold is not None:
            waiting = len(self._suspended) + sum(
                1 for r in self._queue if r.arrival <= self._clock)
            pressure = waiting / self.n_slots
            if not self._degraded and pressure >= self.degrade_threshold:
                self._set_degraded(True, pressure)
            elif self._degraded and pressure <= self.degrade_threshold / 2:
                self._set_degraded(False, pressure)

    def _quarantine(self, slot: int) -> None:
        """A non-finite state was detected in ``slot``: quarantine the
        slot for the rest of the run (its NaNs stay put, frozen by the
        same row masking that isolates inactive slots — neighbours are
        bit-identical to a fault-free run) and retry its request from
        the last good checkpoint on a fresh slot, up to ``max_retries``
        times, else complete it ``status="failed"``."""
        self.stats.quarantined += 1
        self._quarantined[slot] = True
        req = self._slot_req[slot] or self._ingest_req[slot]
        ckpt = self._ckpt.pop(slot, None)
        if req is not None:
            used = self._retry_count.get(req.uid, 0)
            if used < self.max_retries:
                self._retry_count[req.uid] = used + 1
                self.stats.retries += 1
                if ckpt is not None:
                    self._suspended.append(SuspendedRequest(
                        req=req, state=ckpt.state, tok=ckpt.tok,
                        pos=ckpt.pos, remaining=ckpt.remaining,
                        toks=list(ckpt.toks),
                        admitted_step=self._slot_admitted[slot],
                        retries=used + 1))
                else:
                    # poisoned mid-ingest: nothing emitted yet, so the
                    # last good state is the empty start — requeue
                    bisect.insort(self._queue, req,
                                  key=lambda r: (r.arrival, r.uid))
            else:
                toks = list(ckpt.toks) if ckpt is not None else []
                self.stats.failed += 1
                self._retry_count.pop(req.uid, None)
                self._complete(
                    req, toks, status=STATUS_FAILED, retries=used,
                    admitted_step=(self._slot_admitted[slot]
                                   if self._slot_req[slot] is not None
                                   else -1))
        if self._slot_req[slot] is not None and self._spec_k[slot] > 0:
            self.draft.release(slot)
        self._slot_req[slot] = None
        self._slot_toks[slot] = []
        self._spec_k[slot] = 0
        self._active[slot] = False
        self._ingest_req[slot] = None
        self._ingest_cursor[slot] = 0
        self._release_hold(slot)

    def _post_event(self) -> None:
        """Segment/round boundary: chaos injection, the fused
        ``jnp.isfinite`` probe + quarantine, periodic checkpoints of
        healthy active slots. Runs after EVERY decode segment and
        speculative round — the engine's scheduling quantum, so the
        per-token cost is amortized over ``segment_len`` steps."""
        ev = self._events
        if self.injector is not None and self.injector.crashes(ev):
            # process death at a scheduling boundary: nothing after
            # this line runs, so everything not journaled/durably
            # checkpointed by now is what recovery must reconstruct
            raise InjectedCrash(ev)
        self._events += 1
        if self.injector is not None:
            for slot in self.injector.nan_slots(ev):
                self.state = self._poison(self.state, jnp.int32(slot))
            self._clock += self.injector.extra_delay(ev)
        if self.finite_check:
            occupied = self._active | np.asarray(
                [r is not None for r in self._ingest_req])
            if occupied.any():
                finite = self._finite(self.state)
                with _span(WAIT):
                    finite = np.asarray(finite)
                self.stats.finite_checks += 1
                for slot in np.nonzero(occupied & ~finite
                                       & ~self._quarantined)[0]:
                    self._quarantine(int(slot))
        if (self.checkpoint_interval > 0 and self.finite_check
                and self.max_retries > 0):
            for slot in range(self.n_slots):
                if (self._active[slot] and not self._quarantined[slot]
                        and self._events - self._last_ckpt_event[slot]
                        >= self.checkpoint_interval):
                    self._checkpoint_slot(slot)
        if (self._ckpt_mgr is not None and self.checkpoint_every > 0
                and self._events % self.checkpoint_every == 0):
            self.save_checkpoint()

    def _fail_all_pending(self) -> None:
        """Every slot is quarantined: nothing can ever run again — fail
        the remaining work instead of spinning."""
        for s in self._suspended:
            self.stats.failed += 1
            self._complete(s.req, s.toks, admitted_step=s.admitted_step,
                           status=STATUS_FAILED, retries=s.retries)
        self._suspended = []
        for r in self._queue:
            self.stats.failed += 1
            self._complete(r, [], admitted_step=-1, status=STATUS_FAILED)
        self._queue = []

    # ------------------------------------------------------------------
    # durability: engine checkpoints + journal replay
    # ------------------------------------------------------------------

    @staticmethod
    def _req_to_dict(req: Request) -> Dict:
        return {"uid": int(req.uid),
                "prompt": np.asarray(req.prompt, np.int32).tolist(),
                "max_new_tokens": int(req.max_new_tokens),
                "arrival": float(req.arrival),
                "speculate_k": int(req.speculate_k),
                "priority": int(req.priority),
                "deadline_s": (None if req.deadline_s is None
                               else float(req.deadline_s)),
                "fork": int(req.fork)}

    @staticmethod
    def _req_from_dict(d: Dict) -> Request:
        return Request(uid=d["uid"],
                       prompt=np.asarray(d["prompt"], np.int32),
                       max_new_tokens=d["max_new_tokens"],
                       arrival=d["arrival"],
                       speculate_k=d["speculate_k"],
                       priority=d["priority"],
                       deadline_s=d["deadline_s"],
                       fork=d.get("fork", 1))

    @staticmethod
    def _snapshot_kv_rows(snap) -> int:
        """KV time-axis width of a (possibly row-ranged) snapshot, -1
        when it has no KV caches (fixed-size states) — recorded in the
        checkpoint manifest so restore can rebuild shape templates."""
        from repro.models.attention import AttnState
        widths: List[int] = []

        def probe(st):
            if isinstance(st, AttnState) and st.k_cache is not None:
                widths.append(int(st.k_cache.shape[st.k_cache.ndim - 3]))
            return st

        jax.tree.map(probe, snap,
                     is_leaf=lambda x: isinstance(x, AttnState))
        return widths[0] if widths else -1

    def _snapshot_template(self, rows: int):
        """ShapeDtypeStruct pytree of a ``rows``-row slot snapshot
        (``jax.eval_shape`` — nothing allocated)."""
        w = self.max_len if rows is None or rows < 0 else int(rows)
        w = max(1, min(w, self.max_len))
        return jax.eval_shape(
            lambda s: self.backend.snapshot_state_rows(
                s, jnp.int32(0), w), self.state)

    def save_checkpoint(self, step: Optional[int] = None) -> int:
        """Write a durable whole-engine checkpoint via the atomic
        pytree writer. The device tree holds the slot batch, the RNG
        key, and every suspended/last-good snapshot — for the paper's
        fixed-size backends that is O(S·k²) floats per layer however
        long the contexts are (the softmax baseline writes its whole
        KV cache); everything host-side (queues, per-slot scalars,
        completions, stats, the logical clock) rides in the manifest's
        ``extra`` dict. ``journal_seq`` records the journal position
        the checkpoint captures, so recovery replays only later
        records. Requires ``checkpoint_dir``; returns the step id
        (the engine's event counter unless given)."""
        if self._ckpt_mgr is None:
            raise ValueError("engine has no checkpoint_dir configured")
        step = self._events if step is None else int(step)
        tree = {
            "key": self._key,
            "slot_ckpt": {str(s): c.state
                          for s, c in sorted(self._ckpt.items())},
            "state": self.state,
            "suspended": tuple(s.state for s in self._suspended),
        }
        extra = {
            "journal_seq": (self.journal.seq
                            if self.journal is not None else 0),
            "clock": int(self._clock),
            "events": int(self._events),
            "admit_passes": int(self._admit_passes),
            "next_uid": int(self._next_uid),
            "tok": self._tok.tolist(), "pos": self._pos.tolist(),
            "active": [bool(a) for a in self._active],
            "remaining": self._remaining.tolist(),
            "spec_k": self._spec_k.tolist(),
            "slot_req": [None if r is None else self._req_to_dict(r)
                         for r in self._slot_req],
            "slot_toks": [list(t) for t in self._slot_toks],
            "slot_admitted": [int(a) for a in self._slot_admitted],
            "ingest_req": [None if r is None else self._req_to_dict(r)
                           for r in self._ingest_req],
            "ingest_cursor": self._ingest_cursor.tolist(),
            "queue": [self._req_to_dict(r) for r in self._queue],
            "suspended": [
                {"req": self._req_to_dict(s.req), "tok": int(s.tok),
                 "pos": int(s.pos), "remaining": int(s.remaining),
                 "toks": list(s.toks),
                 "admitted_step": int(s.admitted_step),
                 "retries": int(s.retries)}
                for s in self._suspended],
            "slot_ckpt": {
                str(s): {"tok": int(c.tok), "pos": int(c.pos),
                         "remaining": int(c.remaining),
                         "toks": list(c.toks)}
                for s, c in sorted(self._ckpt.items())},
            # row-ranged snapshot widths (KV time-axis rows; -1 for
            # fixed-size states) — restore rebuilds shape templates
            # from these, so a ranged snapshot round-trips exactly
            "suspended_rows": [self._snapshot_kv_rows(s.state)
                               for s in self._suspended],
            "slot_ckpt_rows": {
                str(s): self._snapshot_kv_rows(c.state)
                for s, c in sorted(self._ckpt.items())},
            "completions": [ack_record(c)
                            for _, c in sorted(self._completions.items())],
            "quarantined": [bool(q) for q in self._quarantined],
            "retry_count": {str(u): int(n)
                            for u, n in self._retry_count.items()},
            "last_ckpt_event": self._last_ckpt_event.tolist(),
            "cancel_uids": sorted(int(u) for u in self._cancel_uids),
            "degraded": bool(self._degraded),
            "stats": dataclasses.asdict(self.stats),
            "seen_shapes": sorted(list(k) for k in self._seen_shapes),
        }
        self._ckpt_mgr.save(step, tree, extra, blocking=True)
        return step

    def restore_checkpoint(self, step: Optional[int] = None) -> int:
        """Restore this engine from its checkpoint directory (newest
        retained step by default, falling back past corrupt ones).
        The engine must be constructed with the same (params, cfg,
        n_slots, max_len) the checkpoint was written under — the
        device-tree structure is config-derived. Returns the journal
        sequence number the checkpoint captured (the replay start)."""
        if self._ckpt_mgr is None:
            raise ValueError("engine has no checkpoint_dir configured")

        def like_fn(extra):
            like = {"key": self._key, "slot_ckpt": {}, "state": self.state,
                    "suspended": ()}
            # snapshots may be row-ranged (only the written KV rows
            # were saved); rebuild each template at its recorded width.
            # pre-ranged checkpoints lack the width lists → full width.
            susp_rows = extra.get(
                "suspended_rows", [-1] * len(extra["suspended"]))
            ck_rows = extra.get("slot_ckpt_rows", {})
            like["suspended"] = tuple(self._snapshot_template(w)
                                      for w in susp_rows)
            like["slot_ckpt"] = {
                k: self._snapshot_template(ck_rows.get(k, -1))
                for k in sorted(extra["slot_ckpt"])}
            return like

        tree, extra, ckpt_step = self._ckpt_mgr.restore_with(
            like_fn, step)
        dev = lambda t: jax.tree.map(jnp.asarray, t)  # noqa: E731
        self.state = dev(tree["state"])
        self._key = jnp.asarray(tree["key"])
        self._clock = extra["clock"]
        self._events = extra["events"]
        self._admit_passes = extra["admit_passes"]
        self._next_uid = extra["next_uid"]
        self._tok = np.asarray(extra["tok"], np.int32)
        self._pos = np.asarray(extra["pos"], np.int32)
        self._active = np.asarray(extra["active"], bool)
        self._remaining = np.asarray(extra["remaining"], np.int32)
        self._spec_k = np.asarray(extra["spec_k"], np.int32)
        self._slot_req = [None if d is None else self._req_from_dict(d)
                          for d in extra["slot_req"]]
        self._slot_toks = [list(t) for t in extra["slot_toks"]]
        self._slot_admitted = list(extra["slot_admitted"])
        self._ingest_req = [None if d is None else self._req_from_dict(d)
                            for d in extra["ingest_req"]]
        self._ingest_cursor = np.asarray(extra["ingest_cursor"], np.int64)
        self._queue = [self._req_from_dict(d) for d in extra["queue"]]
        self._suspended = [
            SuspendedRequest(
                req=self._req_from_dict(d["req"]),
                state=dev(tree["suspended"][i]), tok=d["tok"],
                pos=d["pos"], remaining=d["remaining"],
                toks=list(d["toks"]), admitted_step=d["admitted_step"],
                retries=d["retries"])
            for i, d in enumerate(extra["suspended"])]
        self._ckpt = {
            int(k): Checkpoint(state=dev(tree["slot_ckpt"][k]),
                               tok=d["tok"], pos=d["pos"],
                               remaining=d["remaining"],
                               toks=list(d["toks"]))
            for k, d in extra["slot_ckpt"].items()}
        self._completions = {rec["uid"]: completion_from_ack(rec)
                             for rec in extra["completions"]}
        self._quarantined = np.asarray(extra["quarantined"], bool)
        self._retry_count = {int(u): n
                             for u, n in extra["retry_count"].items()}
        self._last_ckpt_event = np.asarray(
            extra["last_ckpt_event"], np.int64)
        self._cancel_uids = set(extra["cancel_uids"])
        self._degraded = extra["degraded"]
        self.stats = EngineStats(**extra["stats"])
        self._seen_shapes = {tuple(k) for k in extra["seen_shapes"]}
        # speculative draft providers hold host/device state per slot;
        # it is fully reconstructible from (prompt + emitted tokens),
        # so re-admit rather than serialize (ModelDraft re-prefills the
        # context — deterministic, and cheap for fixed-size states)
        if self.draft is not None:
            self.draft.reset()
            for slot in range(self.n_slots):
                if self._active[slot] and self._spec_k[slot] > 0:
                    req = self._slot_req[slot]
                    self.draft.admit(slot, np.concatenate(
                        [req.prompt, self._slot_toks[slot]]
                    ).astype(np.int32))
        return extra.get("journal_seq", 0)

    # -- prefix-cache persistence --------------------------------------

    def cache_template(self, n_tokens: int):
        """ShapeDtypeStruct pytree of an ``n_tokens``-row cached state —
        the ``template_fn`` a :class:`PrefixCache` needs to load arrays
        back off disk (block payloads and row-ranged state entries share
        the row-ranged snapshot structure)."""
        return self._snapshot_template(int(n_tokens))

    def save_cache(self, directory, step: Optional[int] = None) -> int:
        """Persist the prefix cache through the atomic checkpoint
        writer into ``directory`` (a path or a CheckpointManager —
        use a SEPARATE directory from the engine's checkpoints).
        Returns the step id written."""
        if self.cache is None:
            raise ValueError("engine has no prefix cache configured")
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(directory, keep=1))
        step = self._events if step is None else int(step)
        self.cache.save(mgr, step)
        return step

    def load_cache(self, directory) -> bool:
        """Restore the prefix cache saved by :meth:`save_cache`. A
        missing or corrupt cache file leaves the cache EMPTY and
        returns False — a cold start, never wrong answers."""
        if self.cache is None:
            raise ValueError("engine has no prefix cache configured")
        mgr = (directory if isinstance(directory, CheckpointManager)
               else CheckpointManager(directory, keep=1))
        return self.cache.load(mgr, self.cache_template)

    def _replay_journal(self, from_seq: int = 0) -> None:
        """Re-apply journal records past ``from_seq`` (the position the
        restored checkpoint captured; 0 with no checkpoint). Journaled
        acks are authoritative: their uids are served the recorded
        completion and their submits are NOT re-run — exactly-once
        delivery. Unacked submits re-enter the queue with their
        original uids (journal order is uid order, so engine-side
        monotonicity holds); greedy decode then reproduces their exact
        token streams, because a greedy completion depends only on
        (params, prompt). A cancel journaled while its request was
        mid-flight replays against the re-queued request, so the
        partial tokens the dead incarnation had emitted (but never
        acked) are not reproduced — the ack the caller eventually sees
        is still unique."""
        assert self.journal is not None
        records = self.journal.records()
        for rec in records:
            if rec["t"] == REC_ACK:
                self._journal_acked[rec["uid"]] = completion_from_ack(rec)
        # journaled acks are the delivery record — serve every one,
        # including acks from before the checkpoint horizon
        self._completions.update(self._journal_acked)
        self._replaying = True
        try:
            for rec in records[from_seq:]:
                if rec["t"] == REC_SUBMIT:
                    fork = rec.get("fork", 1)
                    # a forked submit owns uids uid..uid+fork-1; skip
                    # the replay only when EVERY member was delivered
                    if all(rec["uid"] + i in self._journal_acked
                           for i in range(fork)):
                        continue        # already delivered
                    self.submit(np.asarray(rec["prompt"], np.int32),
                                rec["max_new_tokens"],
                                arrival=rec["arrival"],
                                speculate_k=rec["speculate_k"],
                                priority=rec["priority"],
                                deadline_s=rec["deadline_s"],
                                uid=rec["uid"],
                                fork=fork)
                elif rec["t"] == REC_CANCEL:
                    if rec["uid"] in self._journal_acked:
                        continue        # resolved before the crash
                    self.cancel(rec["uid"])
        finally:
            self._replaying = False

    def recover_in_place(self) -> None:
        """Restore the newest durable checkpoint (if any) and replay
        the journal tail past it. After this the engine is at the exact
        logical state of the dead incarnation's last boundary: running
        it to completion yields every outstanding ack bit-identically
        (greedy), with no ack lost or duplicated."""
        from_seq = 0
        if self._ckpt_mgr is not None and self._ckpt_mgr.has_checkpoint():
            from_seq = self.restore_checkpoint()
        if self.journal is not None:
            self._replay_journal(from_seq)

    @classmethod
    def recover(cls, params: Any, cfg: ModelConfig,
                rules: Optional[Rules] = None, *,
                journal: Optional[Any] = None,
                checkpoint_dir: Optional[str] = None,
                **kwargs) -> "DecodeEngine":
        """Build an engine and bring it to the journal+checkpoint
        state — the restart path after a crash. Pass the same engine
        kwargs the dead incarnation used (the checkpoint's device tree
        is config-shaped)."""
        eng = cls(params, cfg, rules, journal=journal,
                  checkpoint_dir=checkpoint_dir, **kwargs)
        eng.recover_in_place()
        return eng

    # ------------------------------------------------------------------
    # speculative rounds
    # ------------------------------------------------------------------

    def step_spec_round(self) -> None:
        """One draft/verify round, batched across every speculative slot.

        1. The draft provider proposes K tokens per speculative slot.
        2. ONE ``decode_window`` launch verifies the (K+1)-token windows
           [current input, d₁..d_K] at every slot's own position and
           returns the target's greedy token after each window prefix.
        3. Per slot, the longest draft prefix matching the target's
           greedy tokens is accepted and the target's own next token is
           appended — 1..K+1 tokens of the exact plain-greedy sequence.
        4. Slots that accepted the whole window commit the verify state
           via one masked select; partial acceptors rewind by
           re-advancing their accepted prefix from the pre-round
           snapshot (``snapshot_state`` → ``decode_window`` →
           ``restore_state``). The paper's fixed-size states make both
           paths O(k²)-per-layer copies.

        Rewinds are BATCHED: accepted prefixes differ in length across
        slots, and the varlen masked window advances each rewinding row
        by exactly its own accepted count from the pre-round state — ONE
        ``decode_window_varlen`` dispatch plus one masked select per
        round, however many slots rewind (the per-slot path was 3
        dispatches per rewinding slot, one compiled program per distinct
        prefix length). ``spec_rewind_dispatches`` counts the launches;
        tests assert it equals ``spec_rewind_rounds``.
        """
        spec = self._active & (self._spec_k > 0)
        slots = np.nonzero(spec)[0]
        assert slots.size, "step_spec_round with no speculative slot"
        w = int(self._spec_k[slots].max())

        drafts = np.asarray(
            self.draft.propose(self._tok, self._pos, spec, w), np.int32)
        window = np.zeros((self.n_slots, w + 1), np.int32)
        window[:, 0] = self._tok
        window[:, 1:] = drafts

        state_pre = self.state
        pos_pre = self._pos.copy()    # row-range starts for the merges
        greedy, st_verify = self._verify(
            self.params, state_pre, jnp.asarray(window),
            jnp.asarray(self._pos))
        greedy = np.asarray(greedy)                     # (S, w+1)
        t_read = time.perf_counter()
        # chaos hook: a sabotaged round accepts ZERO draft tokens, so
        # every continuing slot takes the rewind path. The emitted token
        # is still g[0] — the target's own greedy next token — so the
        # output sequence stays bit-identical; only the lookahead is
        # wasted (exactly the blast radius a real draft failure has).
        sabotaged = (self.injector is not None
                     and self.injector.sabotages_round(
                         self.stats.spec_rounds))
        self.stats.spec_rounds += 1

        # -- host-side acceptance, budget and EOS resolution per slot --
        commit_full = np.zeros((self.n_slots,), bool)
        rewinds = []                   # (slot, n_consumed) re-advances
        max_emitted = 1
        for slot in slots:
            slot = int(slot)
            ks = int(self._spec_k[slot])
            g = greedy[slot]
            a = 0
            while not sabotaged and a < ks and drafts[slot, a] == g[a]:
                a += 1
            self.stats.spec_drafted += ks
            self.stats.spec_accepted += a

            # emit g[0..a] one at a time under the segment stop rules:
            # budget decrements per token, EOS stops inclusively
            emitted = []
            finished = False
            for t in g[:a + 1]:
                emitted.append(int(t))
                self._remaining[slot] -= 1
                if ((self.eos_id is not None and int(t) == self.eos_id)
                        or self._remaining[slot] <= 0):
                    finished = True
                    break
            self._slot_toks[slot].extend(emitted)
            self._slot_req[slot].t_tokens = t_read
            self.stats.spec_emitted += len(emitted)
            max_emitted = max(max_emitted, len(emitted))

            if finished:
                self._free_slot(slot)
                continue
            # continuing: the slot consumed window[:a+1]; its next input
            # is the last emitted token (the target's own next token)
            n_cons = a + 1
            assert len(emitted) == n_cons
            self.draft.commit(slot, np.asarray(emitted, np.int32))
            self._tok[slot] = emitted[-1]
            if a == w:
                commit_full[slot] = True    # verify state is exact
            else:
                rewinds.append((slot, n_cons))
            self._pos[slot] += n_cons

        # -- apply state: masked select for full acceptors, ONE batched
        #    varlen re-advance from the pre-round state for partials.
        #    Both merges are ROW-RANGED for the softmax baseline: the
        #    round wrote rows [pos_pre, pos_pre+width) per slot, rows
        #    below are bitwise-equal in both operands and rows above
        #    are never read before rewritten — so the select moves
        #    O(W·k) bytes instead of the whole (S, max_len, Hkv, Dh)
        #    caches (fixed-size states keep the plain O(k²) select). --
        if commit_full.any():
            self.state = self._merge_rows(commit_full, st_verify,
                                          self.state, pos_pre, w + 1)
        if rewinds:
            wr = max(n for _, n in rewinds)
            tokens = np.zeros((self.n_slots, wr), np.int32)
            lens = np.zeros((self.n_slots,), np.int32)
            pos0 = np.zeros((self.n_slots,), np.int32)
            mask = np.zeros((self.n_slots,), bool)
            for slot, n_cons in rewinds:
                tokens[slot, :n_cons] = window[slot, :n_cons]
                lens[slot] = n_cons
                pos0[slot] = self._pos[slot] - n_cons
                mask[slot] = True
            self._miss("window_varlen", wr)
            _, st_r = self._window_varlen(
                self.params, state_pre, jnp.asarray(tokens),
                jnp.asarray(pos0), jnp.asarray(lens))
            self.state = self._merge_rows(mask, st_r, self.state,
                                          pos_pre, wr)
            self.stats.spec_rewinds += len(rewinds)
            self.stats.spec_rewind_rounds += 1
            self.stats.spec_rewind_dispatches += 1

        self._clock += max_emitted

    def has_work(self) -> bool:
        """Anything queued, suspended, ingesting, or decode-active?"""
        return bool(self._queue or self._suspended or self._active.any()
                    or self._any_ingesting())

    def queue_depth(self) -> int:
        """Requests waiting in the admission queue (fleet-level bounded
        queues count waiting work across slot groups through this)."""
        return len(self._queue)

    def shed_queued(self, uid: int) -> bool:
        """Shed a QUEUED request by uid (``status="shed"``): the fleet
        scheduler's cross-group eviction primitive — a fleet-wide
        bounded queue may pick its victim in a different slot group
        than the arrival. Returns False if the uid is not queued."""
        for i, r in enumerate(self._queue):
            if r.uid == uid:
                self._queue.pop(i)
                self._shed(r)
                return True
        return False

    def completions(self) -> List[Completion]:
        """Completions recorded so far, in uid order."""
        return [self._completions[u] for u in sorted(self._completions)]

    def progress(self) -> Progress:
        """Each live request's progress, and the completions since the
        last call (in the order they completed).

        ``requests`` maps the uid of every request in a slot, decoding
        or mid-prompt, and of every request in ``done``, to its
        :class:`RequestProgress`. Suspended and queued requests are not
        in it. The times are ``time.perf_counter`` readings the engine
        took as it went: one at submit, one after the host read of each
        admission or ingest dispatch, one after each first token's read,
        and one after each segment's token read. Reading them here costs
        nothing more."""
        seen: Dict[int, RequestProgress] = {}
        for slot in range(self.n_slots):
            req = self._slot_req[slot]
            if req is not None:
                seen[req.uid] = RequestProgress.of(
                    req, len(self._slot_toks[slot]), len(req.prompt))
                continue
            req = self._ingest_req[slot]
            if req is not None:
                seen[req.uid] = RequestProgress.of(
                    req, 0, int(self._ingest_cursor[slot]))
        done: List[Completion] = []
        if len(self._completions) > self._n_progressed:
            done = list(itertools.islice(self._completions.values(),
                                         self._n_progressed, None))
            self._n_progressed = len(self._completions)
            for c in done:
                seen[c.uid] = RequestProgress.of(c, len(c.tokens),
                                                 c.prompt_len)
        return Progress(seen, done)

    def step(self, policy: str = "continuous") -> bool:
        """ONE outer scheduling iteration: lifecycle pass (cancels,
        deadlines, degradation), admission pass (preempt + resume +
        admit), one continuation ingest chunk (if any slot is
        mid-prompt), one slot-masked segment for plain slots, one
        draft/verify round for speculative slots — with the numeric-
        fault probe at every segment/round boundary. Returns whether
        work remains (the fleet scheduler interleaves groups by calling
        this round-robin). No-op returning False when idle."""
        assert policy in ("continuous", "static"), policy
        if not self.has_work():
            return False
        with _span(LIFECYCLE):
            self._lifecycle_pass()
        with _span(ADMIT):
            self._admit_pass(policy)
        if self._any_ingesting():
            with _span(INGEST):
                self._ingest_step()
        if not self._active.any():
            if self._any_ingesting():
                return self.has_work()
            if self._quarantined.all() and (self._queue
                                            or self._suspended):
                self._fail_all_pending()
                return self.has_work()
            if self._work_waiting():
                # work is waiting but nothing was admitted (chaos-
                # dropped wave, or every free slot quarantined):
                # stall one segment and try again
                self._clock += self.segment_len
                return self.has_work()
            if self._queue:
                # the queue head is in the future: fast-forward the
                # logical clock to it (whole segments, to stay on
                # the segment grid)
                ahead = self._queue[0].arrival - self._clock
                skip = max(1, -int(-ahead // self.segment_len))
                self._clock += skip * self.segment_len
            return self.has_work()
        if (self._active & (self._spec_k == 0)).any():
            with _span(SEGMENT):
                self.step_segment()
            with _span(POST):
                self._post_event()
        if (self._active & (self._spec_k > 0)).any():
            self.step_spec_round()
            with _span(POST):
                self._post_event()
        return self.has_work()

    def run(self, policy: str = "continuous") -> List[Completion]:
        """Drive queued requests to completion (repeated :meth:`step`).
        Returns completions in uid order."""
        assert policy in ("continuous", "static"), policy
        while self.step(policy):
            pass
        return self.completions()
