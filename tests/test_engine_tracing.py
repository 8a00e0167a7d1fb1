"""The engine's profiler spans, admission counters and decode scopes.

``DecodeEngine.step`` opens one ``jax.profiler`` span per phase and one
``engine.wait`` around each host read that waits for the device
(``repro.serving.tracing``); ``EngineStats`` counts the token positions
admission computes against the prompt tokens it consumes; the segment
program's ops carry ``decode.*`` named scopes in their metadata.
"""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import get_smoke_config
from repro.models import lm
from repro.serving import DecodeEngine, tracing

SCOPES = ("decode.layers", "decode.attention", "decode.mlp", "decode.head")
STEP = "test.step"


def _engine(backend, n_slots=4, **kw):
    cfg = get_smoke_config("yi-34b").with_backend(backend)
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    return DecodeEngine(params, cfg, n_slots=n_slots, segment_len=4,
                        max_len=64, prefill_chunk=16, admission="batched",
                        **kw), cfg


def _submit(engine, cfg, lengths, gen):
    rng = np.random.default_rng(3)
    for n, g in zip(lengths, gen):
        engine.submit(rng.integers(0, cfg.vocab_size, n), g)


def _host_events(trace_dir):
    """(name, start, end) of every host event of the trace, time-sorted."""
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            out += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events]
    return sorted(out, key=lambda e: (e[1], -e[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_step_spans_in_order_with_waits_inside_phases(backend, tmp_path):
    engine, cfg = _engine(backend)
    # prompts of 5, 20 and 9 tokens: one admission wave, one prompt past
    # the first chunk (a continuation chunk), then plain decode steps
    work = ([5, 20, 9], [6, 9, 14])
    _submit(engine, cfg, *work)
    engine.run()                       # compile every program first
    engine.reset()
    _submit(engine, cfg, *work)
    with jax.profiler.trace(str(tmp_path)):
        more, i = True, 0
        while more:
            with jax.profiler.TraceAnnotation(STEP, i=i):
                more = engine.step()
            i += 1
    events = _host_events(tmp_path)
    ours = [e for e in events if e[0].startswith("engine.")]
    assert {e[0] for e in ours} == set(tracing.SPANS)
    steps = [e for e in events if e[0] == STEP]
    assert len(steps) == i
    phases = [e for e in ours if e[0] in tracing.PHASES]
    waits = [e for e in ours if e[0] == tracing.WAIT]
    # every wait sits inside a phase, and every phase inside a step
    assert all(any(_inside(w, p) for p in phases) for w in waits)
    assert all(any(_inside(p, s) for s in steps) for p in phases)
    plain = 0
    for s in steps[:-1]:     # the last step leaves no slot to probe
        names = [p[0] for p in phases if _inside(p, s)]
        assert names[:2] == [tracing.LIFECYCLE, tracing.ADMIT]
        order = [tracing.PHASES.index(n) for n in names]
        assert order == sorted(order) and len(set(order)) == len(order)
        admit = next(p for p in phases if p[0] == tracing.ADMIT
                     and _inside(p, s))
        dispatched = any(_inside(w, admit) for w in waits)
        if tracing.SEGMENT in names and tracing.INGEST not in names \
                and not dispatched:
            plain += 1
            assert sum(_inside(w, s) for w in waits) == \
                tracing.WAITS_PER_SEGMENT
    assert plain >= 2


@pytest.mark.parametrize("lengths,expect", [
    # one wave of three prompts: the pool-wide first chunk computes
    # 8 rows x 16 (5 + 9 + 16 tokens), the 17-token prompt's last token
    # a pool-wide continuation chunk of 8 rows x 1
    ([5, 9, 17], (8 * 16 + 8 * 1, 5 + 9 + 17)),
    # a lone prompt refills through the batch-1 program: 1 row x 8
    ([5], (8, 5)),
])
def test_admission_counters_are_exact(lengths, expect):
    engine, cfg = _engine("linear", n_slots=8)
    _submit(engine, cfg, lengths, [3] * len(lengths))
    engine.run()
    st = engine.stats
    assert (st.admission_token_slots, st.admission_tokens) == expect


def test_cache_hit_landing_adds_no_admission_work():
    engine, cfg = _engine("linear", n_slots=8, prefix_cache=True)
    prompt = np.arange(40) % cfg.vocab_size
    engine.submit(prompt, 3)
    engine.run()
    st = engine.stats
    # batch-1 first chunk 1 x 16, continuations 8 x 16 and 8 x 8
    assert (st.admission_token_slots, st.admission_tokens) == \
        (16 + 8 * 16 + 8 * 8, 40)
    before = (st.admission_token_slots, st.admission_tokens)
    engine.submit(prompt, 3)
    engine.run()
    assert engine.stats.cache_hits == 1
    # the hit lands 32 cached tokens with no dispatch; only the 8-token
    # suffix is computed, by one continuation chunk of 8 rows x 8
    assert (st.admission_token_slots - before[0],
            st.admission_tokens - before[1]) == (8 * 8, 8)


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_segment_program_carries_the_decode_scopes(backend):
    engine, cfg = _engine(backend)
    _submit(engine, cfg, [5], [3])
    engine.step()
    text = engine.segment_program_text()
    names = " ".join(l for l in text.splitlines() if "op_name=" in l)
    for scope in SCOPES:
        assert scope in names, scope
