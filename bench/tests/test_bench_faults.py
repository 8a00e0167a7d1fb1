"""The comparison that decides ``correct`` fails what it must.

The control, the reference in float8 in the program's place, has to
read worse than the program. And a run whose timed path is broken
underneath has to come out not correct: once with a decode step that
returns its state unchanged, once with a token altered where it is
produced. (The serving cells have no exchange between chips, and a
slot left out of a step never finishes rather than answering wrong.)
"""

import numpy as np
import pytest

from bench import run, weights
from bench.reference import dense
from bench.tests.smoke import smoke_cell, smoke_config


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_control_reads_worse_than_the_program(backend):
    conf = smoke_config(backend)
    params = weights.make_params(conf, 5)
    engine = run.build_engine(conf, params)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n).astype(np.int32) for n in (30, 37)]
    uids = [engine.submit(p, 48) for p in prompts]
    comps = {c.uid: c for c in engine.run()}
    prog, ctrl = 0.0, 0.0
    for p, u in zip(prompts, uids):
        toks = np.asarray(comps[u].tokens)
        prog = max(prog, dense.served_gaps(params, conf, p, toks).max())
        ctrl = max(ctrl, dense.served_gaps(params, conf, p, toks,
                                           control=True).max())
    assert ctrl > 3 * prog, (prog, ctrl)


def _stale_state(monkeypatch):
    from repro.models import lm
    real = lm.generate_segment

    def segment(params, state, *a, **kw):
        toks, carry = real(params, state, *a, **kw)
        return toks, dict(carry, state=state)
    monkeypatch.setattr(lm, "generate_segment", segment)


def _altered_token(monkeypatch):
    from repro.models import lm
    real = lm.generate_segment

    def segment(*a, **kw):
        toks, carry = real(*a, **kw)
        return ((toks >= 0) * ((toks + 1) % 256) + (toks < 0) * toks,
                carry)
    monkeypatch.setattr(lm, "generate_segment", segment)


def test_a_sound_run_is_correct():
    res = run.run_cell(smoke_cell("linear", "reasoning"), 2**33 + 1, 1.0, False)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("fault", [_stale_state, _altered_token])
def test_a_broken_timed_path_is_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    res = run.run_cell(smoke_cell("linear", "reasoning"), 2**33 + 1, 1.0, False)
    assert not res["correct"], res["checks"]
    assert res["checks"]["logit_gap_max"]["value"] > \
        res["checks"]["logit_gap_max"]["limit"]
