"""``DecodeEngine.progress()`` reports what ``EngineView.poll()`` reads
from the engine's internals, token for token, with the engine's own
times of each request."""

import jax
import numpy as np
import pytest

from bench.engine_view import EngineView


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_progress_agrees_with_the_engine_view_after_every_step(backend):
    from repro.configs import get_smoke_config
    from repro.models import lm
    from repro.serving import DecodeEngine

    cfg = get_smoke_config("yi-34b").with_backend(backend)
    engine = DecodeEngine(lm.init_params(jax.random.PRNGKey(1), cfg), cfg,
                          n_slots=3, segment_len=4, max_len=80,
                          prefill_chunk=16, admission="batched")
    view = EngineView(engine)
    rng = np.random.default_rng(5)
    # more requests than slots, prompts past one chunk, and a budget of
    # one token (it completes at admission)
    for n, g in [(5, 7), (40, 9), (9, 1), (17, 12), (3, 5), (30, 6)]:
        engine.submit(rng.integers(0, cfg.vocab_size, n), g)
    seen_done, steps, more = [], 0, True
    while more:
        more = engine.step()
        steps += 1
        seen, done = view.poll()
        prog = engine.progress()
        assert {u: (r.tokens, r.prompt_done)
                for u, r in prog.requests.items()} == seen
        assert [c.uid for c in prog.done] == [c.uid for c in done]
        for r in prog.requests.values():
            times = [r.t_submit, r.t_first_chunk, r.t_first_token,
                     r.t_tokens]
            known = [t for t in times if t is not None]
            assert known == sorted(known) and r.t_submit is not None
            assert (r.t_first_chunk is None) == (r.prompt_done == 0
                                                 and r.tokens == 0)
            assert (r.t_tokens is None) == (r.tokens == 0)
        seen_done += [c.uid for c in prog.done]
    assert steps > 4 and sorted(seen_done) == list(range(6))
    assert engine.progress().done == []
