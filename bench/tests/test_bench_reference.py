"""The plain reference agrees with the engine at the qwen3-0.6b smoke
preset's sizes, and the benchmark's weights have the program's layout."""

import jax
import numpy as np
import pytest

from bench import run, spec, weights
from bench.reference import dense
from bench.tests.smoke import smoke_config


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_weights_have_the_programs_layout(backend):
    from repro.models import lm
    conf = smoke_config(backend)
    cfg = spec.model_config(conf)
    ours = jax.eval_shape(lambda: weights.make_params(conf, 3))
    theirs = jax.eval_shape(lambda: lm.init_params(jax.random.PRNGKey(0),
                                                   cfg))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert a.shape == b.shape and a.dtype == b.dtype


def _served(conf, seed, prompts, n_new):
    params = weights.make_params(conf, seed)
    engine = run.build_engine(conf, params)
    uids = [engine.submit(p, n_new) for p in prompts]
    comps = {c.uid: c for c in engine.run()}
    return params, [comps[u].tokens for u in uids]


@pytest.mark.parametrize("backend,dtype,tol", [
    # float32 end to end: only summation order differs
    ("linear", "float32", 1e-4), ("softmax", "float32", 1e-4),
    # bfloat16 activations (8 mantissa bits) through 2 layers
    ("linear", "bfloat16", 0.05), ("softmax", "bfloat16", 0.05)])
def test_reference_agrees_with_the_engine(backend, dtype, tol):
    conf = smoke_config(backend, dtype)
    rng = np.random.default_rng(0)
    # short, chunk-sized and multi-chunk prompts: batch-1 and pool-wide
    # prefill, chunked ingest and decode segments all serve tokens
    prompts = [rng.integers(0, 256, n).astype(np.int32)
               for n in (5, 16, 40, 23)]
    params, served = _served(conf, 11, prompts, 24)
    for p, toks in zip(prompts, served):
        assert len(toks) == 24
        gaps = dense.served_gaps(params, conf, p, np.asarray(toks))
        assert gaps.shape == (24,)
        assert gaps.max() <= tol, (backend, dtype, gaps.max())
