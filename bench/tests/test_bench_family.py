"""The harness asks a configuration's family for what depends on the
architecture (``bench/families/``), and hands readers every counter of
the engine.

The dense family reads what the harness read before it: the same
``ModelConfig`` for both files, bit for bit the same weights, and the
same reference. A configuration of another family, whose family,
reference, work counts and reader exist only in a copy of ``bench/``,
runs through the harness as it is.
"""

import dataclasses
import hashlib
import json
import shutil
import textwrap
import types

import jax
import numpy as np
import pytest

from bench import check, run, spec, weights
from bench.reference import dense
from bench.tests.smoke import smoke_cell, smoke_config


@pytest.mark.parametrize("backend", ["linear", "softmax"])
def test_dense_family_gives_the_same_model_config(backend):
    from repro.configs.base import ModelConfig
    conf = spec.load_json(spec.BENCH_DIR / "configs"
                          / f"qwen3-0.6b-{backend}.json")
    assert "family" not in conf
    assert spec.model_config(conf) == ModelConfig(
        name=f"qwen3-{backend}", family="dense", n_layers=28, d_model=1024,
        n_heads=16, n_kv_heads=8, head_dim=128, d_ff=3072,
        vocab_size=151936, qk_norm=True, rope_theta=1e6,
        tie_embeddings=True, attention_backend=backend,
        decode_kernel="auto", dtype="bfloat16", param_dtype="bfloat16")
    # a growing KV cache, and so the prompt-length warm-up, on softmax only
    assert spec.family(conf).grows_kv(conf) == (backend == "softmax")


def _digest(params) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        a = np.asarray(leaf)
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


@pytest.mark.parametrize("dtype,digest", [
    # the weights the harness made before it asked a family (the same
    # for both files, which differ in the backend only)
    ("bfloat16",
     "83e180f941f43597662e044194334fea8d306e4e0f3d2ccf29c2d7916a640e29"),
    ("float32",
     "ffd99707a808aaae0f6e0d107d7afa275e8d111d5cf3fd3dd6f6a63c3b66c0d8")])
def test_dense_family_makes_the_same_weights(dtype, digest):
    assert _digest(weights.make_params(smoke_config("linear", dtype),
                                       2**33 + 5)) == digest


@pytest.mark.parametrize("control", [False, True])
def test_dense_family_compares_with_the_same_reference(control):
    conf = smoke_config("linear")
    params = weights.make_params(conf, 9)
    rng = np.random.default_rng(4)
    prompt = rng.integers(0, 256, 21).astype(np.int32)
    served = rng.integers(0, 256, 30).astype(np.int32)
    rec = types.SimpleNamespace(prompt=prompt, tokens=served, max_new=30,
                                status="ok")
    got = check.compare(params, conf, [rec], 256, control=control)
    gaps = dense.served_gaps(params, conf, prompt, served, control=control)
    assert got == {"logit_gap_max": float(gaps.max()), "bad_requests": 0.0,
                   "served_tokens": 30.0}


TOY = {
    "families/toy.py": '''
        """Dense, with its embedding negated."""
        from pathlib import Path

        from bench import spec

        LOG = Path(__file__).resolve().parents[1] / "used.log"
        _dense = spec.load_module("families", "dense")
        REFERENCE = "toy"
        WORK = "toy_lm"
        SMOKE = _dense.SMOKE


        def used(what):
            with open(LOG, "a") as f:
                f.write(what + "\\n")


        def model_config(conf):
            used("family.model_config")
            return _dense.model_config(conf)


        def grows_kv(conf):
            used("family.grows_kv")
            return _dense.grows_kv(conf)


        def make(conf, key):
            used("family.make")
            params = _dense.make(conf, key)
            return dict(params, embed=-params["embed"])
        ''',
    "reference/toy.py": '''
        from bench import spec

        _dense = spec.load_module("reference", "dense")
        _used = spec.load_module("families", "toy").used


        def served_gaps(params, conf, prompt, served, control=False):
            _used("reference.served_gaps")
            return _dense.served_gaps(params, conf, prompt, served,
                                      control=control)
        ''',
    "work/toy_lm.py": '''
        from bench import spec

        _dense = spec.load_module("work", "dense_lm")


        def token_flops(conf):
            """Matmuls and head of one token."""
            return _dense.matmul_flops(conf) + _dense.head_flops(conf)
        ''',
    "metrics/toy_token_flops.py": '''
        def read(run):
            return run.work().token_flops(run.conf)
        ''',
}


def test_a_family_of_new_files_runs_through_the_harness(tmp_path,
                                                        monkeypatch):
    bench = tmp_path / "bench"
    shutil.copytree(spec.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(spec.ROOT / "BENCHMARK.json", tmp_path)
    conf = spec.load_json(bench / "configs" / "qwen3-0.6b-linear.json")
    (bench / "configs" / "toy-linear.json").write_text(
        json.dumps(dict(conf, family="toy")))
    for rel, src in TOY.items():
        (bench / rel).write_text(textwrap.dedent(src).lstrip())
    for rel in TOY:
        assert not (spec.BENCH_DIR / rel).exists()
    monkeypatch.setattr(spec, "BENCH_DIR", bench)
    monkeypatch.setattr(spec, "ROOT", tmp_path)

    cell = smoke_cell("linear", "reasoning", config="toy")
    assert cell.config["family"] == "toy"
    cell.end_to_end.append({"name": "toy_token_flops", "unit": "flops"})
    seed = 2**33 + 3
    result, params, records = run.measure(cell, seed, 1.0, False)
    picked = check.sample(records, seed, cell.traffic["check"])
    numbers = check.compare(params, cell.config, picked, 256)
    assert check.verdict(numbers, cell.limits), numbers

    used = set((bench / "used.log").read_text().split())
    assert used == {"family.model_config", "family.grows_kv",
                    "family.make", "reference.served_gaps"}
    # the toy's weights, not the dense family's
    plain = weights.make_params(smoke_config("linear"), seed)
    np.testing.assert_array_equal(np.asarray(params["embed"]),
                                  -np.asarray(plain["embed"]))
    # its reader, reading its own work counts: 2 x (2 layers x (64 x 64
    # + 2 x 64 x 32 + 64 x 64 + 3 x 64 x 128) + 64 x 256)
    assert result["metrics"]["toy_token_flops"]["value"] == \
        2 * (2 * 36_864 + 16_384)


def test_readers_get_every_counter_of_the_engine(monkeypatch):
    seen = []

    class Seen(run.RunData):
        def __init__(self, **kw):
            super().__init__(**kw)
            seen.append(self)
    monkeypatch.setattr(run, "RunData", Seen)
    result, _, _ = run.measure(smoke_cell("linear", "reasoning"), 2**33 + 7,
                               1.0, False)
    (data,) = seen
    assert data.counters is result["counters"]
    c = data.counters
    # the keys the result line carried before, and the admission counters
    for k in ("segments", "emitted_tokens", "prefills", "admission_batches",
              "prefill_dispatches", "ingest_chunks", "prefill_jit_misses",
              "finite_checks", "n_slots", "segment_len", "queued_at_open",
              "queued_at_close", "admission_token_slots",
              "admission_tokens"):
        assert k in c, k
    assert 0 < c["admission_tokens"] <= c["admission_token_slots"]
    assert c["n_slots"] == 4 and c["segment_len"] == 4

    from repro.serving.engine import EngineStats

    @dataclasses.dataclass
    class Stats(EngineStats):
        lookups: int = 0
    engine = types.SimpleNamespace(stats=Stats(admission_tokens=3,
                                                lookups=5))
    got = run.counters(engine)
    assert got["lookups"] == 5 and got["admission_tokens"] == 3
    assert "degrade_events" not in got
