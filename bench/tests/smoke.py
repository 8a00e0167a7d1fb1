"""A cell at its family's smoke sizes (the qwen3-0.6b smoke preset's,
for the dense family), for CPU tests."""

from __future__ import annotations

import copy

from bench import spec


def smoke_config(backend: str, dtype: str = "bfloat16",
                 config: str = "qwen3-0.6b") -> dict:
    conf = spec.load_json(spec.BENCH_DIR / "configs"
                          / f"{config}-{backend}.json")
    conf.update(spec.family(conf).SMOKE, torch_dtype=dtype)
    conf["serving"] = {"n_slots": 4, "segment_len": 4, "prefill_chunk": 16,
                       "max_len": 300}
    return conf


def smoke_cell(backend: str, traffic: str, dtype: str = "bfloat16",
               limits=None, config: str = "qwen3-0.6b") -> spec.Cell:
    name = f"{config}-{backend}.{traffic}"
    bench = spec.load_json(spec.ROOT / "BENCHMARK.json")
    mix = copy.deepcopy(spec.load_json(spec.BENCH_DIR / "traffic"
                                       / f"{traffic}.json"))
    mix["prompt_tokens"] = {"median": 12, "sigma": 0.6, "min": 4, "max": 40}
    mix["output_tokens"] = {"median": 20, "sigma": 0.6, "min": 4, "max": 60}
    mix["warm_s"], mix["trace_s"] = 0.5, 1.0
    return spec.Cell(
        name=name, config=smoke_config(backend, dtype, config), traffic=mix,
        limits=limits or {"logit_gap_max": 0.05, "bad_requests": 0},
        end_to_end=[m for m in bench["end_to_end"]
                    if spec._applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if spec._applies(m, name)])
