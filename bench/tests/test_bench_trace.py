"""The reduction from a profiler trace to busy time, program and kernel
time, top ops and labelled idle gaps."""

import gzip
import json
import types
from pathlib import Path

import pytest

from bench import trace
from bench.client import SPANS

DATA = Path(__file__).parent / "data"


def planes_from(obj):
    """Planes in the shape ProfileData gives, from plain JSON."""
    ev = lambda n, s, d: types.SimpleNamespace(name=n, start_ns=s,
                                               duration_ns=d)
    return [types.SimpleNamespace(name=p["name"], lines=[
        types.SimpleNamespace(name=ln["name"],
                              events=[ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in obj["planes"]]


SYNTH = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit__segment(7)", 100, 400],
                                           ["jit__ingest_varlen(9)", 600, 200]]},
        {"name": "XLA Ops", "events": [["fusion.1", 100, 100],
                                       ["custom-call.3", 150, 250],
                                       ["fusion.2", 600, 200]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["client.submit", 0, 70],
                                      ["engine.step", 70, 710],
                                      ["client.harvest", 850, 50],
                                      ["other", 0, 1000]]}]}]}


def test_synthetic_trace():
    s = trace.summarize(planes_from(SYNTH), SPANS)
    assert s.window_ns == (0, 900)
    # ops cover [100, 400) and [600, 800)
    assert s.busy_s() == pytest.approx(500e-9)
    assert s.module_s(["jit__segment"]) == pytest.approx(400e-9)
    assert s.module_s(["jit__segment", "jit__ingest_varlen"]) == \
        pytest.approx(600e-9)
    assert s.ops_matching(r"custom-call") == pytest.approx(250e-9)
    assert s.ops_matching(r"fusion", ["jit__segment"]) == \
        pytest.approx(100e-9)
    assert s.top_ops(2) == [["custom-call.3", pytest.approx(250e-9)],
                            ["fusion.2", pytest.approx(200e-9)]]
    gaps = s.idle_gaps(3)
    # [400, 600) inside engine.step, [0, 100) starts in client.submit,
    # [800, 900) in client.harvest
    assert gaps[0] == ["engine.step", pytest.approx(200e-9)]
    assert {g[0] for g in gaps} == {"engine.step", "client.submit",
                                    "client.harvest"}
    assert sum(g[1] for g in gaps) == pytest.approx(400e-9)


def test_top_ops_count_loop_bodies_not_loops():
    nested = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ["%while.4 = (s32[]) while(s32[] %t), body=%b", 0, 100],
            ["%fusion.1 = bf16[8]{0} fusion(bf16[8]{0} %p), kind=kLoop", 0, 30],
            ["%custom-call.2 = f32[4]{0} custom-call(f32[4]{0} %q)", 40, 50]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [["engine.step", 0, 100]]}]}]}
    s = trace.summarize(planes_from(nested), SPANS)
    assert s.top_ops(5) == [["%custom-call.2 custom-call",
                             pytest.approx(50e-9)],
                            ["%fusion.1 fusion", pytest.approx(30e-9)]]
    assert s.busy_s() == pytest.approx(100e-9)


def test_window_clips_events():
    s = trace.summarize(planes_from(SYNTH), SPANS, window_ns=(200, 700))
    assert s.busy_s() == pytest.approx(300e-9)
    assert s.module_s(["jit__segment"]) == pytest.approx(300e-9)


def test_a_trace_recorded_on_the_chip():
    """Two decode segments of the linear reasoning cell on a TPU v5e,
    with the client's spans: the slice of a traced run that the
    reduction reads."""
    with gzip.open(DATA / "trace_linear_reasoning.json.gz", "rt") as f:
        s = trace.summarize(planes_from(json.load(f)), SPANS)
    assert 0.4 < s.window_s < 0.6
    assert 0 < s.busy_s() <= s.window_s
    seg = s.module_s(["jit__segment"])
    assert seg == pytest.approx(0.307, rel=0.01)
    assert s.module_s(["jit__ingest_varlen"]) == 0
    # the fused decode kernel is the segment's one custom call
    kernel = s.ops_matching(r"custom-call", ["jit__segment"])
    assert 0 < kernel < seg
    assert kernel == pytest.approx(s.ops_matching(r"custom-call"))
    top = s.top_ops(4)
    assert "%closed_call.12 custom-call" in [name for name, _ in top]
    assert all(" = " not in name for name, _ in top)
    gaps = s.idle_gaps(3)
    assert [g[0] for g in gaps] == ["engine.step"] * 3
    assert gaps[0][1] > gaps[1][1] > gaps[2][1]


def test_segment_roofline_on_the_chip_trace():
    """Both segments of the chip trace, each 8 steps over 64 full slots:
    16 x 1.192 GB of weights and 1024 x 59.2 MB of state at 819 GB/s is
    97.3 ms of the 0.307 s the segments took."""
    from bench import run, spec
    with gzip.open(DATA / "trace_linear_reasoning.json.gz", "rt") as f:
        s = trace.summarize(planes_from(json.load(f)), SPANS)
    recs = [types.SimpleNamespace(c_t0=1, c_t1=17, prompt=[0] * 256)
            for _ in range(64)]
    data = run.RunData(
        conf=spec.load_json(spec.BENCH_DIR / "configs"
                            / "qwen3-0.6b-linear.json"),
        records=recs, trace=s, counters={"segments": 2, "segment_len": 8},
        device_kind="TPU v5 lite")
    got = spec.load_module("metrics", "segment_roofline").read(data)
    least = (16 * 1_192_099_840 + 1024 * 59_179_008) / 819e9
    assert got == pytest.approx(100 * least / s.module_s(["jit__segment"]))
    assert 31 < got < 32
