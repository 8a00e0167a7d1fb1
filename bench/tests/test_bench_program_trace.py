"""The reduction of the program's own spans and named scopes
(``bench/program_trace.py``) and the three readers built on it."""

import types

import pytest

from bench import program_trace as pt
from bench import spec, trace
from bench.client import SPANS

ATT = "jit(_segment)/while/body/decode.layers/while/body/decode.attention"
LAYERS = "jit(_segment)/while/body/decode.layers/while/body"


def planes_from(obj):
    """Planes in the shape ProfileData gives, from plain JSON, and the
    scope of each op event (its optional fourth element) by name."""
    ev = lambda n, s, d, *_: types.SimpleNamespace(  # noqa: E731
        name=n, start_ns=s, duration_ns=d)
    planes = [types.SimpleNamespace(name=p["name"], lines=[
        types.SimpleNamespace(name=ln["name"],
                              events=[ev(*e) for e in ln["events"]])
        for ln in p["lines"]]) for p in obj["planes"]]
    scopes = {e[0]: e[3] for p in obj["planes"] for ln in p["lines"]
              for e in ln["events"] if len(e) > 3}
    return planes, scopes


SYNTH = {"planes": [
    {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit__segment(1)", 100, 400],
                                           ["jit__finite(2)", 520, 20],
                                           ["jit__segment(1)", 600, 400]]},
        {"name": "XLA Ops", "events": [
            ["%fusion.1", 100, 100, ATT + "/dot_general"],
            ["%custom-call.2", 150, 50, ATT + "/pallas_call"],
            ["%fusion.3", 200, 150, LAYERS + "/dynamic_slice"],
            ["%copy.4", 350, 100, ""],
            ["%fusion.5", 450, 50, "jit(_segment)/while/body/decode.head"],
            ["%reduce.9", 520, 20, "jit(_finite)/decode.attention"],
            ["%fusion.6", 600, 200, ATT + "/mul"],
            ["%fusion.7", 800, 200, LAYERS + "/decode.mlp/dot_general"]]}]},
    {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["client.submit", 0, 50],
            ["engine.step", 50, 510],
            ["engine.lifecycle", 55, 5],
            ["engine.admit", 60, 35],
            ["engine.wait", 70, 20],
            ["engine.segment", 95, 410],
            ["engine.wait", 100, 400],
            ["engine.post", 505, 40],
            ["engine.wait", 515, 25],
            ["client.harvest", 560, 20],
            ["engine.step", 580, 440],
            ["engine.lifecycle", 585, 5],
            ["engine.admit", 590, 5],
            ["engine.segment", 595, 410],
            ["engine.wait", 600, 390],
            ["engine.post", 1005, 10],
            ["engine.wait", 1006, 6],
            ["client.harvest", 1020, 20]]}]}]}


def _synth():
    planes, scopes = planes_from(SYNTH)
    summary = trace.summarize(planes, SPANS)
    return summary, pt.summarize(planes, summary.window_ns, scopes)


def test_program_spans_and_scopes_on_a_synthetic_trace():
    summary, prog = _synth()
    assert summary.window_ns == (0, 1040)
    assert [e.name for e in prog.spans].count("engine.wait") == 5
    # phases 490 + 430 ns, waits 445 + 396 ns, over two segments
    assert prog.engine_host_ms() == pytest.approx(1e-6 * (920 - 841) / 2)
    assert prog.phase_self_ms() == pytest.approx({
        "engine.lifecycle": 5e-6, "engine.admit": 10e-6,
        "engine.ingest": 0.0, "engine.segment": 15e-6,
        "engine.post": 9.5e-6, "engine.wait": 420.5e-6})
    # attention in segment runs: [100, 200) (the custom call nested in
    # the fusion counts once) and [600, 800); the _finite op is left out
    assert prog.scope_s(pt.ATTENTION, ["jit__segment"]) == \
        pytest.approx(300e-9)
    assert prog.scope_s(pt.ATTENTION) == pytest.approx(320e-9)
    assert prog.module_runs(["jit__segment"]) == 2
    assert prog.attention_ms_per_step(4) == pytest.approx(1e-6 * 300 / 8)
    # innermost segment ops: 750 ns, of which attention 250, the layer
    # scan 600, mlp 200, head 50 and no scope 100
    assert prog.scope_shares() == pytest.approx({
        "decode.attention": 100 * 250 / 750, "decode.layers": 80.0,
        "decode.mlp": 100 * 200 / 750, "decode.head": 100 * 50 / 750,
        "(none)": 100 * 100 / 750})
    top = prog.top_ops()
    assert top[0][:2] == ["%fusion.6", pytest.approx(100 * 200 / 750)]
    assert ["%copy.4", pytest.approx(100 * 100 / 750), ""] in top
    assert "%fusion.1" not in [t[0] for t in top]


def test_idle_gaps_are_labelled_by_the_innermost_span():
    summary, prog = _synth()
    # gaps [0, 100), [500, 520), [540, 600), [1000, 1040)
    assert summary.idle_gaps(4)[3] == ["engine.step", pytest.approx(20e-9)]
    gaps = pt.labelled_gaps(summary, prog, 4)
    assert gaps == [["engine.step", pytest.approx(100e-9)],
                    ["client.harvest", pytest.approx(60e-9)],
                    ["client.harvest", pytest.approx(40e-9)],
                    ["engine.post", pytest.approx(20e-9)]]
    # a gap inside a wait names the wait's phase: shift the post's wait
    # of the first step to cover the gap [500, 520)
    moved = dict(SYNTH)
    host = SYNTH["planes"][1]["lines"][0]["events"]
    moved["planes"] = [SYNTH["planes"][0], {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["engine.wait", 505, 30] if e == ["engine.wait", 515, 25]
            else e for e in host]}]}]
    planes, scopes = planes_from(moved)
    summary = trace.summarize(planes, SPANS)
    prog = pt.summarize(planes, summary.window_ns, scopes)
    assert pt.labelled_gaps(summary, prog, 4)[3] == \
        ["engine.post/engine.wait", pytest.approx(20e-9)]


def test_readers_on_a_synthetic_run():
    _, prog = _synth()
    run = types.SimpleNamespace(
        program_trace=prog,
        counters={"segment_len": 4, "admission_token_slots": 136,
                  "admission_tokens": 31})
    read = lambda name: spec.load_module("metrics", name).read(run)  # noqa
    assert read("engine_host_ms") == pytest.approx(1e-6 * 79 / 2)
    assert read("attention_ms_per_step") == pytest.approx(1e-6 * 300 / 8)
    assert read("admission_pad_share") == pytest.approx(
        100 * (1 - 31 / 136))


@pytest.mark.parametrize("name", ["engine_host_ms", "admission_pad_share",
                                  "attention_ms_per_step"])
def test_a_new_reader_with_nothing_to_read_returns_nothing(name):
    read = spec.load_module("metrics", name).read
    # a run of the accepted harness: no program trace, no counters
    assert read(types.SimpleNamespace(
        trace=None, counters={"segment_len": 8})) is None
    # a trace with neither program spans nor scoped ops, and a window
    # with no admission dispatch
    bare = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [["jit__segment(1)", 0, 9]]},
            {"name": "XLA Ops", "events": [["%fusion.1", 0, 9]]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": [["engine.step", 0, 10]]}]}]}
    planes, scopes = planes_from(bare)
    prog = pt.summarize(planes, trace.summarize(planes, SPANS).window_ns,
                        scopes)
    assert read(types.SimpleNamespace(
        program_trace=prog,
        counters={"segment_len": 8, "admission_token_slots": 0,
                  "admission_tokens": 0})) is None


# An XSpace serialised by protobuf from tsl/profiler/protobuf/xplane.proto:
# a TPU plane with stat metadata {1: tf_op, 2: long_name, 7: a scope held
# by reference}, event metadata 11 "%fusion.1 = f32[2] fusion(...)"
# (display name "fusion.1") whose tf_op refers to 7, 12 "%copy.2 = f32[2]
# copy(...)" with tf_op as a string, 13 "%copy.3" with no stat, one XLA Ops
# line; and a host plane whose tf_op stat must be left out.
XSPACE = bytes.fromhex(
    "0a9a020803120d2f6465766963653a5450553a301a0f1207584c41204f70732204"
    "080b1805220f080d120b080d120725636f70792e33223b080c1237080c121a2563"
    "6f70792e32203d206633325b325d20636f7079282e2e2e292a1708012a136a6974"
    "2866292f6465636f64652e6d6c702f782240080b123c080b121e25667573696f6e"
    "2e31203d206633325b325d20667573696f6e282e2e2e292208667573696f6e2e31"
    "2a0808022a046c6f6e672a04080138072a44080712400807123c6a69742866292f"
    "6465636f64652e6c61796572732f7768696c652f626f64792f6465636f64652e61"
    "7474656e74696f6e2f646f745f67656e6572616c2a110802120d080212096c6f6e"
    "675f6e616d652a0d080112090801120574665f6f700a3012092f686f73743a4350"
    "552214080112101206686f73746f702a0608012a026e6f2a0d0801120908011205"
    "74665f6f70")


def test_op_scopes_read_the_event_metadata(tmp_path):
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(XSPACE)
    att = "jit(f)/decode.layers/while/body/decode.attention/dot_general"
    assert pt.op_scopes(str(path)) == {
        "%fusion.1 = f32[2] fusion(...)": att,
        "%copy.2 = f32[2] copy(...)": "jit(f)/decode.mlp/x"}
    assert pt.op_scopes(str(path), stat="long_name") == {
        "%fusion.1 = f32[2] fusion(...)": "long"}


DATA = spec.BENCH_DIR / "tests" / "data"


@pytest.mark.parametrize("cell,host_ms,attention_ms,pad", [
    # hand-computed from the slices' events: (Σ phase spans - Σ waits) /
    # 2 segment spans, the union of decode.attention ops inside the two
    # jit__segment runs / 16 steps, and 1 - 2038 / 72448 from the run's
    # window counters (the softmax window held no admission)
    ("linear", (326_759_793 - 319_791_794) / 2e6, 43_718_426 / 16e6,
     100 * (1 - 2038 / 72448)),
    ("softmax", (695_756_658 - 690_162_837) / 2e6, 103_444_576 / 16e6,
     None),
])
def test_readers_on_chip_slices(cell, host_ms, attention_ms, pad):
    """Two decode segments of each reasoning cell on a TPU v5e, with the
    client's and the program's spans and each op's scope, and the
    traced run's window counters."""
    planes, scopes, counters = pt.read_slice(
        DATA / f"trace_{cell}_reasoning_program.json.gz")
    summary = trace.summarize(planes, SPANS)
    prog = pt.summarize(planes, summary.window_ns, scopes)
    run = types.SimpleNamespace(program_trace=prog, counters=counters)
    read = lambda name: spec.load_module("metrics", name).read(run)  # noqa
    assert read("engine_host_ms") == pytest.approx(host_ms)
    assert read("attention_ms_per_step") == pytest.approx(attention_ms)
    assert read("admission_pad_share") == (
        None if pad is None else pytest.approx(pad))
    # every op of 1% or more of the segments' time either carries a
    # decode.* scope or is a copy XLA added (no op_name)
    for label, share, scope in prog.top_ops():
        assert "/decode." in scope or label.endswith(" copy"), label
