"""Metric arithmetic: rates, nearest-rank tails, inter-token latency
over observations, and the client's bookkeeping that feeds it."""

import types

import pytest

from bench import stats


def rec(**kw):
    base = dict(itl=[])
    base.update(kw)
    return types.SimpleNamespace(**base)


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile(vals, 99) == 99
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile([], 95) is None


def test_rate_over_the_window():
    assert stats.rate(300, 10.0, 12.5) == pytest.approx(120.0)


def test_itl_weights_each_token():
    recs = [rec(itl=[(0.05, 8), (0.2, 1)]),   # 8 tokens at 50 ms, 1 at 200
            rec(itl=[]),                      # only its first token
            rec(itl=[(0.01, 2)])]
    got = stats.itl_samples(recs)
    assert sorted(got) == pytest.approx([0.01] * 2 + [0.05] * 8 + [0.2])
    assert stats.percentile(got, 95) == pytest.approx(0.2)
    assert stats.percentile(got, 50) == pytest.approx(0.05)


class _Engine:
    """Stands in for a DecodeEngine: one slot whose tokens the test
    sets before each observation."""
    n_slots = 1

    def __init__(self):
        self._slot_req = [types.SimpleNamespace(uid=0, prompt=[1, 2, 3])]
        self._slot_toks = [[]]
        self._ingest_req = [None]
        self._ingest_cursor = [0]
        self._completions = {}


def test_client_records_gaps_inside_the_window_only():
    from bench.client import Client, Record
    eng = _Engine()
    traffic = types.SimpleNamespace(mix={}, closed=True)
    c = Client(eng, traffic)
    c.records[0] = Record(uid=0, prompt=[1, 2, 3], max_new=99, due=None,
                          t_sub=0.0)
    for t, n in ((1.0, 1), (1.5, 9)):        # first token, then 8 more
        eng._slot_toks[0] = [5] * n
        c._observe(t)
    c.open_window(1.5)
    for t, n in ((2.0, 17), (2.1, 17), (2.6, 25)):
        eng._slot_toks[0] = [5] * n
        c._observe(t)
    c.close_window(2.6)
    eng._slot_toks[0] = [5] * 33
    c._observe(3.0)                          # after the window
    r = c.records[0]
    assert r.t_first == 1.0
    assert r.itl == [(pytest.approx(0.5 / 8), 8), (pytest.approx(0.6 / 8), 8)]
    assert (r.c_t0, r.c_t1) == (9, 25)


def test_closed_loop_attempts_what_the_window_served():
    """A window shorter than every request submits none; it still
    answers for the requests it served, and not for an idle one."""
    from bench.client import Client, Record
    c = Client(_Engine(), types.SimpleNamespace(mix={}, closed=True))
    kw = dict(prompt=[1], max_new=99, due=None, t_sub=0.0)
    c.records = {0: Record(uid=0, c_t0=9, c_t1=25, **kw),   # decoding
                 1: Record(uid=1, p_t0=0, p_t1=64, **kw),   # ingesting
                 2: Record(uid=2, c_t0=4, c_t1=4, **kw),    # waited
                 3: Record(uid=3, in_window=True, **kw)}    # submitted
    assert sorted(r.uid for r in c.attempted(0.0, 5.0)) == [0, 1, 3]


def test_check_sample_holds_the_longest_and_seeded_others():
    from bench import check
    recs = [rec(uid=u, prompt=[0] * (10 + u), tokens=[1] * 20)
            for u in range(12)] + [rec(uid=99, prompt=[0], tokens=None)]
    a = check.sample(recs, 2**40 + 3, {"requests": 6})
    b = check.sample(recs, 2**40 + 3, {"requests": 6})
    assert [r.uid for r in a] == [r.uid for r in b]
    assert len(a) == 6 and len({r.uid for r in a}) == 6
    assert a[0].uid == 11 and 99 not in {r.uid for r in a}
    assert len(check.sample(recs[:3], 1, {"requests": 6})) == 3


@pytest.mark.parametrize("name", ["itl_p99_ms", "segment_mfu",
                                  "segment_roofline", "device_idle_share"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    from bench import spec
    run = types.SimpleNamespace(records=[rec(c_t0=0, c_t1=0, prompt=[1])],
                                trace=None)
    assert spec.load_module("metrics", name).read(run) is None
