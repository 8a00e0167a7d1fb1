"""The traffic generator: deterministic from the seed, true to its file."""

import numpy as np
import pytest

from bench import spec
from bench.traffic import Traffic, lognormal_quantiles

# An open-loop mix kept here: the generator serves both loops, and no
# cell runs an open-loop mix yet.
OPEN = {"loop": "open", "rate_per_s": 5.0,
        "prompt_tokens": {"median": 384, "sigma": 1.0, "min": 32, "max": 1536},
        "output_tokens": {"median": 128, "sigma": 1.0, "min": 8, "max": 512},
        "pool": 256, "warm_s": 10, "trace_s": 5, "check": {"requests": 6}}
MIXES = ("reasoning", "open")


def _mix(name):
    if name == "open":
        return dict(OPEN)
    return spec.load_json(spec.BENCH_DIR / "traffic" / f"{name}.json")


def _take(t, n):
    return [t.next() for _ in range(n)]


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_requests(name):
    a = _take(Traffic(_mix(name), 151936, 2**40 + 17), 40)
    b = _take(Traffic(_mix(name), 151936, 2**40 + 17), 40)
    for x, y in zip(a, b):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new_tokens, x.due_s) == (y.max_new_tokens, y.due_s)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    """Every seed gets the same lengths and arrivals in the same order,
    so a window holds the same work; only the token ids differ."""
    mix = _mix(name)
    n = mix["pool"]
    a = _take(Traffic(mix, 151936, 1), n + 8)
    b = _take(Traffic(mix, 151936, 2**40 + 1), n + 8)
    assert [(len(r.prompt), r.max_new_tokens, r.due_s) for r in a] == \
        [(len(r.prompt), r.max_new_tokens, r.due_s) for r in b]
    assert not any(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    # a block repeats the pool's lengths
    assert [len(r.prompt) for r in a[n:]] == [len(r.prompt) for r in a[:8]]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_file(name):
    mix = _mix(name)
    reqs = _take(Traffic(mix, 151936, 5), mix["pool"])
    for key, vals in (("prompt_tokens", [len(r.prompt) for r in reqs]),
                      ("output_tokens", [r.max_new_tokens for r in reqs])):
        d = mix[key]
        assert min(vals) >= d["min"] and max(vals) <= d["max"]
        assert np.median(vals) == pytest.approx(d["median"], rel=0.02)
        logs = np.log(np.clip(vals, d["min"] + 1, d["max"] - 1))
        inner = (np.array(vals) > d["min"]) & (np.array(vals) < d["max"])
        # the spread of the unclipped middle matches sigma
        q1, q3 = np.percentile(logs[inner], [25, 75])
        assert (q3 - q1) / 1.349 == pytest.approx(d["sigma"], rel=0.25)
    ids = np.concatenate([r.prompt for r in reqs])
    assert ids.min() >= 0 and ids.max() < 151936
    if mix["loop"] == "open":
        gaps = np.diff([0.0] + [r.due_s for r in reqs])
        assert gaps.mean() == pytest.approx(1.0 / mix["rate_per_s"],
                                            rel=0.02)


def test_quantiles_are_clipped_lognormal():
    q = lognormal_quantiles({"median": 100, "sigma": 1.0, "min": 50,
                             "max": 300}, 1001)
    assert q[500] == 100 and q.min() == 50 and q.max() == 300
