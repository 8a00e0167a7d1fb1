"""Whether what the timed path served is correct.

After the window has closed and the engine is freed, a sample of the
requests it finished, drawn from the seed and always holding the
longest, is recomputed by the configuration family's plain float32
reference (``bench/reference/<REFERENCE>.py``) over each prompt and its
served tokens. The number compared is the widest gap by which a
served (greedy) token's logit lies below the reference's best at its
position; its limit is in ``bench/limits/<cell>.json``. Every request in
the sample must also have finished ``ok`` with exactly the tokens it
asked for, each inside the vocabulary.
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

from bench import spec


def sample(records: Sequence, seed: int, check: Dict) -> List:
    """``check["requests"]`` finished requests to recompute, each in
    full: the longest (prompt and output), and others drawn from the
    seed. Requests that finished at different times held different
    slots, so the sample reads several slots of the pool."""
    done = [r for r in records if r.tokens is not None]
    if not done:
        return []
    done.sort(key=lambda r: r.uid)
    longest = max(done, key=lambda r: (len(r.prompt) + len(r.tokens), r.uid))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 2]).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:check["requests"] - 1]]


def compare(params, conf: Dict, picked: Sequence, vocab_size: int,
            control: bool = False) -> Dict[str, float]:
    """Numbers the limits hold: ``logit_gap_max`` (the widest gap) and
    ``bad_requests`` (sampled requests that did not finish ``ok`` with
    their full, in-vocabulary output)."""
    reference = spec.load_module("reference", spec.family(conf).REFERENCE)
    worst, bad, served = 0.0, 0, 0
    for r in picked:
        toks = np.asarray(r.tokens)
        if (r.status != "ok" or len(toks) != r.max_new
                or toks.min() < 0 or toks.max() >= vocab_size):
            bad += 1
            continue
        gaps = reference.served_gaps(params, conf, r.prompt, toks,
                                     control=control)
        served += len(gaps)
        worst = max(worst, float(gaps.max()))
    return {"logit_gap_max": worst, "bad_requests": float(bad),
            "served_tokens": float(served)}


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    """Correct when every number with a limit is at or under it and
    something was compared."""
    return (numbers.get("served_tokens", 0) > 0
            and all(numbers[k] <= v for k, v in limits.items()))
