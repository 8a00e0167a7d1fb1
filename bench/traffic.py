"""The one traffic generator: every mix is a data file it reads.

A mix file (``bench/traffic/<mix>.json``) gives:

- ``loop``: ``"closed"`` (the client keeps ``backlog_per_slot`` x slots
  requests queued; offline batch or eval traffic) or ``"open"``
  (arrivals on a schedule at ``rate_per_s``, whatever the server does);
- ``prompt_tokens`` and ``output_tokens``: lognormal lengths, each as
  ``{"median", "sigma", "min", "max"}``;
- ``pool``: how many quantiles of each distribution a block of
  requests holds (a power of two);
- ``warm_s``: seconds of traffic before the window opens, so the slots
  reach steady occupancy;
- ``trace_s``: the window's length in a traced run (the profiler's
  trace of a long window is too large to read in a run's time);
- ``check``: ``{"requests": n}``, how many finished requests the
  reference recomputes.

Every seed gets the same work. Each block of ``pool`` requests holds
the same lengths and inter-arrival gaps, the ``pool`` quantiles of
their distributions, interleaved so that any run of 8 consecutive
requests draws one length from each eighth of each distribution
(van der Corput order; prompts, outputs and gaps each in an order of
their own, so that they are not correlated). The order is the same
under every seed; the seed draws the token ids, uniform over the
vocabulary, so no two prompts share a prefix. With the seed reordering
even groups of 4 requests, a closed loop's requests finished in other
steps, so its admission waves and ingest chunks came out otherwise:
on a TPU v5e one seed read 73 waves and 124 chunks in a 51 s window
and another 79 and 129, and the rate moved 4% between seeds while two
runs of one seed agreed within 0.9%.
"""

from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Dict

import numpy as np

_NORMAL = statistics.NormalDist()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray            # (P,) int32 token ids
    max_new_tokens: int
    due_s: float                  # offset from the traffic's start; 0
    #                               for a closed loop


def lognormal_quantiles(dist: Dict[str, float], n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a clipped lognormal, as whole tokens."""
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(x) for x in u])
    vals = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def interleaved(n: int, mult: int, add: int) -> np.ndarray:
    """Indices 0..n-1 (n a power of two) in van der Corput order of
    ``(mult * i + add) mod n``: every aligned run of 2**k entries holds
    one index from each 1/2**k of the range."""
    bits = n.bit_length() - 1
    if n != 1 << bits or mult % 2 == 0:
        raise ValueError("pool must be a power of two, mult odd")
    j = (mult * np.arange(n) + add) % n
    rev = np.zeros(n, np.int64)
    for b in range(bits):
        rev |= ((j >> b) & 1) << (bits - 1 - b)
    return rev


def exponential_quantiles(rate: float, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of the gaps of a Poisson process."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


class Traffic:
    """An endless, seeded stream of requests for one mix."""

    def __init__(self, mix: Dict, vocab_size: int, seed: int):
        self.mix = mix
        self.vocab_size = vocab_size
        self.closed = mix["loop"] == "closed"
        if mix["loop"] not in ("closed", "open"):
            raise ValueError(f"loop {mix['loop']!r}: closed or open")
        n = self.pool = int(mix["pool"])
        self._prompt_lens = lognormal_quantiles(
            mix["prompt_tokens"], n)[interleaved(n, 1, 0)]
        self._output_lens = lognormal_quantiles(
            mix["output_tokens"], n)[interleaved(n, 5, 3)]
        self._gaps = (None if self.closed else exponential_quantiles(
            float(mix["rate_per_s"]), n)[interleaved(n, 3, 1)])
        # seeds may exceed 32 bits; numpy takes any non-negative int
        self._id_rng = np.random.default_rng([seed, 1])
        self._due = 0.0
        self._next = 0

    def next(self) -> Request:
        """The next request of the stream."""
        j = self._next % self.pool
        self._next += 1
        if self._gaps is not None:
            self._due += float(self._gaps[j])
        prompt = self._id_rng.integers(
            0, self.vocab_size, size=int(self._prompt_lens[j]),
            dtype=np.int32)
        return Request(prompt=prompt,
                       max_new_tokens=int(self._output_lens[j]),
                       due_s=0.0 if self.closed else self._due)


def pow2_ceil(n: int) -> int:
    return 1 << max(0, math.ceil(math.log2(max(1, n))))
