"""Names of the profiler spans ``DecodeEngine.step`` opens.

Each span is a ``jax.profiler.TraceAnnotation``: inactive (well under a
microsecond) unless a ``jax.profiler`` trace is running, and then a host
event on the clock the device events are placed on. Run
``jax.profiler.trace(<dir>)`` around the serving loop to record them.

One step opens the phases in this order; ``engine.ingest`` only while a
prompt is mid-way, ``engine.segment`` only when a decode segment runs and
``engine.post`` after it (and after a speculative round, which has no
span of its own):

* ``engine.lifecycle`` — cancellations, deadlines, overload degradation;
* ``engine.admit``     — slot choice, padding, the first-chunk dispatch
  and the first token of each prompt that finishes;
* ``engine.ingest``    — one continuation chunk across mid-prompt slots;
* ``engine.segment``   — the decode segment: dispatch, token and carry
  reads, per-slot bookkeeping;
* ``engine.post``      — the finite probe, checkpoints.

``engine.wait`` nests inside a phase around each host read that waits for
the device, and around nothing else: a plain decode step (no admission
dispatch, no ingest) that leaves a slot occupied opens exactly
``WAITS_PER_SEGMENT`` of them, the segment's token and carry read and the
finite probe. A phase's self time is its duration less the waits inside
it.
"""

from __future__ import annotations

LIFECYCLE = "engine.lifecycle"
ADMIT = "engine.admit"
INGEST = "engine.ingest"
SEGMENT = "engine.segment"
POST = "engine.post"
WAIT = "engine.wait"

PHASES = (LIFECYCLE, ADMIT, INGEST, SEGMENT, POST)
SPANS = PHASES + (WAIT,)
WAITS_PER_SEGMENT = 2
