"""Share of the decode segments' slot-steps that emitted a token, in %,
from the engine's counters over the window."""


def read(run):
    c = run.counters
    total = c["segments"] * c["n_slots"] * c["segment_len"]
    return 100.0 * c["emitted_tokens"] / total if total else None
