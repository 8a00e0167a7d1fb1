"""Share of the token positions admission computed that held no prompt
token, in %, over the window: 100 x (1 - prompt tokens the prefill and
ingest dispatches consumed / rows x bucket width they computed), from
the window deltas of the engine's ``admission_tokens`` and
``admission_token_slots`` counters. None where the window held no
admission dispatch, or the run did not count them."""


def read(run):
    c = run.counters
    slots = c.get("admission_token_slots")
    if not slots:
        return None
    return 100.0 * (1.0 - c["admission_tokens"] / slots)
