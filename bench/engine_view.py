"""The one reader of ``DecodeEngine`` internals.

The engine hands out tokens only in ``completions()``, once a request
has finished. A client that times its first token and the tokens after
it needs to see them as they come, and the engine exposes no event for
that yet. This module reads the per-slot token lists and ingest cursors
for it, and changes nothing. Every other file of the benchmark drives
the engine through ``submit``, ``step`` and ``completions`` only.
"""

from __future__ import annotations

import itertools
from typing import Dict, List, Tuple


class EngineView:
    """Polls an engine for each request's progress after a step."""

    def __init__(self, engine):
        self._engine = engine
        self._n_done = 0

    def poll(self) -> Tuple[Dict[int, Tuple[int, int]], List]:
        """Returns ``({uid: (output tokens, prompt tokens consumed)},
        new completions)`` for every request in a slot, and for every
        request that completed since the last poll."""
        e = self._engine
        seen: Dict[int, Tuple[int, int]] = {}
        for slot in range(e.n_slots):
            req = e._slot_req[slot]
            if req is not None:
                seen[req.uid] = (len(e._slot_toks[slot]), len(req.prompt))
                continue
            req = e._ingest_req[slot]
            if req is not None:
                seen[req.uid] = (0, int(e._ingest_cursor[slot]))
        done = []
        if len(e._completions) > self._n_done:
            done = list(itertools.islice(e._completions.values(),
                                         self._n_done, None))
            self._n_done = len(e._completions)
            for c in done:
                seen[c.uid] = (len(c.tokens), c.prompt_len)
        return seen, done
