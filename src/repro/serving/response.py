"""The unified serving response surface (ISSUE 10 api_redesign).

Every serving layer — the bare ``RetrievalEngine``, the hardened
``GuardedEngine``, and the microbatching ``MicrobatchServer`` — answers a
dense request with the same typed object:

    RetrievalResponse(scores, ids, status, queue_us)

replacing the old bare-``(scores, ids)`` vs ``(scores, ids, status)``
mismatch between the engine and the guard.  ``ServingStatus`` lives here
(not in ``serving.guard``) so the bare engine can stamp a healthy status
without importing the guard layer above it; ``serving.guard`` re-exports
it unchanged.

``RetrievalResponse`` is a NamedTuple with ``scores`` and ``ids`` first,
so positional access from the tuple era keeps meaning the same thing:
``resp[0]``/``resp[1]`` are the scores/ids panels and ``resp[:2]`` is the
old pair.  Full-tuple unpacking now yields four fields — legacy
two/three-target unpacks migrate to ``scores, ids, *_ = resp`` or
attribute access.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax


class ServingStatus(NamedTuple):
    """How a request was actually served — attached to every response.

    path:      name of the ladder rung that produced the answer.
    step:      rung index (0 = the configured primary path).
    degraded:  True whenever the answer differs in ANY way from what the
               healthy primary path would have returned (stepped-down
               rung, sanitized inputs, partial shard coverage).
    fault:     why serving left the primary path (None when healthy).
    shards_total / shards_used: mesh shard accounting (1/1 unsharded).
    coverage:  fraction of the candidate catalog actually scored — the
               recall bound for partial results (1.0 = full catalog).
    retries:   shard retry attempts spent before this answer.
    sanitized: count of non-finite query values zeroed at admission.
    deadline_exceeded: the budget ran out; the answer came from the
               cheapest remaining path rather than being dropped.
    """

    path: str
    step: int = 0
    degraded: bool = False
    fault: Optional[str] = None
    shards_total: int = 1
    shards_used: int = 1
    coverage: float = 1.0
    retries: int = 0
    sanitized: int = 0
    deadline_exceeded: bool = False


class RetrievalResponse(NamedTuple):
    """One served retrieval request: the answer plus how it was produced.

    scores / ids: the (Q?, n) top-n panels — exactly what the tuple-era
        API returned, in the same positions (``resp[0]``/``resp[1]``).
    status: the ``ServingStatus`` describing the path taken.  A bare
        ``RetrievalEngine`` stamps a healthy status (its configured path,
        step 0); the guard and the batcher stamp what actually happened.
    queue_us: host wall-clock the request spent queued before dispatch —
        0.0 for direct (unbatched) calls; the microbatcher fills it in.

    Where the time of the serve itself goes is recorded by each layer's
    ``serve.*`` profiler spans (listed in ``repro.serving``), not on the
    response.
    """

    scores: jax.Array
    ids: jax.Array
    status: ServingStatus
    queue_us: float = 0.0

    @property
    def pair(self) -> tuple[jax.Array, jax.Array]:
        """The tuple-era ``(scores, ids)`` view."""
        return self.scores, self.ids
