"""Readings of the program's own spans (``repro.obs``) over a run's window.

The volume service records its host work as spans: ``engine.submit``,
``engine.tick`` and, inside a tick, ``executor.fetch`` (the host waiting
for the device and copying its output back), and per request an
``engine.queued`` mark from submission to the tick that first pops one
of its patches.  A program that records no spans, or whose ring dropped
some the window needs, reads None.
"""

from __future__ import annotations

from typing import List, Optional

HOST_SPANS = ("engine.tick", "engine.submit")
WAIT_SPAN = "executor.fetch"
QUEUED = "engine.queued"


def _records(t0: float, t1: float = float("inf")):
    try:
        from repro import obs
    except ImportError:
        return None  # a program without a span recorder
    recs, dropped = obs.spans(t0, t1)
    return None if dropped else recs


def host_share(run) -> Optional[float]:
    """100 × (time in ``engine.tick`` and ``engine.submit`` less time in
    ``executor.fetch``, each clipped to the window) over the window."""
    w0, w1 = run.t_open, run.t_close
    recs = _records(w0, w1) if w1 > w0 else None
    if not recs:
        return None

    def inside(r):
        return max(0.0, min(r.t1, w1) - max(r.t0, w0))

    host = sum(inside(r) for r in recs if r.name in HOST_SPANS)
    if host <= 0:
        return None
    waiting = sum(inside(r) for r in recs if r.name == WAIT_SPAN)
    return 100.0 * (host - waiting) / (w1 - w0)


def queue_waits(run) -> List[float]:
    """Seconds each request due in the window spent queued (its
    ``engine.queued`` mark), matched by rid; a request the engine never
    popped has no mark and is left out (it counts in ``failed``)."""
    recs = _records(run.t_open)
    if not recs:
        return []
    waited = {r.attrs.get("rid"): r.t1 - r.t0 for r in recs if r.name == QUEUED}
    return [waited[r.rid] for r in run.attempted() if r.due is not None and r.rid in waited]
