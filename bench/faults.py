"""Faults planted in the timed path, to see ``correct`` come out false.

Each breaks what the executor's patch step returns, under the engine, so
that the whole run — scheduling, write-back, crop, the check — goes on as
usual over wrong answers:

* ``half_batch``: the second half of each batch of patch outputs is never
  computed (zeros in its place);
* ``answer_altered``: each patch output shifted by one voxel along z, as an
  indexing fault would.

A serving cell has no training state to leave unchanged, and one chip has
no exchange between chips to leave out.
"""

from __future__ import annotations

import contextlib

import numpy as np


def half_batch(ys):
    ys = np.array(ys)
    ys[len(ys) // 2 :] = 0.0
    return ys


def answer_altered(ys):
    return np.roll(np.asarray(ys), 1, axis=-1)


FAULTS = {"half_batch": half_batch, "answer_altered": answer_altered}


@contextlib.contextmanager
def planted(name: str):
    """``PlanExecutor.run_patch_batch`` broken by fault ``name`` while open."""
    from repro.volume.executor import PlanExecutor

    step, fault = PlanExecutor.run_patch_batch, FAULTS[name]

    def broken(self, xs, **kw):
        return fault(step(self, xs, **kw))

    PlanExecutor.run_patch_batch = broken
    try:
        yield
    finally:
        PlanExecutor.run_patch_batch = step
