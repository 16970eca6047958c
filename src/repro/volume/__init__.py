"""Volume inference runtime — run a planner Plan over arbitrary-size volumes.

README / architecture
=====================

ZNNi's output is a *plan* (patch size n_in, batch S, per-layer primitives,
strategy); this package is the runtime that turns a plan into dense output
over a volume far larger than any single patch:

┌────────────┐  PatchSpecs  ┌──────────────────────────────┐  (S,out,core³)
│  tiler     │ ───────────▶ │ PlanExecutor                 │ ──▶ dense
│ (geometry) │              │  CompiledPlan + jit-per-S    │     output
└────────────┘              └──────────────────────────────┘

* ``tiler``     — pure geometry.  Decomposes (X, Y, Z) into overlapping
  patches: interior starts at multiples of core = m·P, a shifted patch for
  the edge remainder (value-identical overlap), zero padding for axes
  shorter than one patch (exact, because valid-conv output v only reads
  input [v, v+FOV)).  MPF divisibility is checked, never re-derived.
* ``executor``  — ``PlanExecutor`` compiles the plan ONCE into a
  ``core.primitives.CompiledPlan`` (per-layer one-time setup via the
  primitive registry: cached kernel spectra for ``fft_cached``, per-layer
  pruned-FFT shapes, overlap-save segment grids, pool modes), then jits
  one prepared-layer walk per batch size — the prepared states are jit
  *arguments*, shared by all compiled sizes, so kernel FFTs run once per
  plan rather than once per patch.  Ragged tail batches run through a
  smaller compiled batch (no padded-and-discarded work;
  ``last_stats["padded_patches"]`` counts any remaining pipeline-stream
  padding).  MPF plans recombine fragments on device; plain-pool baseline
  plans sweep the P³ shifted subsamplings (the paper's naive outer loop);
  pipeline2 plans stream patch chunks through
  ``core.pipeline.pipelined_apply`` on the ``pod`` mesh axis, both stages
  walking the same CompiledPlan.  Plans whose first conv is
  ``overlap_save`` additionally reuse layer-0 input segment spectra
  between x-adjacent patches within a sweep (the FOV overlap transformed
  once — see ``core/overlap_save.py`` and docs/architecture.md).  Plans
  solved under a ``ram_budget`` execute host-staged (ISSUE 5): the volume
  stays in host RAM, one x-slab per plane double-buffers onto the device,
  caches evict per plane, and ``last_stats["peak_device_bytes"]`` (the
  executor's ledger) is pinned against ``Plan.memory``'s prediction.
  ``run`` fills ``last_stats`` with measured vs. planner-predicted vox/s,
  border waste included, plus ``os_seg_fft``/``os_seg_hits`` reuse
  counters and the memory counters.
* ``serving.volume_engine`` — ``VolumeEngine`` queues volume requests and
  continuously batches *patches across requests* into executor steps (the
  3D analogue of token-level continuous batching in ``serving/engine.py``);
  every request shares the executor's one CompiledPlan.

Entry points: ``examples/serve_volume.py`` (service demo) and
``bench/run.py`` (the benchmark's served cells, ``BENCHMARK.json``).

Test-suite conventions (repo-wide, recorded here per ISSUE 1):
* slow tests carry ``@pytest.mark.slow`` and are deselected by default via
  ``pytest.ini``; run them with ``-m "slow or not slow"``.
* hypothesis is optional: property tests import from
  ``tests/_hypothesis_compat.py``, which falls back to a deterministic
  boundary grid when hypothesis is missing.
"""

from .executor import PlanExecutor, tiled_apply  # noqa: F401
from .tiler import (  # noqa: F401
    PatchSpec,
    VolumeTiling,
    extract_patch,
    pad_volume,
    tile_for_net,
    tile_volume,
)
