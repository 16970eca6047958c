"""Plan-driven patch executor: run a planner ``Plan`` over a whole volume.

``PlanExecutor`` compiles a plan (per-layer primitives + patch geometry)
into a ``primitives.CompiledPlan`` — one-time per-layer setup: cached
kernel spectra for ``fft_cached`` layers, per-layer pruned-FFT shapes,
pool modes — and sweeps an arbitrary-size volume with jitted walks over
the prepared layers:

* patches come from the tiler (FOV overlap, shifted edge patches, zero
  padding for undersized axes);
* ``batch`` patches are stacked per compiled step (one XLA compile per
  batch size, cached — patch shape is fixed by the plan); the prepared
  states are jit arguments, shared by every batch size, so kernel FFTs
  run once per plan, not once per patch or compile; ragged tail batches
  run through a smaller compiled batch instead of padded-and-discarded
  work;
* MPF plans emit their full ``core³`` dense block per patch in one call
  (fragments recombined on device);
* plain-pool baseline plans sweep the P³ shifted subsamplings of each
  patch — the paper's naive "compute all subsamplings" outer loop —
  interleaving the strided outputs into the same dense core;
* ``pipeline2`` plans route the patch stream through
  ``core.pipeline.pipelined_apply`` (lax.scan over patches, stage hand-off
  across the ``pod`` mesh axis; queue depth 1 per §VII-C).

Patch-geometry invariants this module relies on (see ``tiler``): every
patch spans ``extent = core + FOV - 1`` input voxels and contributes a
``core³`` dense block; adjacent patches overlap by FOV-1 input voxels;
edge patches are *shifted* (value-identical overlap), and the patch stream
is x-major with non-decreasing x.

Overlap-save input-spectra reuse: when the plan's FIRST conv layer is
``overlap_save``, the layer-0 segment grid is pinned to the patch core
(``compile_plan(overlap_seg=core)``) so the segments of x-adjacent patches
land on identical absolute input coordinates.  Within one sweep the
executor caches segment spectra keyed by ``tiler.segment_keys`` — the
FOV-overlap a neighbour shares is transformed once, not once per patch
(ZNNi's border waste removed from the transform).  The cache is scoped to
a *sweep* (``begin_sweep``/``end_sweep``): volume edges (shifted patches,
different y/z rows) and new requests simply miss and recompute; eviction
rides the tiler's non-decreasing-x guarantee.  ``last_stats`` reports
``os_seg_fft`` (input segment FFTs actually run) and ``os_seg_hits``
(segments served from the cache).

Deep activation reuse (``deep_reuse``, default on for reuse-capable
plans): the sweep cache extends BELOW layer 0.  Every patch stores, per
layer l >= 1, the trailing ``size_l - 1`` x-columns of that layer's input
(the activation halo), keyed by the x-successor patch start — per-layer
coordinate frames stay aligned across patches because the patch stride
``core`` is divisible by every cumulative pooling factor, fragment
offsets included.  An *interior* patch (core-aligned x start whose left
neighbour completed in an earlier chunk) then runs the STRIP path: layer
0 pays MAD + inverse only for the ``tail_segments`` covering its new core
columns, and each deeper layer runs on ``new_x + size - 1`` assembled
columns (cached halo + newly computed strip) instead of the full patch
extent — the FOV-1 overlap is never recomputed at any depth.  Interior
and edge patches of one chunk run as two fused jit calls; eligibility is
decided against the halo cache as of the chunk start, so batches never
race on intra-chunk dependencies.  ``last_stats`` adds
``os_mad_segments`` (per-segment MAD+inverse passes actually run),
``deep_strip_patches``/``deep_full_patches``, and ``retraces`` (distinct
jit specializations seen).  ``predict_counts`` returns the planner-side
``SweepCounts`` for a volume shape — by construction these equal the
measured counters exactly (the sweep-aware planning acceptance property).
Every step walks layers 1.. through one walk (``_walk_below_input``: the
full, capture and strip walks differ only in their layers, cached leads
and ``capture``), and every batch maps segment keys to cache entries
through one resolver (``_resolve_segments``).  ``fuse_os`` additionally
routes eligible ``fft_cached``+``mpf`` pairs of the capture and strip
walks through the halo-emitting fused epilogue
(``fft_conv_pool_fused_halo``): the pool input is never materialized as a
walk step but its trailing columns still reach the halo cache via the
fused call's second output — dense output and exported halos are bitwise
equal to the unfused walks off the Pallas path.  ``last_stats`` adds
``fused_pair_calls`` ((strip+full patches) × eligible pairs) and
``os_fused_segments`` (segments run through the fused Pallas segment
kernel; equals ``os_mad_segments`` on the Pallas path, else 0).

Host-staged streaming (``ram_budget``/``streaming``, ISSUE 5): a plan
solved under a RAM budget executes with the volume resident in HOST
memory only.  Chunks are capped at x-plane boundaries
(``tiler.chunk_patches``) so each chunk reads one constant-shape input
x-slab ``[x0, x0 + span)``; ``_slab`` double-buffers the next plane's
slab onto the device while the current fused step runs, and the per-key
eviction sweep (``_evict_left_of``) frees segment spectra, activation
halos, and slabs the stream moved past — miss spectra are stored split
by absolute segment x (``_store_spectra``) precisely so eviction
releases real buffers.  The fused-step programs are identical to the
dense mode's (only the volume operand and slab-relative miss starts
change), so streamed output is bitwise-equal to the dense path.  A
``_DeviceLedger`` accounts every executor-managed device buffer;
``last_stats["peak_device_bytes"]`` reports the per-sweep peak and
``predict_memory``/``Plan.memory`` reproduce it analytically (the
memory-model contract in docs/architecture.md).

``run`` returns the dense (out_ch, X-FOV+1, ...) output and records
``last_stats`` (patch/batch counts, wall seconds, measured vox/s with border
waste, the planner's predicted vox/s, measured/predicted peak device bytes);
its seconds and vox/s time that offline sweep alone.  On the served path,
``counter_stats`` gives the reuse counters and the conv FLOPs dispatched
(``conv_flops``) that a ``VolumeEngine`` tick publishes,
and ``repro.obs`` spans time the host work (``executor.begin_sweep``,
``executor.resolve``, ``executor.dispatch``, ``executor.store``,
``executor.fetch``): a single-sweep batch dispatches every group and stores
its spectra and halos, then one fetch blocks on the device.  The jitted
programs trace under named scopes (``os_step``, ``os_strip_step``, ``os_walk``,
``walk``; in them ``L0.spectra``, ``L<i>.<prim>`` and ``recombine``), so each
device op of a profiler trace names the program and the layer that issued it.
"""

from __future__ import annotations

import itertools
import math
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..configs.base import ConvNetConfig
from ..core import overlap_save as os_mod
from ..core.fft_conv import fft_conv_pool_fused_halo
from ..core.mpf import recombine_fragments
from ..core.pipeline import hetero_stage_devices, make_stage_fns, pipelined_apply
from ..core.planner import Plan
from ..core.primitives import (
    CompiledPlan,
    PreparedLayer,
    activate,
    compile_plan,
    conv_primitive,
    fused_pair_starts,
    plan_input_size,
    pool_primitive,
    resolve_primitive,
)
from ..kernels import resolve_use_pallas
from ..tuning import TunedConfig, load_tuned_config
from .tiler import (
    HaloSpec,
    SweepCounts,
    VolumeTiling,
    chunk_patches,
    extract_patch,
    pad_volume,
    predict_sweep_counts,
    sweep_perm,
    tile_volume,
)


def _permute_conv_params(params, net: ConvNetConfig, perm: Tuple[int, int, int]):
    """Permute conv kernels into the working frame of a sweep axis.

    The sweep machinery runs in the tiler's working frame (sweep axis =
    spatial axis 0).  Valid correlation commutes with a joint permutation
    of data and kernel spatial axes: if ``x_work = transpose(x, perm)``
    then ``conv(x, w)`` permutes to ``conv(x_work, transpose(w, perm))``
    — so permuting every conv weight by the SAME spatial permutation as
    the volume makes the whole compiled stack axis-generic with no kernel
    changes (pools, bias, ReLU, and MPF recombination are isotropic).
    Identity perm returns ``params`` unchanged (same objects).
    """
    if perm == (0, 1, 2):
        return params
    axes = (0, 1, 2 + perm[0], 2 + perm[1], 2 + perm[2])
    out = []
    for p, layer in zip(params, net.layers):
        if layer.kind == "conv" and p is not None:
            w, b = p
            out.append((jnp.transpose(w, axes), b))
        else:
            out.append(p)
    return out


def _flat_halo(h: jnp.ndarray) -> jnp.ndarray:
    """(B, f, w, ny, nz) -> (B, f, w·ny·nz): the form a halo is cached in.

    A halo waits on the device for its x-successor, beside the full steps
    that run meanwhile.  Laid out 5-D, its minor dims pad to whole (8, 128)
    tiles (f=80 or n=44 to 128 lanes on a TPU: up to 1.6x the data); one
    flat minor axis pads at most one lane tile.
    """
    return h.reshape(h.shape[:2] + (-1,))


def _unflat_halo(h: jnp.ndarray, cross: Tuple[int, int]) -> jnp.ndarray:
    """Inverse of ``_flat_halo`` for a halo of cross extents ``cross``."""
    return h.reshape(h.shape[:2] + (-1,) + tuple(cross))


def _layer_states(strip) -> Optional[List[Any]]:
    """The strip walk's state pytree from ``_build_strip_plan``'s
    ``(layers, info)`` (None without deep reuse)."""
    if strip is None:
        return None
    return [pl.state if pl is not None else None for pl in strip[0]]


class _PendingMiss(NamedTuple):
    """Sweep-cache placeholder: this key's spectrum is being computed in
    the current batch as miss row ``idx`` (dedups within-batch repeats)."""

    idx: int


class _SpectrumRef(NamedTuple):
    """Sweep-cache entry: row ``idx`` of a stored miss-FFT output array.

    Rows are never copied out — the fused step receives the parent arrays
    as jit arguments and selects rows at trace time, so a cache hit costs
    no host work at all.  Parents are split by absolute segment x at
    storage time (all rows of one parent share one x), so the per-key
    eviction sweep actually frees device memory: a still-needed tail
    segment can never pin an otherwise-dead batch buffer alive.
    """

    parent: Any  # (M, f, ña, ñb, ñc) device array; one absolute x per parent
    idx: int


class _DeviceLedger:
    """Accounting of the executor-managed device working set (bytes).

    ``current`` tracks buffers the executor holds across steps (prepared
    states, staged slabs, cached segment spectra, activation halos, a
    non-streaming sweep's resident volume); ``transient`` samples a
    step's in-flight extras (patch inputs, chunk outputs, miss spectra,
    freshly captured halos) on top of ``current``.  ``peak`` is the
    number ``last_stats["peak_device_bytes"]`` reports and the planner's
    ``predict_stream_peak`` simulation reproduces: both sides count the
    same objects at the same points, which is what makes the prediction
    pinnable within 10%.  (jit-internal scratch — FFT temporaries inside
    a fused step — is modelled on the planner side as per-layer
    ``LayerCost`` stage peaks, not measured here.)
    """

    def __init__(self) -> None:
        self.current = 0.0
        self.peak = 0.0

    def alloc(self, nbytes: float) -> None:
        self.current += nbytes
        if self.current > self.peak:
            self.peak = self.current

    def free(self, nbytes: float) -> None:
        self.current = max(0.0, self.current - nbytes)

    def transient(self, nbytes: float) -> None:
        """A step's extra in-flight bytes: bumps peak, not current."""
        if self.current + nbytes > self.peak:
            self.peak = self.current + nbytes

    def begin_run(self) -> None:
        """Scope the peak to one sweep (states/caches carry over)."""
        self.peak = self.current


def _tree_nbytes(*trees) -> float:
    """Total bytes of the distinct array buffers in the given pytrees."""
    seen: Dict[int, float] = {}
    for leaf in jax.tree_util.tree_leaves(list(trees)):
        n = getattr(leaf, "nbytes", None)
        if n is not None:
            seen[id(leaf)] = float(n)
    return sum(seen.values())


class PlanExecutor:
    """Bind a Plan (or explicit prims + fragment size) to a volume sweep."""

    def __init__(
        self,
        params,
        net: ConvNetConfig,
        plan: Optional[Plan] = None,
        *,
        prims: Optional[Sequence[str]] = None,
        m: Optional[int] = None,
        batch: Optional[int] = None,
        theta: int = -1,
        use_pallas: Optional[bool] = None,
        fuse_pairs: Optional[bool] = None,
        fprime_chunk=None,
        fuse_os: Optional[bool] = None,
        tuned: Union[str, TunedConfig, None] = "auto",
        deep_reuse: bool = True,
        ram_budget: Optional[float] = None,
        streaming: Optional[bool] = None,
        sweep_axis: Optional[int] = None,
    ):
        # default sweep axis: explicit arg > the plan's costed choice > x.
        # All geometry below lives in that axis's working frame; the conv
        # weights are permuted to match (see _permute_conv_params), so the
        # compiled stack keeps its axis-0 machinery unchanged.
        if sweep_axis is None:
            sweep_axis = getattr(plan, "sweep_axis", 0) if plan is not None else 0
        self.sweep_axis = int(sweep_axis)
        self._orig_params = params
        self.params = _permute_conv_params(
            params, net, sweep_perm(self.sweep_axis)
        )
        self.net = net
        self.plan = plan
        # per-hardware tuned config (repro.tuning): ``"auto"`` loads the
        # persisted winner for (this device kind, net.name) if one exists.
        # A tuned config fills only knobs the caller left unset — and only
        # the execution knobs (use_pallas / fuse_pairs / fprime_chunk) when
        # a Plan is given: m/batch are part of the planner's costed
        # geometry contract (predicted == measured counters) and are taken
        # from the tuner only on plan-less explicit-prims construction.
        self.tuned: Optional[TunedConfig] = (
            load_tuned_config(net.name) if tuned == "auto"
            else (tuned if isinstance(tuned, TunedConfig) else None)
        )
        if self.tuned is not None:
            if use_pallas is None:
                use_pallas = self.tuned.use_pallas
            if fuse_pairs is None:
                fuse_pairs = self.tuned.fuse_pairs
            if fprime_chunk is None:
                fprime_chunk = self.tuned.fprime_chunk
            if fuse_os is None:
                fuse_os = getattr(self.tuned, "fuse_os", None)
            if plan is None and prims is not None:
                m = m if m is not None else self.tuned.m
                batch = batch if batch is not None else self.tuned.batch
        if plan is not None:
            prims = plan.prims
            m = plan.m_final
            batch = batch or plan.batch
            theta = plan.theta if plan.strategy in ("pipeline2", "hetero") else -1
            if ram_budget is None:
                ram_budget = plan.ram_budget
        # hetero plans run the split as a TWO-BACKEND pipeline (stage 0 on
        # the host CPU backend, stage 1 on the default accelerator, host
        # RAM as the hand-off medium) instead of the pod-axis scan
        self.hetero = plan is not None and plan.strategy == "hetero"
        if prims is None or m is None:
            raise ValueError("need either a Plan or explicit prims + m")
        # a plan solved under a RAM budget executes in the mode that honors
        # it: host-staged streaming (the volume never becomes device-
        # resident in full).  ``streaming`` can force either mode.
        self.ram_budget = ram_budget
        self.streaming = (
            bool(streaming) if streaming is not None else ram_budget is not None
        )
        self.prims = tuple(prims)
        self.m = m
        self.batch = max(1, batch or 1)
        self.theta = theta
        self.use_pallas = resolve_use_pallas(use_pallas)

        self.P = net.total_pooling()
        self.fov = net.field_of_view()
        self.core = m * self.P
        self.uses_mpf = "mpf" in self.prims
        # input voxels per axis a patch spans: n_in for MPF; the plain-pool
        # baseline sweeps P³ shifted n_in-windows, needing core + fov - 1.
        self.n_in = self._n_in()
        self.extent = self.n_in if self.uses_mpf else self.n_in + self.P - 1
        assert self.extent == self.core + self.fov - 1, (
            self.extent, self.core, self.fov
        )
        self.out_channels = [l for l in net.layers if l.kind == "conv"][-1].out_channels
        # overlap-save input-spectra reuse: active when the patch walk starts
        # with an overlap_save conv over the full patch extent (MPF plans; the
        # plain-pool subsampling sweep slices shifted sub-windows, which
        # breaks segment alignment).  Deep activation reuse: interior patches
        # run a strip walk assembled from cached per-layer activation halos
        # (see module docstring).
        self._os_reuse = self.prims[0] == "overlap_save" and self.uses_mpf
        self.deep_reuse = bool(self._os_reuse and deep_reuse)

        # one-time setup for every layer (cached kernel spectra, per-layer
        # FFT shapes, pool modes) — shared by every compiled batch size and
        # by the pipeline2 stage functions; ``fuse_pairs`` is None until
        # compile_plan resolves it.
        self.fuse_pairs = fuse_pairs
        self._fprime_chunk = fprime_chunk
        self.compiled, strip = self._prepare_frame(self.params)
        self.fuse_pairs = self.compiled.fuse_pairs

        recombine = self.uses_mpf

        def _walk(states, xs):
            with jax.named_scope("walk"):
                return self.compiled.apply(xs, states=states, recombine=recombine)

        # one jitted walk; jax.jit specializes (retraces) per patch-batch
        # shape, while the prepared states stay shared call arguments.
        self._jit_walk = jax.jit(_walk)
        self._seen_batch_sizes: set = set()
        self._pipeline_fn = None
        self._hetero_fns = None
        self._hetero_stats: Dict[str, float] = {}
        self.last_stats: Dict[str, float] = {}

        # -- overlap-save input-spectra reuse state --------------------------
        self._sweeps: Dict[int, Dict[Tuple[int, int, int], jnp.ndarray]] = {}
        self._sweep_vols: Dict[int, jnp.ndarray] = {}  # non-streaming scopes
        self._sweep_hosts: Dict[int, np.ndarray] = {}  # streaming scopes
        self._sweep_slabs: Dict[int, Dict[int, jnp.ndarray]] = {}
        self._key_bytes: Dict[Tuple[int, Tuple[int, int, int]], float] = {}
        self._sweep_counter = 0
        self._os_misses = 0
        self._os_hits = 0
        self._os_mad_segments = 0
        self._deep_strips = 0
        self._deep_fulls = 0
        # convolution FLOPs of every dispatched step, from the layers' static
        # shapes: what a patch's full walk and its strip walk compute
        self._conv_flops = 0
        self._full_conv_flops = self._walk_conv_flops(strip=False)
        self._strip_conv_flops = self._walk_conv_flops(strip=True)
        # the conv+pool pairs below the input the halo-emitting fused
        # epilogue serves in the walks below layer 0 (``fused_pair_starts``)
        self._fused_pairs = fused_pair_starts(net, self.compiled.layers[1:])
        # fused halo-emitting epilogue in the capture/strip walks: off by
        # default (conservative; tuned configs switch it on per hardware),
        # and a no-op unless the plan has fusable pairs and runs the
        # overlap-save reuse walks at all
        self.fuse_os = bool(fuse_os) and bool(self._fused_pairs) and self._os_reuse
        self._trace_keys: set = set()  # distinct jit specializations seen
        self._halo_caches: Dict[int, Dict[Tuple[int, int, int], List]] = {}
        if self._os_reuse:
            spec0 = self.compiled.layers[0].os_spec
            self._jit_os_walk = jax.jit(self._os_walk)
            # the fused per-batch step retraces per miss/hit *pattern*; the
            # tiler produces only a handful (first row, interior row,
            # shifted edge row) per batch size
            self._jit_os_step = jax.jit(self._os_step, static_argnames=("pattern",))
            self.halo = HaloSpec(spec0.seg_core, spec0.seg_extent, spec0.starts)
        else:
            self.halo = None
        if self.deep_reuse:
            spec0 = self.compiled.layers[0].os_spec
            # trailing segments covering an interior patch's new core columns
            self._q_strip = os_mod.tail_segments(spec0, self.core)
            self._strip_layers, self._strip_info = strip
            self._jit_os_strip_step = jax.jit(
                self._os_strip_step, static_argnames=("pattern",)
            )
        else:
            self._q_strip = None
        # per-axis prepared states for mixed-axis serving: every sweep
        # scope records its axis (``_sweep_axes``); scopes on the default
        # axis use the primary compiled/strip states, other axes get their
        # own state pytrees lazily (``_states_for_axis``) — metadata and
        # jitted step programs are shared, since cubic patches/kernels make
        # every working frame shape-identical.
        self._sweep_axes: Dict[int, int] = {}
        self._axis_states: Dict[int, Tuple[Any, Any]] = {
            self.sweep_axis: (self.compiled.states, _layer_states(strip))
        }
        # device-working-set ledger: prepared states (weights, cached kernel
        # spectra at full AND strip shapes) are resident for the executor's
        # lifetime; sweeps add slabs/caches on top.
        self._ledger = _DeviceLedger()
        self._ledger.alloc(
            _tree_nbytes(self.params, *self._axis_states[self.sweep_axis])
        )
        self._predict_memory_cache: Dict[Tuple[int, int, int], Any] = {}

    def tuned_provenance(self) -> Optional[Dict[str, Any]]:
        """The tuned config this executor runs under (bench-row provenance
        dict, see ``TunedConfig.provenance``) — ``None`` when untuned."""
        return None if self.tuned is None else self.tuned.provenance()

    # -- geometry ------------------------------------------------------------

    def _n_in(self) -> int:
        """Input size per apply call, from the net walked backwards.

        ``primitives.plan_input_size`` generalizes ``net.valid_input_size``
        / ``planner._n_in_for_m`` to per-layer primitive assignments (those
        assume all pools are MPF or none are); the ``extent`` assertion in
        __init__ cross-checks the walks against the shared core/FOV
        identity.
        """
        return plan_input_size(self.net, self.prims, self.m)

    def _walk_conv_flops(self, strip: bool) -> int:
        """Σ 2·k³·f·f' × conv output voxels of one patch's walk.

        The full walk convolves every MPF fragment at every layer's whole
        extent (P³ shifted passes where the pools are plain); the strip
        walk's convs output only the patch's new core columns in x.
        """
        n, p_cur, frag, f, total = self.n_in, 1, 1, self.net.in_channels, 0
        for i, layer in enumerate(self.net.layers):
            if layer.kind == "conv":
                k, fo = layer.size, layer.out_channels
                n = n - k + 1
                cols = self.core // p_cur if strip else n
                total += 2 * k**3 * f * fo * frag * cols * n * n
                f = fo
            else:
                n //= layer.size
                p_cur *= layer.size
                if self.prims[i] == "mpf":
                    frag *= layer.size**3
        return total if self.uses_mpf else total * self.P**3

    def tiling_for(
        self, vol_shape: Sequence[int], *, sweep_axis: Optional[int] = None
    ) -> VolumeTiling:
        return tile_volume(
            vol_shape, core=self.core, fov=self.fov, halo=self.halo,
            sweep_axis=self.sweep_axis if sweep_axis is None else int(sweep_axis),
        )

    def bucket_shape(self, vol_shape: Sequence[int]) -> Tuple[int, int, int]:
        """Round a volume shape up to the executor's patch-grid bucket.

        Axes are padded so the dense output is a whole number of cores:
        the padded shapes of arbitrary requests collapse onto a small set
        of buckets, every patch start is core-aligned (no shifted edge
        patches, maximum cross-patch reuse), and the fused per-batch jit
        step — keyed on the device-resident volume's shape — stops
        retracing per distinct request size.  Exact by the pad-and-crop
        argument: outputs over the padding are simply never written.
        Raises for axes below the FOV — the same no-valid-output contract
        ``tile_volume`` enforces on unbucketed shapes.
        """
        for ax, x in enumerate(vol_shape):
            if x < self.fov:
                raise ValueError(
                    f"axis {ax} extent {x} < FOV {self.fov}: no valid output exists"
                )
        return tuple(
            math.ceil((x - self.fov + 1) / self.core) * self.core
            + self.fov - 1
            for x in vol_shape
        )

    def predict_counts(
        self, vol_shape: Sequence[int], *, batch: Optional[int] = None,
        sweep_axis: Optional[int] = None,
    ) -> SweepCounts:
        """Planner-side prediction of this executor's sweep counters.

        Simulates the sweep caches over the exact tiling ``run`` would
        use (same ``sweep_axis``, default the executor's); the returned
        counts equal the measured ``last_stats`` counters 1:1 (the
        sweep-aware planning acceptance property, for every axis).
        """
        if not self._os_reuse:
            raise ValueError("predict_counts needs an overlap-save reuse plan")
        tiling = self.tiling_for(vol_shape, sweep_axis=sweep_axis)
        return predict_sweep_counts(
            tiling, batch=batch or self.batch,
            deep_reuse=self.deep_reuse, strip_segments=self._q_strip,
        )

    def _states_for_axis(self, axis: int):
        """Prepared state pytrees ``(states, strip_states)`` for one axis.

        Metadata (segment specs, FFT shapes, pool modes) is axis-
        independent — patches and kernels are cubic, so every working
        frame is shape-identical and all axes share the same jitted step
        functions (and their compiled programs).  Only the numeric state
        buffers differ: weights and cached kernel spectra permuted into
        that axis's working frame.  Non-default axes are built lazily and
        ledger-accounted like the primary states.
        """
        got = self._axis_states.get(axis)
        if got is None:
            compiled, strip = self._prepare_frame(
                _permute_conv_params(self._orig_params, self.net, sweep_perm(axis))
            )
            got = self._axis_states[axis] = (compiled.states, _layer_states(strip))
            self._ledger.alloc(_tree_nbytes(*got))
        return got

    def _prepare_frame(self, params) -> Tuple[CompiledPlan, Optional[Tuple]]:
        """``(compiled, strip)`` for one working frame's conv weights.

        Every axis's prepared layers come from here: the primary axis's at
        construction, the others' in ``_states_for_axis``.  A first-layer
        overlap_save conv gets its segment grid pinned to the patch core so
        x-adjacent patches share segment spectra (cross-patch input-FFT
        reuse).  ``strip`` is ``_build_strip_plan``'s ``(layers, info)``
        under deep reuse, else None.
        """
        compiled = compile_plan(
            params, self.net, prims=self.prims, n_in=self.n_in,
            use_pallas=self.use_pallas, fuse_pairs=self.fuse_pairs,
            fprime_chunk=self._fprime_chunk, plan=self.plan,
            overlap_seg=self.core if self.prims[0] == "overlap_save" else None,
        )
        strip = self._build_strip_plan(params) if self.deep_reuse else None
        return compiled, strip

    def _build_strip_plan(self, params):
        """One-time setup of the interior-patch strip walk (layers >= 1).

        For each layer below the input, bind its primitive to the strip
        extent an interior patch runs: ``new_x + size - 1`` sweep-axis
        columns (the newly computed columns plus the cached activation
        halo) at the full-walk cross extents.  Returns ``(layers, info)``
        where ``layers[i]`` is the strip ``PreparedLayer`` (None at 0 —
        layer 0 runs through the segment-spectra tail) and ``info[i] =
        (halo columns, fragment batch multiplier at this layer's input)``.
        ``params`` are the conv weights in one axis's working frame.
        """
        n = self.n_in  # full-walk spatial extent entering each layer
        P_cur, frag = 1, 1
        layers: List[Optional[PreparedLayer]] = [None] * len(self.net.layers)
        info: List[Optional[Tuple[int, int]]] = [None] * len(self.net.layers)
        for i, layer in enumerate(self.net.layers):
            if i > 0:
                new_x = self.core // P_cur
                h = layer.size - 1
                w_in = new_x + h
                assert w_in <= n, (i, w_in, n)
                if layer.kind == "conv":
                    w, b = params[i]
                    layers[i] = conv_primitive(self.prims[i]).setup(
                        w, b, (w_in, n, n), index=i
                    )
                else:
                    layers[i] = pool_primitive(self.prims[i]).setup(
                        layer.size, (w_in, n, n), index=i
                    )
                info[i] = (h, frag)
            if layer.kind == "conv":
                n = n - layer.size + 1
            else:
                n = n // layer.size
                P_cur *= layer.size
                frag *= layer.size**3
        return tuple(layers), tuple(info)

    def _record_trace(self, key: Tuple) -> None:
        """Track distinct jit specializations (last_stats["retraces"])."""
        self._trace_keys.add(key)

    # -- overlap-save sweep cache -------------------------------------------

    def begin_sweep(
        self, padded: np.ndarray, *, sweep_axis: Optional[int] = None
    ) -> int:
        """Open a fresh spectra-reuse scope (one volume sweep / request).

        Scoping the cache to a sweep is what makes reuse safe: segment keys
        are absolute coordinates *within one padded volume swept on one
        axis*, so spectra must never leak across requests — and distinct
        sweep axes are simply distinct scopes, which is what lets one
        serving tick batch mixed-axis requests with no key collisions.
        ``padded`` must already be in ``sweep_axis``'s working frame
        (``tiler.pad_volume`` of a matching tiling); the default is the
        executor's axis.  The volume is extended along working axis 0 so
        the aligned grid's tail segments stay in bounds (the extra voxels
        are zeros; exact, because the outputs they influence are cropped),
        then either uploaded to the device once (dense mode) or kept in
        HOST RAM (streaming mode) — the streaming sweep stages one slab
        per plane on demand (``_slab``), so peak device bytes scale with
        the slab, not the volume.
        """
        with obs.span("executor.begin_sweep", bytes=int(padded.nbytes)):
            spec0 = self.compiled.layers[0].os_spec
            max_x0 = max(0, padded.shape[1] - self.extent)
            short = max(0, max_x0 + spec0.span - padded.shape[1])
            self._sweep_counter += 1
            token = self._sweep_counter
            self._sweeps[token] = {}
            self._sweep_axes[token] = (
                self.sweep_axis if sweep_axis is None else int(sweep_axis)
            )
            if self.streaming:
                host = np.asarray(padded, np.float32)
                if short:
                    host = np.pad(host, ((0, 0), (0, short), (0, 0), (0, 0)))
                self._sweep_hosts[token] = host
                self._sweep_slabs[token] = {}
            else:
                vol = jnp.asarray(padded)
                if short:
                    vol = jnp.pad(vol, ((0, 0), (0, short), (0, 0), (0, 0)))
                self._sweep_vols[token] = vol
                self._ledger.alloc(vol.nbytes)
            return self._sweep_counter

    def end_sweep(self, token: Optional[int]) -> None:
        self._sweep_axes.pop(token, None)
        vol = self._sweep_vols.pop(token, None)
        if vol is not None:
            self._ledger.free(vol.nbytes)
        self._sweep_hosts.pop(token, None)
        for slab in self._sweep_slabs.pop(token, {}).values():
            self._ledger.free(slab.nbytes)
        for key in self._sweeps.pop(token, {}):
            self._ledger.free(self._key_bytes.pop((token, key), 0.0))
        for entry in self._halo_caches.pop(token, {}).values():
            self._ledger.free(sum(h.nbytes for h in entry))

    # -- host-staged streaming slabs ----------------------------------------

    def _slab(self, token: int, x0: int) -> jnp.ndarray:
        """Device-stage the input x-slab ``[x0, x0 + span)`` of a sweep.

        Every chunk of a plane reads the same constant-shape slab (the
        plane cap in ``tiler.chunk_patches`` guarantees it), so the fused
        step's volume operand never retraces on shape.  Already-staged
        slabs are returned as-is — the double-buffer prefetch in
        ``_run_batched`` stages the next plane's slab while the current
        chunk runs.
        """
        slabs = self._sweep_slabs.setdefault(token, {})
        slab = slabs.get(x0)
        if slab is None:
            host = self._sweep_hosts[token]
            spec0 = self.compiled.layers[0].os_spec
            slab = jnp.asarray(host[:, x0 : x0 + spec0.span])
            slabs[x0] = slab
            self._ledger.alloc(slab.nbytes)
        return slab

    def _drop_slabs(self, token: int, keep) -> None:
        slabs = self._sweep_slabs.get(token, {})
        for x0 in [x for x in slabs if x not in keep]:
            self._ledger.free(slabs.pop(x0).nbytes)

    def _evict_left_of(self, token: int, x_lo: int) -> None:
        """Free every cache entry strictly left of ``x_lo`` (both caches).

        Exact by the tiler's non-decreasing-x patch stream: no later patch
        of this sweep can resolve an evicted key.  Because stored spectra
        parents are split by absolute x, eviction really releases the
        device buffers (and the ledger records it).
        """
        cache = self._sweeps.get(token, {})
        for dead in [k for k in cache if k[0] < x_lo]:
            del cache[dead]
            self._ledger.free(self._key_bytes.pop((token, dead), 0.0))
        halo_cache = self._halo_caches.get(token)
        if halo_cache:
            for dead in [k for k in halo_cache if k[0] < x_lo]:
                self._ledger.free(sum(h.nbytes for h in halo_cache.pop(dead)))
        if self.streaming:
            self._drop_slabs(
                token,
                {x for x in self._sweep_slabs.get(token, {}) if x >= x_lo},
            )

    # -- shard boundary handoff (sharded serving fleet) ----------------------

    def export_handoff(self, token: int, x_lo: int):
        """Stage this sweep scope's boundary caches out to host.

        Returns a ``distributed.collectives.HaloPackage`` holding every
        segment-spectra row and activation-halo entry whose absolute-x key
        is >= ``x_lo`` — exactly the entries a single-device sweep would
        still hold when its next chunk starts at plane ``x_lo`` (everything
        left of it is ``_evict_left_of`` food).  Rows are materialized to
        host ndarrays (output-to-host staging), so the package can cross
        workers; re-import round-trips bit-exactly (no arithmetic touches
        the values, only copies).
        """
        from repro.distributed.collectives import HaloPackage

        spectra = {}
        for key, ref in self._sweeps.get(token, {}).items():
            if key[0] >= x_lo and isinstance(ref, _SpectrumRef):
                spectra[key] = np.asarray(ref.parent[ref.idx])
        halos = {}
        for key, entry in self._halo_caches.get(token, {}).items():
            if key[0] >= x_lo:
                halos[key] = tuple(np.asarray(h) for h in entry)
        return HaloPackage(x_lo=x_lo, spectra=spectra, halos=halos)

    def import_handoff(self, token: int, pkg) -> None:
        """File a predecessor shard's boundary package into this scope.

        Spectra rows are grouped by absolute segment x and uploaded as one
        parent per x (the same split ``_store_spectra`` maintains, so the
        per-key eviction sweep keeps really freeing device memory); halo
        entries upload per key.  The ledger accounts both, mirroring what
        a single-device sweep would have resident at this boundary.
        """
        if pkg is None or pkg.is_empty():
            return
        cache = self._sweeps.setdefault(token, {})
        by_x: Dict[int, List] = {}
        for key in sorted(pkg.spectra):
            by_x.setdefault(key[0], []).append(key)
        for _x, keys in sorted(by_x.items()):
            parent = jnp.asarray(np.stack([pkg.spectra[k] for k in keys]))
            share = parent.nbytes / len(keys)
            self._ledger.alloc(parent.nbytes)
            for i, key in enumerate(keys):
                cache[key] = _SpectrumRef(parent, i)
                self._key_bytes[(token, key)] = share
        halo_cache = self._halo_caches.setdefault(token, {})
        for key in sorted(pkg.halos):
            entry = [jnp.asarray(h) for h in pkg.halos[key]]
            halo_cache[key] = entry
            self._ledger.alloc(sum(h.nbytes for h in entry))

    def handoff_entry_nbytes(self) -> Tuple[int, int]:
        """Per-entry byte sizes of boundary-package contents.

        Returns ``(seg_row_bytes, halo_entry_bytes)``: one layer-0 segment
        spectrum row is the complex64 rfftn of an (f_in, *fft_shape) block;
        one activation-halo entry stacks, per layer below the input, the
        (frag, C_in, size-1, n, n) float32 capture of the strip walk, kept
        lane-flat as (frag, C_in, (size-1)·n·n).
        Every key's entry has the same size (patch extent is constant), so
        ``predict_shard_handoff`` counts x per-entry sizes give the exact
        exchanged bytes.
        """
        if not self._os_reuse:
            raise ValueError("handoff accounting needs an overlap-save plan")
        spec0 = self.compiled.layers[0].os_spec
        fa, fb, fc = spec0.fft_shape
        seg_row = self.net.in_channels * fa * fb * (fc // 2 + 1) * 8
        halo_entry = 0
        if self.deep_reuse:
            c = self.net.in_channels
            n = self.n_in
            for i, layer in enumerate(self.net.layers):
                if i > 0:
                    h, frag = self._strip_info[i]
                    halo_entry += frag * c * h * n * n * 4
                if layer.kind == "conv":
                    c = layer.out_channels
                    n = n - layer.size + 1
                else:
                    n = n // layer.size
        return int(seg_row), int(halo_entry)

    def _walk_below_input(self, layers, states, x, S, *, capture, leads=None):
        """Layers 1.. over a layer-0 output: the one walk of every step.

        ``layers``/``states`` are the full walk's prepared layers (the full
        and capture walks) or the strip walk's (``_build_strip_plan``).
        Applies each layer in turn, ReLU by the whole-net rule
        (``primitives.activate``).  ``leads`` (strip walk) holds the left
        neighbour's cached activation halos: ``leads[i-1]`` is concatenated
        in front of layer i's input, so each layer runs on ``new_x + size -
        1`` columns.  When ``capture`` (deep reuse on), records per layer
        the trailing ``size - 1`` x-columns of its input, after any lead is
        concatenated: the halos the next x-patch's strip walk assembles
        from.  ``capture`` is a trace-time constant — jitted callers that
        discard halos (deep reuse off, the mixed-sweep fallback) must not
        materialize them as jit outputs.  Returns ``(out, halos)``.

        With ``fuse_os`` every pair ``_fused_pairs`` names dispatches to
        ``fft_conv_pool_fused_halo``: the pool layer's input is never a
        separate walk step, yet its trailing columns still reach the halo
        cache via the fused call's second output, with the pool's lead
        ``leads[i]`` assembled inside the call (bitwise-identical to the
        unfused walk off the Pallas path).  Layers trace under the named
        scopes ``apply_prepared_range`` uses.
        """
        halos = []
        i = 1
        while i < len(layers):
            pl = layers[i]
            size = self.net.layers[i].size
            fused = self.fuse_os and i in self._fused_pairs
            with jax.named_scope(f"L{i}.{pl.prim}" + ("_mpf" if fused else "")):
                if leads is not None:
                    lead = _unflat_halo(leads[i - 1], x.shape[3:])
                    x = jnp.concatenate([lead, x], axis=2)
                if capture:
                    halos.append(_flat_halo(x[:, :, -(size - 1):]))
                if fused:
                    nxt = layers[i + 1]
                    pool_lead = None
                    if leads is not None:
                        pool_lead = _unflat_halo(
                            leads[i], tuple(n - size + 1 for n in x.shape[3:])
                        )
                    x, pool_halo = fft_conv_pool_fused_halo(
                        x, states[i]["W"], states[i]["b"],
                        fft_shape=pl.fft_shape, k=pl.kernel_size,
                        p=nxt.pool_size, halo_cols=nxt.pool_size - 1,
                        lead=pool_lead, use_pallas=self.use_pallas,
                        fprime_chunk=pl.fprime_chunk,
                    )
                    if capture:
                        halos.append(_flat_halo(pool_halo))
                    i += 2
                    continue
                x = resolve_primitive(pl).apply(
                    pl, x, states[i], use_pallas=self.use_pallas
                )
                x = activate(self.net, i, x)
            i += 1
        if self.uses_mpf:
            with jax.named_scope("recombine"):
                x = recombine_fragments(x, list(self.compiled.mpf_pools), S)
        return x, tuple(halos)

    def _os_walk(self, states, F, *, capture: bool = False):
        """Jitted forward from precomputed layer-0 segment spectra.

        F (S, n_seg, f, ña, ñb, ñc) — the stacked per-patch spectra the
        sweep cache assembled; layers 1.. walk the shared prepared states
        exactly like the plain batched path.  Returns ``(out, halos)``
        (empty halos unless ``capture``; see ``_walk_below_input``).
        """
        pl0 = self.compiled.layers[0]
        with jax.named_scope("os_walk"):
            with jax.named_scope("L0.overlap_save"):
                x = os_mod.os_apply_from_spectra(
                    F, states[0]["W"], states[0]["b"], pl0.os_spec,
                    use_pallas=self.use_pallas,
                )
                x = activate(self.net, 0, x)
            return self._walk_below_input(
                self.compiled.layers, states, x, F.shape[0], capture=capture
            )

    def _layer0_spectra(self, vol, starts, parents, pattern, rows_per_patch):
        """Both step programs' layer-0 prologue: ``(Fm, F)``.

        ``Fm`` holds the miss spectra sliced from ``vol`` at ``starts``
        (None without misses); ``F`` (S, rows_per_patch, f, ña, ñb, ñc)
        stacks the rows ``pattern`` names, from ``Fm`` or ``parents``.
        """
        spec0 = self.compiled.layers[0].os_spec
        with jax.named_scope("L0.spectra"):
            Fm = None
            if starts is not None:
                Fm = os_mod.slice_segment_spectra(vol, starts, spec0, self.extent)
            rows = [Fm[j] if p < 0 else parents[p][j] for p, j in pattern]
            S = len(pattern) // rows_per_patch
            F = jnp.stack(rows).reshape((S, rows_per_patch) + rows[0].shape)
        return Fm, F

    def _os_step(self, states, vol, starts, parents, *, pattern):
        """ONE jitted call per full-path patch batch: miss FFTs + assembly
        + walk (+ halo capture).

        ``pattern`` is the batch's static miss/hit layout — slot i of the
        (S·n_seg)-row spectra stack is ``(-1, j)`` (row j of the miss FFTs
        computed here from ``starts``) or ``(p, j)`` (row j of
        ``parents[p]``, a previous batch's miss-FFT output held by the
        sweep cache).  Fusing the miss transforms into the walk's jit lets
        XLA schedule them with the MAD instead of paying a host round-trip
        per batch, and selecting cached rows at trace time means reuse
        costs no host copies; the miss spectra are returned so the sweep
        cache can serve them to the next x-row, the halos so the deep
        activation cache can serve the next x-patch's strip walk.
        """
        n_seg = self.compiled.layers[0].os_spec.n_segments
        with jax.named_scope("os_step"):
            Fm, F_all = self._layer0_spectra(vol, starts, parents, pattern, n_seg)
            out, halos = self._os_walk(states, F_all, capture=self.deep_reuse)
        return out, Fm, halos

    def _os_strip_step(
        self, states, strip_states, vol, starts, parents, halos, *, pattern
    ):
        """ONE jitted call per interior-patch batch: the deep-reuse strip.

        Layer 0 pays MAD + inverse only for the ``tail_segments`` covering
        the batch's new core columns (``pattern`` holds q slots per patch,
        mixing cached and miss spectra exactly like the full step); every
        deeper layer runs on ``new_x + size - 1`` assembled columns —
        ``halos[i-1]`` (the left neighbour's cached activation halo)
        concatenated with the newly computed strip from below.  The FOV-1
        overlap is recomputed at no layer.  Returns the patch cores, the
        miss spectra, and the batch's own trailing halos for the cache.
        """
        spec0 = self.compiled.layers[0].os_spec
        with jax.named_scope("os_strip_step"):
            Fm, F = self._layer0_spectra(vol, starts, parents, pattern, self._q_strip)
            with jax.named_scope("L0.overlap_save"):
                x = os_mod.os_apply_tail_from_spectra(
                    F, states[0]["W"], states[0]["b"], spec0, self.core,
                    use_pallas=self.use_pallas,
                )
                x = activate(self.net, 0, x)
            x, new_halos = self._walk_below_input(
                self._strip_layers, strip_states, x, F.shape[0],
                capture=True, leads=halos,
            )
        return x, Fm, new_halos

    def _run_os_batch(self, meta) -> np.ndarray:
        """Patch batch with layer-0 segment spectra served from the cache.

        ``meta[i] = (sweep_token, segment_keys, patch_start)`` for patch
        i; keys come from ``tiler.segment_keys`` and pair 1:1 (same order)
        with the prepared layer-0 ``os_spec.starts``.  The segment grid is
        volume-global (segments read the padded volume directly, past the
        patch's own extent if needed), so an interior patch transforms only
        the ``core/seg_core`` segments the sweep newly entered — everything
        else is a hit.  Single-sweep batches (the volume sweep, and serving
        ticks that drained one request) run fused: the chunk partitions
        into the full-extent group and (under deep reuse) the
        interior-strip group — eligibility decided against the halo cache
        as of the chunk start, so a patch whose left neighbour is in the
        SAME chunk safely falls back to the full path — and each group is
        one jit call.  Mixed-sweep batches (cross-request serving ticks)
        fall back to one ``segment_spectra_at`` call per sweep plus the
        spectra-stack walk, with no deep reuse.
        """
        tokens = {mm[0] for mm in meta}
        if len(tokens) > 1:
            return self._run_os_batch_mixed(meta)
        token = next(iter(tokens))
        with obs.span("executor.resolve"):
            groups, halo_cache = self._partition(token, meta)
        # file each group's spectra and halos while the device computes, and
        # block on it once, after the last dispatch: a later group still finds
        # all an earlier one files before it resolves, so only the wait moves
        pending = []
        for rows, strip in groups:
            metas = [meta[i] for i in rows]
            out, halos = self._run_os_group(token, metas, strip)
            if self.deep_reuse:
                self._store_halos(halo_cache, metas, halos)
            pending.append((rows, out))
        outs: List[Optional[np.ndarray]] = [None] * len(meta)
        with obs.span("executor.fetch"):
            fetched = jax.device_get([out for _, out in pending])
        for (rows, _), ys in zip(pending, fetched):
            for j, idx in enumerate(rows):
                outs[idx] = ys[j]
        return np.stack(outs)

    def _partition(self, token, meta):
        """Evict what the chunk has passed; split it into groups.

        Returns ``([(rows, strip)], halo_cache)``: the full-path group and
        (under deep reuse) the strip-path group, each per x-plane when
        streaming.
        """
        self._sweeps.setdefault(token, {})
        halo_cache = self._halo_caches.setdefault(token, {})
        # the patch stream is x-major with non-decreasing x (tiler
        # invariant): cache entries strictly left of this chunk's earliest
        # patch start can never be requested again.  (Keyed by patch START
        # — not first resolved key — so a strip patch, which resolves only
        # its trailing keys, never evicts a key a same-plane full patch
        # still needs.)  Streaming sweeps also release staged slabs the
        # chunk has moved past.
        x_lo = min(mm[2][0] for mm in meta)
        self._evict_left_of(token, x_lo)
        # partition BEFORE running anything: strip eligibility is decided
        # against the halo cache as of the chunk start
        full_rows: List[int] = []
        strip_rows: List[int] = []
        for idx, (_, keys, start) in enumerate(meta):
            eligible = (
                self.deep_reuse
                and start[0] > 0
                and start[0] % self.core == 0
                and start in halo_cache
            )
            (strip_rows if eligible else full_rows).append(idx)
        groups: List[Tuple[List[int], bool]] = []
        for rows, strip in ((full_rows, False), (strip_rows, True)):
            if not rows:
                continue
            if self.streaming:
                # one staged slab serves one x-plane: sub-partition the
                # group so every jit call reads a single slab (serving
                # ticks can pop patches spanning planes; offline chunks
                # are already plane-capped)
                by_plane: Dict[int, List[int]] = {}
                for i in rows:
                    by_plane.setdefault(meta[i][2][0], []).append(i)
                groups.extend((by_plane[x], strip) for x in sorted(by_plane))
            else:
                groups.append((rows, strip))
        return groups, halo_cache

    def _resolve_segments(self, cache, keys, misses) -> List:
        """Map a patch's segment keys to sweep-cache entries.

        A key not in ``cache`` becomes a ``_PendingMiss`` for the next row
        of ``misses`` (which the key is appended to); the marker, filed in
        the cache, also dedups a key repeated later in the same batch
        (bucketed tail repeats).  Counts hits, misses and the MAD+inverse
        passes the keys cost.  Returns one entry per key.
        """
        entries = []
        for key in keys:
            F = cache.get(key)
            if F is None:
                F = cache[key] = _PendingMiss(len(misses))
                misses.append(key)
                self._os_misses += 1
            else:
                self._os_hits += 1
            entries.append(F)
        self._os_mad_segments += len(keys)
        return entries

    def _run_os_group(self, token, metas, strip: bool):
        """Resolve + run one homogeneous (full or strip) patch group.

        Resolution inserts ``_PendingMiss`` markers, so repeated keys
        within the group dedup; groups run sequentially (full before
        strip), so the strip group sees the full group's fresh
        ``_SpectrumRef``s.  Returns ``(out, halos)``, both still on the
        device: the caller fetches ``out`` once every group is dispatched.
        """
        with obs.span("executor.resolve"):
            n_seg = self.compiled.layers[0].os_spec.n_segments
            cache = self._sweeps[token]
            # the sweep scope's axis picks the state pytrees (working-frame
            # weights + kernel spectra); the jitted step programs are shared
            states, strip_states = self._states_for_axis(
                self._sweep_axes.get(token, self.sweep_axis)
            )
            q = self._q_strip if strip else n_seg
            misses: List[Tuple[int, int, int]] = []
            pattern: List[Tuple[int, int]] = []
            parents: List = []
            parent_pos: Dict[int, int] = {}
            for _, keys, _start in metas:
                for F in self._resolve_segments(cache, keys[n_seg - q :], misses):
                    if isinstance(F, _PendingMiss):
                        pattern.append((-1, F.idx))
                    else:
                        pos = parent_pos.get(id(F.parent))
                        if pos is None:
                            pos = parent_pos[id(F.parent)] = len(parents)
                            parents.append(F.parent)
                        pattern.append((pos, F.idx))
            if self.streaming:
                # the group is one x-plane (plane-capped chunks / per-plane
                # sub-groups): its segments all live in the staged slab
                # [x0, x0 + span), so miss starts shift into slab coordinates
                # and the fused step's volume operand keeps one constant shape
                x0 = metas[0][2][0]
                vol = self._slab(token, x0)
                off = np.asarray([x0, 0, 0], np.int32)
            else:
                vol = self._sweep_vols[token]
                off = np.zeros(3, np.int32)
            starts = (
                jnp.asarray(np.asarray(misses, np.int32) - off) if misses else None
            )
            if strip:
                halos_in = tuple(
                    jnp.concatenate(
                        [self._halo_caches[token][m[2]][pos] for m in metas], axis=0
                    )
                    for pos in range(len(self.net.layers) - 1)
                )
                step = self._jit_os_strip_step
                step_args = (states, strip_states, vol, starts, tuple(parents), halos_in)
            else:
                step = self._jit_os_step
                step_args = (states, vol, starts, tuple(parents))
        self._record_trace(
            ("strip" if strip else "full", tuple(pattern),
             None if starts is None else len(misses), vol.shape, len(parents))
        )
        with obs.span("executor.dispatch"):
            out, F_m, halos = step(*step_args, pattern=tuple(pattern))
        if strip:
            self._deep_strips += len(metas)
            self._conv_flops += len(metas) * self._strip_conv_flops
        else:
            self._deep_fulls += len(metas)
            self._conv_flops += len(metas) * self._full_conv_flops
        # the ledger's transient sample: group output + miss spectra +
        # captured halos in flight on top of the resident working set
        self._ledger.transient(
            out.nbytes
            + (F_m.nbytes if F_m is not None else 0)
            + sum(h.nbytes for h in halos)
        )
        self._store_spectra(token, cache, misses, F_m)
        return out, halos

    def _store_spectra(self, token, cache, misses, F_m) -> None:
        """File a group's miss spectra, split by absolute segment x.

        All rows of one stored parent share one x, so the per-key
        eviction sweep frees whole buffers exactly when their plane falls
        behind the patch stream — the property both the ledger and the
        planner's byte simulation rely on.  (The split costs one gather
        per distinct x; interior planes miss at a single x, so it is
        usually free.)
        """
        if not misses:
            return
        with obs.span("executor.store"):
            by_x: Dict[int, List[int]] = {}
            for i, key in enumerate(misses):
                by_x.setdefault(key[0], []).append(i)
            for _x, idxs in by_x.items():
                if len(idxs) == len(misses):
                    parent = F_m
                else:
                    parent = jnp.take(F_m, jnp.asarray(np.asarray(idxs, np.int32)), axis=0)
                self._ledger.alloc(parent.nbytes)
                share = parent.nbytes / len(idxs)
                for j, i in enumerate(idxs):
                    cache[misses[i]] = _SpectrumRef(parent, j)
                    self._key_bytes[(token, misses[i])] = share

    def _store_halos(self, halo_cache, metas, halos) -> None:
        """File a group's trailing activation halos for the x-successors.

        ``halos[pos]`` stacks the whole group (fragment-expanded batch);
        patch j owns rows [j·frag, (j+1)·frag) at each layer.  Only
        core-aligned patches store — a shifted edge patch's coverage can
        never serve an aligned successor's coordinate frame.
        """
        with obs.span("executor.store"):
            for j, (_, _, start) in enumerate(metas):
                if start[0] % self.core:
                    continue
                entry = []
                for pos in range(len(self.net.layers) - 1):
                    _, frag = self._strip_info[pos + 1]
                    entry.append(halos[pos][j * frag : (j + 1) * frag])
                key = (start[0] + self.core, start[1], start[2])
                old = halo_cache.get(key)
                if old is not None:
                    self._ledger.free(sum(h.nbytes for h in old))
                halo_cache[key] = entry
                self._ledger.alloc(sum(h.nbytes for h in entry))

    def _run_os_batch_mixed(self, meta) -> np.ndarray:
        """Cross-request serving batches: one batched FFT per sweep, then
        the spectra-stack walk (full path; deep reuse resumes on the next
        single-sweep tick — mixed ticks don't store halos)."""
        with obs.span("executor.resolve"):
            spec0 = self.compiled.layers[0].os_spec
            slots: List[List] = []  # per patch: (key, _SpectrumRef | _PendingMiss)
            miss_keys: Dict[int, List[Tuple[int, int, int]]] = {}
            for token, keys, start in meta:
                cache = self._sweeps.setdefault(token, {})
                self._evict_left_of(token, start[0])
                misses = miss_keys.get(token, [])
                slots.append(list(zip(keys, self._resolve_segments(cache, keys, misses))))
                if misses:
                    miss_keys[token] = misses
                self._deep_fulls += 1
            for token, keys_m in miss_keys.items():
                # pad the miss count to a power of two so the distinct compiled
                # FFT batch sizes stay O(log(S·n_seg))
                M = len(keys_m)
                Mp = 1
                while Mp < M:
                    Mp *= 2
                starts = np.asarray(keys_m + [keys_m[-1]] * (Mp - M), np.int32)
                if self.streaming:
                    # stage a transient slab covering this token's misses; the
                    # shape varies per tick (fallback path — the single-sweep
                    # fused path is the one with the constant-shape guarantee)
                    host = self._sweep_hosts[token]
                    x_min = min(k[0] for k in keys_m)
                    x_hi = max(k[0] for k in keys_m) + spec0.seg_extent
                    slab = jnp.asarray(host[:, x_min:x_hi])
                    self._ledger.transient(slab.nbytes)
                    starts = starts - np.asarray([x_min, 0, 0], np.int32)
                    vol = slab
                else:
                    vol = self._sweep_vols[token]
                F_all_miss = os_mod.segment_spectra_at(
                    vol, jnp.asarray(starts), spec0, self.extent
                )
                self._ledger.transient(F_all_miss.nbytes)
                # store split by absolute segment x (same invariant as the
                # single-sweep path): per-key eviction then frees real device
                # buffers instead of leaving a multi-plane parent pinned by
                # its youngest rows — the ledger stays honest in exactly the
                # cross-request mode the shared device budget governs.  The
                # power-of-two padding rows are dropped before storage.
                self._store_spectra(
                    token, self._sweeps[token], keys_m, F_all_miss[:M]
                )
            # pass 2: materialize rows and walk.  Requests sweeping different
            # axes need different state pytrees (working-frame weights), so the
            # tick sub-batches per axis — one stacked walk per axis group,
            # outputs reassembled in meta order.  Single-axis ticks (the common
            # case) keep the one-stack walk.
            by_axis: Dict[int, List[int]] = {}
            for i, (token, _, _) in enumerate(meta):
                axis = self._sweep_axes.get(token, self.sweep_axis)
                by_axis.setdefault(axis, []).append(i)
        outs: List[Optional[np.ndarray]] = [None] * len(slots)
        for axis in sorted(by_axis):
            rows = by_axis[axis]
            with obs.span("executor.resolve"):
                flat = []
                for i in rows:
                    cache = self._sweeps[meta[i][0]]
                    for key, F in slots[i]:
                        if isinstance(F, _PendingMiss):
                            F = cache[key]  # _store_spectra filed the real ref
                        flat.append(F.parent[F.idx])
                F_all = jnp.stack(flat).reshape(
                    (len(rows), spec0.n_segments) + flat[0].shape
                )  # (S_axis, n_seg, f, ña, ñb, ñc)
            self._record_trace(("oswalk", F_all.shape))
            states, _ = self._states_for_axis(axis)
            with obs.span("executor.dispatch"):
                out, _ = self._jit_os_walk(states, F_all)
            self._conv_flops += len(rows) * self._full_conv_flops
            self._ledger.transient(F_all.nbytes + out.nbytes)
            with obs.span("executor.fetch"):
                out = np.asarray(out)
            for j, i in enumerate(rows):
                outs[i] = out[j]
        return np.stack(outs)

    # -- compiled patch-batch kernels ---------------------------------------

    def padded_batch_size(self, n: int) -> int:
        """Batch size to run for ``n`` ready patches without compile churn.

        ``n`` itself when it is full or already compiled; otherwise the next
        power of two (capped at ``batch``), bounding the distinct compiled
        sizes a continuous-serving caller can trigger to O(log batch) while
        still avoiding most padded-and-discarded work.
        """
        if n >= self.batch or n in self._seen_batch_sizes:
            return min(n, self.batch)
        s = 1
        while s < n:
            s *= 2
        return min(s, self.batch)

    def run_patch_batch(
        self, xs: Optional[np.ndarray], *, meta=None
    ) -> np.ndarray:
        """(S, f, extent³) patches -> (S, out_ch, core³) dense cores.

        The per-layer states (weights, cached kernel spectra) are jit
        *arguments*, so every batch-size specialization shares the same
        prepared buffers — kernel FFTs ran once, in ``compile_plan``.

        ``meta`` (overlap-save reuse only): per-patch ``(sweep_token,
        segment_keys, patch_start)`` naming each patch's layer-0 segments
        by absolute volume coordinates, so input spectra shared with an
        x-adjacent patch are served from the sweep cache instead of
        recomputed (and, under deep reuse, interior patches assemble
        deeper-layer inputs from cached activation halos); ``xs`` may then
        be None (the walk starts from spectra of the sweep's
        device-resident volume, never from the raw patch).  Callers without
        sweep context (tests, raw batches) omit ``meta`` and get the
        self-contained walk.
        """
        if self._os_reuse and meta is not None:
            self._seen_batch_sizes.add(len(meta))
            return self._run_os_batch(meta)
        S = xs.shape[0]
        self._seen_batch_sizes.add(S)
        states = self.compiled.states
        self._conv_flops += S * self._full_conv_flops
        if self.uses_mpf:
            self._record_trace(("walk", xs.shape))
            with obs.span("executor.dispatch"):
                y = self._jit_walk(states, jnp.asarray(xs))
            self._ledger.transient(xs.nbytes + y.nbytes)
            with obs.span("executor.fetch"):
                return np.asarray(y)
        # baseline: all-subsamplings outer loop (P³ shifted passes)
        out = np.empty(
            (S, self.out_channels) + (self.core,) * 3, np.float32
        )
        n = self.n_in
        for ox, oy, oz in itertools.product(range(self.P), repeat=3):
            sub = xs[:, :, ox : ox + n, oy : oy + n, oz : oz + n]
            with obs.span("executor.dispatch"):
                yd = self._jit_walk(states, jnp.asarray(sub))
            self._ledger.transient(sub.nbytes + yd.nbytes)
            with obs.span("executor.fetch"):
                y = np.asarray(yd)
            out[:, :, ox :: self.P, oy :: self.P, oz :: self.P] = y
        return out

    # -- volume sweep --------------------------------------------------------

    def run(
        self, vol: np.ndarray, *, sweep_axis: Optional[int] = None
    ) -> np.ndarray:
        """Sweep (f, X, Y, Z) -> dense (out_ch, X-FOV+1, Y-FOV+1, Z-FOV+1).

        Output is always in the VOLUME frame, whatever the sweep axis.
        ``sweep_axis`` overrides the executor's default for this run
        (overlap-save reuse plans only — the split-strategy and non-reuse
        paths run on the default axis's compiled states).
        """
        vol = np.asarray(vol, np.float32)
        axis = self.sweep_axis if sweep_axis is None else int(sweep_axis)
        if axis != self.sweep_axis and not (self._os_reuse and self.theta < 0):
            raise ValueError(
                "per-run sweep_axis override needs an overlap-save reuse plan"
            )
        tiling = self.tiling_for(vol.shape[1:], sweep_axis=axis)
        padded = pad_volume(vol, tiling)  # working frame (sweep axis first)
        out = np.empty(
            (self.out_channels,) + tiling.to_volume_frame(tiling.out_shape),
            np.float32,
        )

        self._os_misses = self._os_hits = self._os_mad_segments = 0
        self._deep_strips = self._deep_fulls = self._conv_flops = 0
        self._ledger.begin_run()  # peak scoped to this sweep
        t0 = time.perf_counter()
        # the sweep's device upload is real per-volume work the other
        # execution modes pay per batch (patch extraction + transfer), so
        # it belongs inside the timed region for fair measured vox/s
        sweep = (
            self.begin_sweep(padded, sweep_axis=axis)
            if self._os_reuse and self.theta < 0 else None
        )
        try:
            if self.theta >= 0:
                run_split = self._run_hetero if self.hetero else self._run_pipeline
                n_batches, padded_patches = run_split(padded, tiling, out)
            else:
                n_batches, padded_patches = self._run_batched(
                    padded, tiling, out, sweep
                )
        finally:
            self.end_sweep(sweep)
        dt = time.perf_counter() - t0

        vox = float(np.prod(out.shape[1:]))
        self.last_stats = {
            "patches": tiling.n_patches,
            "batches": n_batches,
            # compute-then-discarded padding slots (pipeline stream padding;
            # the batched path routes ragged tails through a smaller compiled
            # batch instead of padding, so it reports 0)
            "padded_patches": padded_patches,
            "seconds": dt,
            "out_voxels": vox,
            "measured_voxps": vox / dt if dt > 0 else float("inf"),
            "predicted_voxps": self.plan.throughput if self.plan else float("nan"),
            "waste_fraction": tiling.waste_fraction,
            **self.counter_stats(),
            # fused-epilogue accounting: conv+pool pairs the halo-emitting
            # fused epilogue served (``fuse_os``: every patch walk runs each
            # eligible pair), and segments run through the fused Pallas
            # os_segment kernel (one pallas_call: MAD, DC-bin bias, inverse,
            # crop), which on the Pallas path runs every MAD+inverse pass
            "fused_pair_calls": (
                (self._deep_strips + self._deep_fulls) * len(self._fused_pairs)
                if self.fuse_os else 0
            ),
            "os_fused_segments": self._os_mad_segments if self.use_pallas else 0,
            # distinct jit specializations dispatched so far (cumulative
            # over the executor's lifetime — serving watches this to see
            # shape-bucketing suppress per-request retraces)
            "retraces": len(self._trace_keys),
            # peak executor-managed device bytes this sweep (states + slabs
            # + caches + in-flight chunk tensors; the _DeviceLedger's
            # accounting, reproduced by predict_memory / Plan.memory)
            "peak_device_bytes": self._ledger.peak,
            "predicted_peak_device_bytes": (
                self.predict_memory(vol.shape[1:], sweep_axis=axis).device_bytes
                if self._os_reuse and self.theta < 0
                else float("nan")
            ),
        }
        if self.hetero:
            # per-stage / hand-off counters of the two-backend pipeline,
            # next to their plan predictions (bytes match EXACTLY: the
            # per-patch hand-off size is chunk-size independent)
            self.last_stats.update(self._hetero_stats)
        return out

    def counter_stats(self) -> Dict[str, int]:
        """The exact reuse counters under their ``last_stats`` names.

        Counted since construction or the last offline ``run`` (which
        restarts them), so a serving engine's ticks publish lifetime
        totals and ``run`` one sweep's.
        """
        return {
            # overlap-save input-spectra reuse (0/0 when not active):
            # segment FFTs actually run vs. segments served from the cache
            "os_seg_fft": self._os_misses,
            "os_seg_hits": self._os_hits,
            # sweep-aware accounting (matches predict_counts exactly):
            # per-segment MAD+inverse passes run, and how many patches
            # took the deep-reuse strip path vs. the full-extent path
            "os_mad_segments": self._os_mad_segments,
            "deep_strip_patches": self._deep_strips,
            "deep_full_patches": self._deep_fulls,
            # convolution FLOPs of the steps dispatched, full and strip:
            # Σ 2·k³·f·f' × conv output voxels each computed
            "conv_flops": self._conv_flops,
        }

    # -- memory model --------------------------------------------------------

    def predict_memory(
        self, vol_shape: Sequence[int], *, sweep_axis: Optional[int] = None
    ):
        """Predicted peak device working set for sweeping ``vol_shape``.

        The planner-side simulation (``planner.plan_stream_memory``) run
        for THIS executor's mode (streaming or dense) and the given sweep
        axis (default the executor's): the returned
        ``MemoryFootprint.device_bytes`` equals what ``run`` will record
        in ``last_stats["peak_device_bytes"]`` up to the analytic-vs-
        measured state rounding (pinned within 10% by the test suite).
        Memoized per (shape, axis) — the simulation is deterministic, and
        ``run`` consults it every sweep for the predicted-peak stat.
        """
        if not self._os_reuse:
            raise ValueError("predict_memory needs an overlap-save reuse plan")
        axis = self.sweep_axis if sweep_axis is None else int(sweep_axis)
        key = tuple(int(x) for x in vol_shape) + (axis,)
        hit = self._predict_memory_cache.get(key)
        if hit is not None:
            return hit
        from ..core.planner import plan_stream_memory

        mem = plan_stream_memory(
            self.net, self.prims, self.m, key[:3],
            batch=self.batch, deep_reuse=self.deep_reuse,
            streaming=self.streaming, sweep_axis=axis,
        )
        self._predict_memory_cache[key] = mem
        return mem

    def sweep_bytes_estimate(
        self, vol_shape: Sequence[int], *, sweep_axis: Optional[int] = None
    ) -> float:
        """Device bytes OPENING a sweep over ``vol_shape`` would add.

        The serving engine's admission estimate: predicted peak minus the
        always-resident prepared states (already counted in the ledger).
        """
        mem = self.predict_memory(vol_shape, sweep_axis=sweep_axis)
        return mem.device_bytes - mem.spectra_bytes

    def write_core(self, out, tiling, spec, y) -> None:
        """Crop a patch's dense core (out_ch, core³) into the output.

        ``spec``/``y`` are in the tiling's working frame; ``out`` is the
        VOLUME-frame dense output (possibly the true un-bucketed crop).
        Each working axis clips against the matching volume axis's extent
        and, for non-identity frames, the cropped core transposes back —
        the only place sweep output re-enters volume coordinates.
        """
        c = tiling.core
        perm, inv = tiling.perm, tiling.inv_perm
        sls = []
        for i in range(3):
            s = spec.start[i]
            sls.append(slice(s, min(s + c, out.shape[1 + perm[i]])))
        y = y[:, : sls[0].stop - sls[0].start,
              : sls[1].stop - sls[1].start, : sls[2].stop - sls[2].start]
        if perm == (0, 1, 2):
            out[:, sls[0], sls[1], sls[2]] = y
        else:
            out[(slice(None),) + tuple(sls[inv[a]] for a in range(3))] = (
                np.transpose(y, (0,) + tuple(1 + inv[a] for a in range(3)))
            )

    def _run_batched(self, padded, tiling, out, sweep=None):
        S = self.batch
        specs = tiling.patches
        n_batches = 0
        if sweep is not None:
            # reuse path: chunks are capped at x-plane boundaries so every
            # aligned interior patch's left neighbour completed in an
            # EARLIER chunk — the strip path survives batch sizes larger
            # than the x-plane (and, streaming, every chunk reads one slab)
            chunks = [
                [specs[i] for i in idxs] for idxs in chunk_patches(tiling, S)
            ]
        else:
            chunks = [list(specs[i : i + S]) for i in range(0, len(specs), S)]
        for ci, chunk in enumerate(chunks):
            # a ragged tail runs through a smaller compiled batch (one extra
            # compile, cached per size) instead of computing-and-discarding
            # repeated padding patches.
            if sweep is not None:
                if self.streaming:
                    # double-buffered staging: release planes the stream
                    # moved past, keep/stage the current plane, and kick
                    # off the NEXT plane's host→device copy so it overlaps
                    # the current chunk's fused step (async dispatch)
                    x_cur = chunk[0].start[0]
                    keep = {x_cur}
                    if ci + 1 < len(chunks):
                        keep.add(chunks[ci + 1][0].start[0])
                    self._drop_slabs(sweep, keep)
                    for x0 in sorted(keep):
                        self._slab(sweep, x0)
                # overlap-save: the walk starts from cached/computed segment
                # spectra of the sweep's resident volume (or staged slab) —
                # no host-side patch extraction
                meta = [
                    (sweep, tiling.segment_keys(s), s.start) for s in chunk
                ]
                ys = self.run_patch_batch(None, meta=meta)
            else:
                xs = np.stack(
                    [extract_patch(padded, s, tiling.extent) for s in chunk]
                )
                ys = self.run_patch_batch(xs)
            for spec, y in zip(chunk, ys):
                self.write_core(out, tiling, spec, y)
            n_batches += 1
        return n_batches, 0

    def _run_pipeline(self, padded, tiling, out):
        """pipeline2: stream patch chunks through the two-stage scan."""
        from jax.sharding import Mesh, PartitionSpec as P

        S = self.batch
        specs = list(tiling.patches)
        n_chunks = math.ceil(len(specs) / S)
        devices = np.array(jax.devices())
        n_pods = len(devices)
        # equal local stream length per pod: pad the chunk count
        T = math.ceil(n_chunks / n_pods) * n_pods
        xs_all = np.empty(
            (T, S, padded.shape[0]) + (tiling.extent,) * 3, np.float32
        )
        chunk_specs: List[List] = []
        for t in range(T):
            chunk = specs[t * S : (t + 1) * S] or [specs[-1]]
            chunk_specs.append(chunk)
            for j in range(S):
                spec = chunk[min(j, len(chunk) - 1)]
                xs_all[t, j] = extract_patch(padded, spec, tiling.extent)

        if self._pipeline_fn is None:
            mesh = Mesh(devices, ("pod",))

            def local(states, xs):  # xs (T_local, S, f, n³) — this pod's stream
                # prepared states arrive as (replicated) jit arguments, not
                # trace constants, matching the batched path's convention
                stage0, stage1 = make_stage_fns(
                    self.compiled, self.theta, states=states
                )
                return pipelined_apply(stage0, stage1, xs, axis_name="pod")

            # check_vma=False: the stage walks may run Pallas kernels, whose
            # output shapes carry no varying-mesh-axes annotation
            self._pipeline_fn = jax.jit(
                jax.shard_map(
                    local, mesh=mesh,
                    in_specs=(P(), P("pod")), out_specs=P("pod"),
                    check_vma=False,
                )
            )

        # the pipeline schedule stages the whole patch stream at once; the
        # ledger records it so peak_device_bytes stays honest there too
        self._ledger.transient(xs_all.nbytes)
        ys = np.asarray(
            self._pipeline_fn(self.compiled.states, jnp.asarray(xs_all))
        )
        self._conv_flops += T * S * self._full_conv_flops
        # ring hand-off: pod p's local outputs are pod p-1's patches; roll
        # the pod-major chunk axis by one local-stream length to realign.
        if n_pods > 1:
            ys = np.roll(
                ys.reshape((n_pods, T // n_pods) + ys.shape[1:]), -1, axis=0
            ).reshape((T,) + ys.shape[1:])
        pools = list(self.compiled.mpf_pools)
        for t, chunk in enumerate(chunk_specs):
            y = ys[t]
            if pools:
                y = np.asarray(recombine_fragments(jnp.asarray(y), pools, S))
            for j, spec in enumerate(chunk[:S]):
                self.write_core(out, tiling, spec, y[j])
        return T, T * S - tiling.n_patches

    def _run_hetero(self, padded, tiling, out):
        """hetero: two-backend pipeline, host RAM as the hand-off medium.

        Stage 0 (layers [0, θ)) runs on ``jax.devices("cpu")[0]``, stage 1
        (layers [θ, L) + MPF recombination) on the default accelerator —
        the plan's ``devices[0]``/``devices[1]`` profiles respectively.
        Between them the split-point activation is materialized as a host
        ndarray (the paper's §VII-C "host RAM is the shared medium"), so
        the hand-off is an explicit, measured device→host→device round
        trip, not a backend-internal transfer.  Chunks run the two stages
        back to back with per-stage timing; a single-accelerator container
        cannot physically overlap them, so measured wall time is t0+t1+
        xfer per chunk while the plan's steady-state model is
        max(t0,t1)+xfer — the per-stage/hand-off counters in
        ``last_stats`` are what pin the prediction, and the hand-off
        *bytes* match ``Plan.xfer_bytes`` exactly (per-patch size is
        chunk-size independent).
        """
        S = self.batch
        specs = list(tiling.patches)
        dev0, dev1 = hetero_stage_devices()
        pools = list(self.compiled.mpf_pools)
        frag = 1
        for p in pools:
            frag *= p**3

        if self._hetero_fns is None:
            theta = self.theta

            def stage0_fn(states, xs):
                return self.compiled.apply_range(xs, 0, theta, states=states)

            def stage1_fn(states, a):
                y = self.compiled.apply_range(a, theta, None, states=states)
                if pools:
                    y = recombine_fragments(y, pools, y.shape[0] // frag)
                return y

            # per-device copies of the prepared states; committed inputs
            # pin each jitted stage to its backend
            self._hetero_fns = (
                jax.jit(stage0_fn),
                jax.jit(stage1_fn),
                jax.device_put(self.compiled.states, dev0),
                jax.device_put(self.compiled.states, dev1),
            )
            self._ledger.alloc(_tree_nbytes(self._hetero_fns[3]))
        jit0, jit1, states0, states1 = self._hetero_fns

        stage0_s = stage1_s = xfer_s = 0.0
        xfer_bytes = 0.0
        n_chunks = 0
        for i in range(0, len(specs), S):
            chunk = specs[i : i + S]  # ragged tail runs at true size
            xs = np.stack(
                [extract_patch(padded, s, tiling.extent) for s in chunk]
            )
            self._record_trace(("hetero", xs.shape))
            t = time.perf_counter()
            a = jit0(states0, jax.device_put(xs, dev0))
            a.block_until_ready()
            t2 = time.perf_counter()
            stage0_s += t2 - t
            # the hand-off: device 0 → host RAM → device 1
            a_host = np.asarray(a)
            a1 = jax.device_put(a_host, dev1)
            a1.block_until_ready()
            t3 = time.perf_counter()
            xfer_s += t3 - t2
            xfer_bytes += float(a_host.nbytes)
            y = jit1(states1, a1)
            self._conv_flops += len(chunk) * self._full_conv_flops
            y.block_until_ready()
            stage1_s += time.perf_counter() - t3
            self._ledger.transient(xs.nbytes + a.nbytes + y.nbytes)
            for spec, yy in zip(chunk, np.asarray(y)):
                self.write_core(out, tiling, spec, yy)
            n_chunks += 1

        plan = self.plan
        scale = tiling.n_patches / plan.batch  # plan counters are per batch
        self._hetero_stats = {
            "stage0_seconds": stage0_s,
            "stage1_seconds": stage1_s,
            "xfer_seconds": xfer_s,
            "xfer_bytes": xfer_bytes,
            "predicted_stage0_seconds": plan.stage_times[0] * scale,
            "predicted_stage1_seconds": plan.stage_times[1] * scale,
            "predicted_xfer_seconds": plan.xfer_seconds * scale,
            "predicted_xfer_bytes": plan.xfer_bytes * scale,
        }
        return n_chunks, 0


def tiled_apply(
    params,
    net: ConvNetConfig,
    vol: np.ndarray,
    prims: Sequence[str],
    m: int,
    *,
    batch: int = 1,
    use_pallas: bool = False,
) -> np.ndarray:
    """One-shot tiled inference without a Plan (tests, notebooks)."""
    ex = PlanExecutor(
        params, net, prims=prims, m=m, batch=batch, use_pallas=use_pallas
    )
    return ex.run(vol)
