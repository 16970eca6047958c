"""Sweep-aware planning: predicted reuse counters == executor counters (ISSUE 4).

The acceptance property of threading ``PlanGeometry`` through the
cost/primitive/planner stack: for the deployed mix (overlap_save at layer
0, fft_cached deeper, MPF pools), the planner-side cache simulation
(``tiler.predict_sweep_counts``, surfaced as ``PlanExecutor.predict_counts``
and ``Plan.sweep``) must match the executor's measured ``last_stats``
EXACTLY — segment FFTs, cache hits, MAD segments, and strip/full patch
counts — across interior-rich, shifted-edge, ragged, and degenerate
single-patch tilings, at multiple batch sizes.  Alongside exactness, the
deep-reuse strip path must (a) equal the dense oracle, and (b) strictly
reduce per-interior-patch MAD work versus the PR-3 full path.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs.base import ConvLayerSpec as L, ConvNetConfig
from repro.core import convnet, cost_model, planner
from repro.core.hw import TPU_V5E
from repro.volume import PlanExecutor
from repro.volume.tiler import extract_patch, pad_volume, predict_sweep_counts

NET = ConvNetConfig(
    "sweep-toy", 1,
    (L("conv", 3, 4), L("pool", 2), L("conv", 3, 4), L("pool", 2), L("conv", 3, 2)),
)
# the deployed mix: overlap_save where the sweep cache has a cross-patch
# identity to exploit (layer 0), fft_cached deeper
MIX = [
    "overlap_save" if i == 0 else ("fft_cached" if l.kind == "conv" else "mpf")
    for i, l in enumerate(NET.layers)
]
FOV = NET.field_of_view()
CORE = NET.total_pooling()  # m = 1


def _dense(params, vol):
    return np.asarray(
        convnet.apply_dense_reference(params, NET, jnp.asarray(vol)[None])[0]
    )


@pytest.fixture(scope="module")
def params():
    return convnet.init_params(jax.random.PRNGKey(0), NET)


# interior-rich, shifted x edge, ragged y, and the degenerate single patch
SHAPES = {
    "interior": (4 * CORE + FOV - 1, 2 * CORE + FOV - 1, CORE + FOV - 1),
    "shifted_x": (3 * CORE + 1 + FOV - 1, 2 * CORE + FOV - 1, CORE + FOV - 1),
    "ragged_yz": (3 * CORE + 2 + FOV - 1, CORE + 3 + FOV - 1, CORE + 1 + FOV - 1),
    "single_patch": (CORE + FOV - 1, CORE + FOV - 1, CORE + FOV - 1),
}


@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
@pytest.mark.parametrize("batch", [1, 3])
def test_predicted_counters_match_executor_exactly(params, rng, shape, batch):
    vol = rng.normal(size=(1,) + shape).astype(np.float32)
    ex = PlanExecutor(params, NET, prims=MIX, m=1, batch=batch)
    got = ex.run(vol)
    np.testing.assert_allclose(got, _dense(params, vol), atol=1e-3)
    s = ex.last_stats
    pred = ex.predict_counts(shape)
    assert s["os_seg_fft"] == pred.seg_fft
    assert s["os_seg_hits"] == pred.seg_hits
    assert s["os_mad_segments"] == pred.mad_segments
    assert s["deep_strip_patches"] == pred.strip_patches
    assert s["deep_full_patches"] == pred.full_patches
    assert pred.n_patches == s["patches"]
    # a second sweep is a fresh scope: identical counts, no leak
    ex.run(vol)
    assert ex.last_stats["os_seg_fft"] == pred.seg_fft
    assert not ex._sweeps and not ex._halo_caches


def test_planner_sweep_counts_equal_executor(params, rng):
    """``plan_fixed(volume_shape=...)`` records on the Plan exactly what
    the executor measures — the planner and the runtime agree on the whole
    sweep, not just per-patch shapes."""
    shape = SHAPES["shifted_x"]
    plan = planner.plan_fixed(
        NET, TPU_V5E, MIX, m=1, batch=2, volume_shape=shape
    )
    assert plan.sweep is not None and plan.geometry is not None
    assert plan.geometry.seg_core == plan.core  # executor's pinned grid
    ex = PlanExecutor(params, NET, plan)
    vol = rng.normal(size=(1,) + shape).astype(np.float32)
    ex.run(vol)
    s = ex.last_stats
    assert s["os_seg_fft"] == plan.sweep.seg_fft
    assert s["os_seg_hits"] == plan.sweep.seg_hits
    assert s["os_mad_segments"] == plan.sweep.mad_segments
    assert s["deep_strip_patches"] == plan.sweep.strip_patches


def test_deep_reuse_reduces_interior_work(params, rng):
    """Interior patches pay strictly less: fewer MAD segments than the
    PR-3 full path, identical segment-FFT counts (layer-0 input reuse is
    unchanged), and bitwise-equal-to-oracle outputs either way."""
    shape = SHAPES["interior"]
    vol = rng.normal(size=(1,) + shape).astype(np.float32)
    want = _dense(params, vol)
    deep = PlanExecutor(params, NET, prims=MIX, m=1, batch=1)
    flat = PlanExecutor(params, NET, prims=MIX, m=1, batch=1, deep_reuse=False)
    np.testing.assert_allclose(deep.run(vol), want, atol=1e-3)
    np.testing.assert_allclose(flat.run(vol), want, atol=1e-3)
    sd, sf = deep.last_stats, flat.last_stats
    assert sd["deep_strip_patches"] > 0
    assert sd["os_mad_segments"] < sf["os_mad_segments"]
    assert sd["os_seg_fft"] == sf["os_seg_fft"]
    # per-interior-patch MAD at the jit boundary: q trailing segments
    q = deep._q_strip
    spec0 = deep.compiled.layers[0].os_spec
    assert 0 < q < spec0.n_segments
    assert (
        sd["os_mad_segments"]
        == sd["deep_strip_patches"] * q
        + sd["deep_full_patches"] * spec0.n_segments
    )


def _fetch_each_group(ex):
    """Make ``ex`` wait for each group's output before its halos are
    filed: one group at a time, the order the one fetch per batch replaced."""
    run_group = ex._run_os_group

    def fetched(*args, **kwargs):
        out, halos = run_group(*args, **kwargs)
        return np.asarray(out), halos

    ex._run_os_group = fetched
    return ex


def test_one_fetch_after_full_and_strip_groups_changes_nothing(params, rng):
    """Chunks of 3 over the interior shape, across x-planes (the engine's
    plane cap never builds one; ``run_patch_batch`` takes any chunk): the
    second holds a full group and a strip group, so its one fetch waits on
    two dispatches.  Outputs, filed halo keys and the ledger peak equal
    those of fetching each group before filing its halos; outputs also
    match the self-contained walk of the extracted patches."""
    shape = SHAPES["interior"]
    vol = rng.normal(size=(1,) + shape).astype(np.float32)
    runs = []
    for ex in (
        PlanExecutor(params, NET, prims=MIX, m=1, batch=3),
        _fetch_each_group(PlanExecutor(params, NET, prims=MIX, m=1, batch=3)),
    ):
        tiling = ex.tiling_for(shape)
        padded = pad_volume(vol, tiling)
        token = ex.begin_sweep(padded)
        specs = list(tiling.patches)
        ticks = []
        for i in range(0, len(specs), 3):
            chunk = specs[i : i + 3]
            strips, fulls = ex._deep_strips, ex._deep_fulls
            ys = ex.run_patch_batch(
                None, meta=[(token, tiling.segment_keys(s), s.start) for s in chunk]
            )
            ticks.append((
                ex._deep_strips - strips, ex._deep_fulls - fulls, ys,
                sorted(ex._halo_caches[token]), ex._ledger.peak,
            ))
        ex.end_sweep(token)
        runs.append(ticks)
        # after the sweep, so the walk's own transient leaves the peaks alone
        for i, tick in zip(range(0, len(specs), 3), ticks):
            xs = np.stack([extract_patch(padded, s, tiling.extent) for s in specs[i : i + 3]])
            np.testing.assert_allclose(tick[2], ex.run_patch_batch(xs), rtol=0, atol=1e-5)
    once, each = runs
    assert [t[:2] for t in once] == [(0, 3), (2, 1), (2, 0)]
    # halos filed at the x-successor of every core-aligned patch, as before
    assert once[1][3] == [(x, y, 0) for x in (CORE, 2 * CORE, 3 * CORE) for y in (0, CORE)]
    for a, b in zip(once, each):
        np.testing.assert_array_equal(a[2], b[2])
        assert a[3] == b[3]
        assert a[4] == b[4] > 0


def test_single_patch_volume_degenerates_to_full_path(params, rng):
    """The degenerate single-patch sweep: nothing to reuse, the strip path
    never fires, and prediction still matches exactly."""
    shape = SHAPES["single_patch"]
    vol = rng.normal(size=(1,) + shape).astype(np.float32)
    ex = PlanExecutor(params, NET, prims=MIX, m=1, batch=2)
    np.testing.assert_allclose(ex.run(vol), _dense(params, vol), atol=1e-3)
    s = ex.last_stats
    assert s["deep_strip_patches"] == 0 and s["deep_full_patches"] == 1
    assert s["os_seg_hits"] == 0
    pred = ex.predict_counts(shape)
    assert (s["os_seg_fft"], s["os_mad_segments"]) == (
        pred.seg_fft, pred.mad_segments
    )


def test_predict_counts_requires_reuse_plan(params):
    prims = ["fft_cached" if l.kind == "conv" else "mpf" for l in NET.layers]
    ex = PlanExecutor(params, NET, prims=prims, m=1, batch=1)
    with pytest.raises(ValueError):
        ex.predict_counts(SHAPES["single_patch"])


def test_predict_sweep_counts_rejects_plain_tiling():
    from repro.volume.tiler import tile_volume

    with pytest.raises(ValueError):
        predict_sweep_counts(tile_volume((40, 40, 40), core=4, fov=18))


# -- geometry-aware costing ---------------------------------------------------


def test_sweep_geometry_prices_below_local():
    """Sweep-aware costing strictly undercuts context-free costing for the
    reuse-capable mix (amortized input FFTs + strip-priced deeper layers),
    and the pricing uses the executor's core-pinned layer-0 grid."""
    shape = SHAPES["interior"]
    sweep = planner.plan_fixed(
        NET, TPU_V5E, MIX, m=1, batch=2, volume_shape=shape
    )
    local = planner.plan_fixed(NET, TPU_V5E, MIX, m=1, batch=2)
    assert sweep.total_time < local.total_time
    assert sweep.throughput > local.throughput
    # deep reuse off still amortizes input FFTs, but strictly less
    no_deep = planner.plan_fixed(
        NET, TPU_V5E, MIX, m=1, batch=2, volume_shape=shape, deep_reuse=False
    )
    assert sweep.total_time < no_deep.total_time < local.total_time
    assert no_deep.sweep.strip_patches == 0


def test_geometry_local_default_is_self_contained():
    """Context-free costing prices every segment transform (the honest
    price of the one-shot apply); a sweep geometry with exact per-patch
    averages prices less input-FFT work."""
    S, f, fp, n, k = 2, 8, 8, (21, 21, 21), 3
    local = cost_model.conv_overlap_save_cost(S, f, fp, n, k)
    geom = cost_model.PlanGeometry(
        core=4, fov=18, seg_core=4, interior_frac=0.5,
        seg_fft_per_patch=2.0, n_patches=8,
    ).at_layer(0)
    swept = cost_model.conv_overlap_save_cost(S, f, fp, n, k, geom)
    assert swept.flops < local.flops
    assert swept.hbm_bytes < local.hbm_bytes
    # geometry does not relax the memory-budget axis
    assert swept.peak_bytes == local.peak_bytes


def test_plan_single_volume_shape_search(params):
    """The searches accept the geometry: plan_single under a volume shape
    returns a plan whose recorded counters (when the winning mix is
    reuse-capable) come from the same simulation predict_counts runs."""
    shape = SHAPES["interior"]
    plan = planner.plan_single(
        NET, TPU_V5E, max_m=2, batches=(2,),
        conv_prims=("overlap_save",), strategy_name="os",
        volume_shape=shape,
    )
    assert plan is not None
    assert plan.sweep is not None
    assert plan.geometry.n_patches == plan.sweep.n_patches
    strategies = planner.plan_all_strategies(
        NET, TPU_V5E, chips=4, volume_shape=shape
    )
    assert strategies["single"] is not None


def test_cached_halos_are_lane_flat_and_sized_as_predicted(params, rng):
    """Every filed halo is kept as (frag·B, f, w·n·n): one flat minor axis,
    so a halo waiting for its x-successor pads at most one lane tile.  An
    entry holds exactly the bytes ``handoff_entry_nbytes`` predicts, and
    the strip path that reads them back still equals the dense oracle."""
    vol = rng.normal(size=(1,) + SHAPES["interior"]).astype(np.float32)
    ex = PlanExecutor(params, NET, prims=MIX, m=1, batch=1)
    filed = []
    store = ex._store_halos

    def recording(halo_cache, metas, halos):
        store(halo_cache, metas, halos)
        filed.extend(halo_cache.values())

    ex._store_halos = recording
    got = ex.run(vol)
    np.testing.assert_allclose(got, _dense(params, vol), atol=1e-3)
    assert ex.last_stats["deep_strip_patches"] > 0 and filed
    _, entry_bytes = ex.handoff_entry_nbytes()
    for entry in filed:
        assert [h.ndim for h in entry] == [3] * (len(NET.layers) - 1)
        assert sum(h.nbytes for h in entry) == entry_bytes
