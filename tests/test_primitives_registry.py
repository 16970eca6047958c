"""Primitive registry + CompiledPlan (ISSUE 2): the ``fft_cached`` path is
real — kernel spectra are computed exactly once per plan and reused across
patches, batch sizes, and sweeps — and the registry is the single place
primitive names resolve to code."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ConvLayerSpec as L, ConvNetConfig
from repro.core import convnet, cost_model, fft_conv, planner, primitives
from repro.core.hw import TPU_V5E
from repro.volume import PlanExecutor

NET = ConvNetConfig(
    "cached-toy", 1,
    (L("conv", 3, 4), L("pool", 2), L("conv", 3, 4), L("pool", 2), L("conv", 3, 2)),
)
N_CONVS = sum(1 for l in NET.layers if l.kind == "conv")


def _cached_prims(net):
    return ["fft_cached" if l.kind == "conv" else "mpf" for l in net.layers]


def _dense(params, net, vol):
    return np.asarray(
        convnet.apply_dense_reference(params, net, jnp.asarray(vol)[None])[0]
    )


def _volume(net, m, rng, extra=(3, 0, 0)):
    fov = net.field_of_view()
    core = m * net.total_pooling()
    shape = tuple(2 * core + e + fov - 1 for e in extra)
    return rng.normal(size=(1,) + shape).astype(np.float32)


# -- the cached path is correct ---------------------------------------------


def test_fft_cached_forced_plan_matches_dense(rng):
    plan = planner.plan_single(
        NET, TPU_V5E, max_m=1, batches=(2,),
        conv_prims=("fft_cached",), strategy_name="fft_cached",
    )
    assert plan is not None
    assert all(c.prim == "fft_cached" for c in plan.choices if c.kind == "conv")
    params = convnet.init_params(jax.random.PRNGKey(0), NET)
    vol = _volume(NET, plan.m_final, rng)
    ex = PlanExecutor(params, NET, plan)
    got = ex.run(vol)
    np.testing.assert_allclose(got, _dense(params, NET, vol), atol=1e-3)
    assert ex.last_stats["patches"] > 1  # the sweep actually reused spectra


def test_kernel_fft_runs_exactly_once_per_conv_layer(rng, monkeypatch):
    """The tentpole property: across a multi-patch sweep (with a ragged
    tail batch and a second full sweep), ``kernel_rfftn`` runs exactly
    N_CONVS times — all at compile_plan setup, none per patch."""
    calls = []
    real = fft_conv.kernel_rfftn

    def counted(w, fft_shape):
        calls.append(tuple(fft_shape))
        return real(w, fft_shape)

    monkeypatch.setattr(fft_conv, "kernel_rfftn", counted)
    params = convnet.init_params(jax.random.PRNGKey(1), NET)
    ex = PlanExecutor(params, NET, prims=_cached_prims(NET), m=1, batch=5)
    assert len(calls) == N_CONVS  # setup transformed each conv kernel once

    vol = _volume(NET, 1, rng)
    out1 = ex.run(vol)
    assert ex.last_stats["patches"] % ex.batch != 0  # exercises the tail path
    out2 = ex.run(vol)
    np.testing.assert_allclose(out1, out2, atol=0)
    np.testing.assert_allclose(out1, _dense(params, NET, vol), atol=1e-3)
    assert len(calls) == N_CONVS  # no per-patch / per-compile recompute


def test_compiled_plan_matches_apply_plan(rng):
    """CompiledPlan.apply == the string-prims compatibility walk."""
    params = convnet.init_params(jax.random.PRNGKey(2), NET)
    prims = ["direct", "mpf", "fft_task", "mpf", "fft_cached"]
    compiled = primitives.compile_plan(params, NET, prims=prims, m=1)
    x = jnp.asarray(
        rng.normal(size=(2, 1) + (compiled.n_in,) * 3).astype(np.float32)
    )
    want = convnet.apply_plan(params, NET, x, prims)
    got = compiled.apply(x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4)
    # states round-trip as explicit jit arguments (the executor's calling
    # convention): same result, same prepared buffers
    f = jax.jit(lambda states, xs: compiled.apply(xs, states=states))
    got2 = f(compiled.states, x)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(want), atol=1e-4)


def test_fft_shapes_are_chosen_at_setup():
    params = convnet.init_params(jax.random.PRNGKey(3), NET)
    compiled = primitives.compile_plan(
        params, NET, prims=_cached_prims(NET), m=1
    )
    n = compiled.n_in
    for pl in compiled.layers:
        if pl.kind == "conv":
            assert pl.fft_shape is not None
            assert all(s >= n for s in pl.fft_shape)  # covers the layer input
            # cached spectra exist and match the chosen FFT shape
            W = pl.state["W"]
            na, nb, nc = pl.fft_shape
            assert W.shape[-3:] == (na, nb, nc // 2 + 1)
            n = n - NET.layers[pl.index].size + 1
        else:
            assert pl.pool_size == NET.layers[pl.index].size
            n = n // pl.pool_size


# -- ragged tail ------------------------------------------------------------


def test_ragged_tail_uses_smaller_batch_not_padding(rng):
    params = convnet.init_params(jax.random.PRNGKey(4), NET)
    ex = PlanExecutor(params, NET, prims=_cached_prims(NET), m=1, batch=4)
    vol = _volume(NET, 1, rng, extra=(3, 3, 0))  # patches not divisible by 4
    got = ex.run(vol)
    s = ex.last_stats
    assert s["patches"] % 4 != 0
    assert s["padded_patches"] == 0
    assert s["batches"] == -(-s["patches"] // 4)
    # the tail ran at its own (smaller) size, full batches at the plan's
    assert ex._seen_batch_sizes == {4, int(s["patches"]) % 4}
    np.testing.assert_allclose(got, _dense(params, NET, vol), atol=1e-3)


def test_padded_batch_size_bounds_serving_compiles():
    """Continuous serving drains arbitrary ready-counts; bucketing keeps the
    distinct compiled batch sizes O(log batch)."""
    params = convnet.init_params(jax.random.PRNGKey(5), NET)
    ex = PlanExecutor(params, NET, prims=_cached_prims(NET), m=1, batch=16)
    assert ex.padded_batch_size(16) == 16
    assert ex.padded_batch_size(20) == 16  # never above the plan batch
    assert ex.padded_batch_size(5) == 8  # bucket up: 5,6,7,8 share a compile
    assert ex.padded_batch_size(1) == 1
    ex._seen_batch_sizes.add(5)  # already compiled -> run exactly
    assert ex.padded_batch_size(5) == 5


# -- bias broadcasting: one rule for every registered conv primitive --------


@pytest.mark.parametrize("name", primitives.registered_conv_names())
def test_conv_apply_bias_matches_dense_oracle_on_ragged_patch(name, rng):
    """ISSUE 3 satellite: the one-shot path and the registry apply agree on
    bias broadcasting for EVERY registered conv primitive, pinned to the
    dense oracle on a ragged patch — anisotropic spatial extent and f'=5
    channels, multiples of neither the Pallas FP_BLOCK nor the x-tile."""
    import jax.numpy as jnp
    from jax import lax

    x = jnp.asarray(rng.normal(size=(2, 3, 9, 8, 7)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(5, 3, 3, 3, 3)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(5,)).astype(np.float32))
    want = lax.conv_general_dilated(
        x, w, (1, 1, 1), "VALID",
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    ) + b.reshape(1, 5, 1, 1, 1)
    got = primitives.conv_apply(name, x, w, b)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3)
    # one-shot path == registry setup+apply (the same prepared state walk)
    prim = primitives.conv_primitive(name)
    pl = prim.setup(w, b, (9, 8, 7))
    got2 = prim.apply(pl, x, pl.state)
    np.testing.assert_allclose(np.asarray(got2), np.asarray(got), atol=0)


@pytest.mark.parametrize("name", primitives.registered_conv_names())
def test_conv_apply_bias_contract_is_uniform(name, rng):
    """Scalar bias broadcasts, wrong-length bias raises — identically for
    every primitive (the pre-fix state let each apply re-derive f' from a
    different tensor, so mismatches failed differently per primitive)."""
    import jax.numpy as jnp

    x = jnp.asarray(rng.normal(size=(1, 2, 7, 7, 7)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 2, 3, 3, 3)).astype(np.float32))
    none = np.asarray(primitives.conv_apply(name, x, w, None))
    scalar = np.asarray(primitives.conv_apply(name, x, w, jnp.float32(0.5)))
    np.testing.assert_allclose(scalar, none + 0.5, atol=1e-5)
    with pytest.raises(ValueError):
        primitives.conv_apply(name, x, w, jnp.zeros((4,), jnp.float32))


# -- one-shot registry apply (sublayer / halo paths) ------------------------


def test_conv_apply_resolves_aliases(rng):
    x = jnp.asarray(rng.normal(size=(1, 2, 6, 6, 6)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(3, 2, 3, 3, 3)).astype(np.float32))
    b = jnp.zeros((3,), jnp.float32)
    want = np.asarray(primitives.conv_apply("fft_task", x, w, b))
    got = np.asarray(primitives.conv_apply("fft", x, w, b))  # sublayer alias
    np.testing.assert_allclose(got, want, atol=1e-5)
    direct = np.asarray(primitives.conv_apply("direct", x, w, b))
    np.testing.assert_allclose(direct, want, atol=1e-3)
    with pytest.raises(ValueError):
        primitives.conv_apply("winograd", x, w, b)


def test_cost_model_dispatch_goes_through_registry():
    c1 = cost_model.conv_cost("fft_cached", 2, 4, 4, (12, 12, 12), 3)
    c2 = cost_model.conv_fft_cached_kernels_cost(2, 4, 4, (12, 12, 12), 3)
    assert c1 == c2
    p1 = cost_model.pool_cost_by_name("mpf", 2, 4, (12, 12, 12), 2)
    assert p1 == cost_model.mpf_cost(2, 4, (12, 12, 12), 2)
    with pytest.raises(ValueError):
        cost_model.conv_cost("nope", 1, 1, 1, (8, 8, 8), 3)


def test_fft_cached_cost_drops_kernel_weight_bytes():
    """Satellite: cached cost drops kernel-FFT flops AND the weights read."""
    S, f, fp, n, k = 2, 8, 16, (16, 16, 16), 3
    task = cost_model.conv_fft_task_parallel_cost(S, f, fp, n, k)
    cached = cost_model.conv_fft_cached_kernels_cost(S, f, fp, n, k)
    assert cached.flops < task.flops
    assert cached.hbm_bytes == task.hbm_bytes - fp * f * k**3 * cost_model.F32
    assert cached.peak_bytes == task.peak_bytes  # spectra residency still paid


# -- the walk rules every walk shares ------------------------------------------

C3, C2, POOL = L("conv", 3, 4), L("conv", 2, 4), L("pool", 2)


def _prepared(net, prims):
    """Metadata-only PreparedLayers: all the walk rules read."""
    return tuple(
        primitives.PreparedLayer(i, l.kind, p)
        for i, (l, p) in enumerate(zip(net.layers, prims))
    )


@pytest.mark.parametrize(
    "layers, prims, starts",
    [
        # an fft_cached conv followed by its mpf pool is chosen
        ((C2, POOL, C3, POOL, C3, C3),
         ["fft_cached", "mpf", "fft_cached", "mpf", "direct", "direct"], (0, 2)),
        # the net's last conv is never chosen, even before an mpf pool
        ((C2, POOL, C3, POOL), ["fft_cached", "mpf", "fft_cached", "mpf"], (0,)),
        # a direct conv before an mpf pool is not chosen
        ((C2, POOL, C3, POOL, C3), ["direct", "mpf", "direct", "mpf", "fft_cached"], ()),
        # an fft_cached conv followed by a conv is not chosen
        ((C3, C3, POOL, C3), ["fft_cached", "direct", "mpf", "fft_cached"], ()),
        # a plain pool does not pair
        ((C3, POOL, C3), ["fft_cached", "pool", "fft_cached"], ()),
    ],
    ids=["eligible", "last-conv", "direct-conv", "conv-after", "plain-pool"],
)
def test_fused_pair_rule(layers, prims, starts):
    net = ConvNetConfig("rule-toy", 1, layers)
    prepared = _prepared(net, prims)
    assert primitives.fused_pair_starts(net, prepared) == starts
    # the executor's walks below layer 0 see the pairs that start there
    assert primitives.fused_pair_starts(net, prepared[1:]) == tuple(
        i for i in starts if i >= 1
    )


@pytest.mark.parametrize("i, relu", [(0, True), (1, False), (2, True), (3, False), (4, False)])
def test_activation_rule_relu_after_every_conv_but_the_last(i, relu):
    """NET is conv, pool, conv, pool, conv: ReLU after convs 0 and 2 only."""
    assert primitives.last_conv_index(NET) == 4
    y = np.asarray(primitives.activate(NET, i, jnp.asarray([-1.0, 2.0])))
    np.testing.assert_array_equal(y, [0.0, 2.0] if relu else [-1.0, 2.0])
