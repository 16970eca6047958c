"""The volume service's spans and served-path counters (``repro.obs``).

A span nests under the one open on its own thread, its self time is its
duration less its children's, the ring keeps the newest records and
says when it dropped one a window needs, and a span lands in the
profiler's own trace.  Serving one request through ``VolumeEngine``
records only dotted span names (the benchmark's trace reduction owns
``step``, ``submit`` and ``wait``), one ``engine.queued`` mark per
request, and ``last_stats`` counters equal to the planner's prediction.
"""

import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.configs.base import ConvLayerSpec as L, ConvNetConfig
from repro.core import convnet
from repro.serving import VolumeEngine, VolumeRequest

NET = ConvNetConfig(
    "obs-toy", 1,
    (L("conv", 3, 4), L("pool", 2), L("conv", 3, 4), L("pool", 2), L("conv", 3, 2)),
)
MIX = [
    "overlap_save" if i == 0 else ("fft_cached" if l.kind == "conv" else "mpf")
    for i, l in enumerate(NET.layers)
]
FOV = NET.field_of_view()
CORE = NET.total_pooling()
# interior-rich (8 patches, 6 on the strip path) and a 3x3x3 patch cube
SHAPES = {
    "interior": (4 * CORE + FOV - 1, 2 * CORE + FOV - 1, CORE + FOV - 1),
    "cube": (3 * CORE + FOV - 1,) * 3,
}
SERVED = {
    "engine.submit", "engine.tick", "engine.schedule", "engine.write", "engine.queued",
    "executor.begin_sweep", "executor.resolve", "executor.dispatch", "executor.fetch",
    "executor.store",
}


@pytest.fixture(scope="module")
def params():
    return convnet.init_params(jax.random.PRNGKey(0), NET)


def _volume(shape, seed=0):
    return np.random.default_rng(seed).normal(size=(1,) + shape).astype(np.float32)


def _serve(params, requests, batch=2):
    """Serve ``requests`` [(rid, shape)] through one engine; return the
    engine and every record made meanwhile."""
    eng = VolumeEngine(params, NET, prims=MIX, m=1, batch=batch, tuned=None)
    t0 = time.perf_counter()
    for rid, shape in requests:
        eng.submit(VolumeRequest(rid, _volume(shape, rid)))
    eng.run_until_drained()
    recs, dropped = obs.spans(t0)
    assert not dropped
    return eng, recs


def test_nesting_parent_and_self_time():
    rec = obs.Recorder()
    with rec.span("a", k=1) as a:
        time.sleep(0.01)
        with rec.span("b") as b:
            time.sleep(0.02)
        with rec.span("c") as c:
            with rec.span("d") as d:
                time.sleep(0.01)
    recs, dropped = rec.spans()
    assert not dropped
    by = {r.name: r for r in recs}
    assert [r.name for r in recs] == ["b", "d", "c", "a"]  # in order of their end
    assert by["a"].parent is None and by["a"].attrs == {"k": 1}
    assert by["b"].parent == by["c"].parent == a.sid
    assert by["d"].parent == c.sid and b.sid != d.sid
    own = obs.self_times(recs)
    dur = {r.name: r.t1 - r.t0 for r in recs}
    assert own[a.sid] == pytest.approx(dur["a"] - dur["b"] - dur["c"])
    assert own[c.sid] == pytest.approx(dur["c"] - dur["d"])
    assert own[d.sid] == dur["d"] >= 0.01
    assert 0.01 <= own[a.sid] < dur["a"] - 0.03


def test_spans_nest_per_thread():
    rec = obs.Recorder()
    gate = threading.Barrier(2, timeout=10)

    def work(tag):
        with rec.span(f"outer.{tag}"):
            gate.wait()  # both outer spans are open at once
            with rec.span(f"inner.{tag}"):
                gate.wait()

    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    assert not any(t.is_alive() for t in threads)
    recs, _ = rec.spans()
    by = {r.name: r for r in recs}
    assert len(by) == 4
    for tag in "xy":
        assert by[f"outer.{tag}"].parent is None
        assert by[f"inner.{tag}"].parent == by[f"outer.{tag}"].sid
    with rec.span("after"):
        pass
    assert rec.spans()[0][-1].parent is None  # nothing left open on this thread


def test_ring_keeps_the_newest_and_reports_drops(monkeypatch):
    monkeypatch.setattr(obs, "RING", 4)
    rec = obs.Recorder()
    for i in range(6):  # [0, 1], [1, 2], ... [5, 6]: the first two fall out
        rec.mark("m", float(i), float(i + 1), i=i)
    recs, dropped = rec.spans()
    assert [r.attrs["i"] for r in recs] == [2, 3, 4, 5]
    assert dropped  # the unbounded window reaches the dropped [0, 1] and [1, 2]
    recs, dropped = rec.spans(2.5, 4.5)
    assert [r.attrs["i"] for r in recs] == [2, 3, 4] and not dropped
    assert rec.spans(2.0)[1]  # [1, 2] ended at 2.0, inside the window
    assert [r.attrs["i"] for r in rec.spans(5.5, 9.0)[0]] == [5]
    assert not obs.Recorder().spans()[1]


def test_a_span_lands_in_the_profilers_trace(tmp_path):
    from jax.profiler import ProfileData

    rec = obs.Recorder()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("executor.fetch", rid=5):
            time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert "executor.fetch" in names
    assert [r.name for r in rec.spans()[0]] == ["executor.fetch"]


def test_served_spans_are_dotted_and_never_the_harness_names(params):
    _, recs = _serve(params, [(1, SHAPES["interior"])])
    names = {r.name for r in recs}
    assert names == SERVED
    assert not names & {"step", "submit", "wait"}
    ticks = {r.sid for r in recs if r.name == "engine.tick"}
    inside = {"engine.schedule", "engine.write", "executor.resolve", "executor.dispatch",
              "executor.fetch", "executor.store", "executor.begin_sweep"}
    by_sid = {r.sid: r for r in recs}

    def tick_of(r):
        while r.parent is not None:
            r = by_sid[r.parent]
        return r.sid

    # every executor and engine phase runs inside a tick, nested once
    assert all(tick_of(r) in ticks for r in recs if r.name in inside)
    assert all(by_sid[r.parent].name == "engine.tick" for r in recs
               if r.name in ("engine.schedule", "engine.write", "executor.dispatch"))


def test_a_tick_files_everything_before_it_blocks_on_the_device(params):
    """In a single-sweep tick the host resolves, dispatches and files every
    group's spectra and halos before the tick's first ``executor.fetch``:
    the store runs while the device computes, not after it has finished."""
    eng, recs = _serve(params, [(1, SHAPES["interior"])])
    stats = eng.executor.last_stats
    assert stats["deep_strip_patches"] > 0 and stats["deep_full_patches"] > 0
    by_sid = {r.sid: r for r in recs}

    def tick_of(r):
        while r.parent is not None:
            r = by_sid[r.parent]
        return r.sid

    ticks: dict = {}
    for r in recs:
        if r.name.startswith("executor."):
            ticks.setdefault(tick_of(r), []).append(r)
    assert all(any(r.name == "executor.store" for r in rs) for rs in ticks.values())
    for rs in ticks.values():
        fetch = min(r.t0 for r in rs if r.name == "executor.fetch")
        late = [r.name for r in rs if r.t0 > fetch
                and r.name in ("executor.resolve", "executor.dispatch", "executor.store")]
        assert not late, late


def test_queued_marks_each_request_once(params):
    shapes = [SHAPES["interior"], SHAPES["cube"], SHAPES["interior"]]
    eng, recs = _serve(params, list(zip((11, 12, 13), shapes)))
    queued = [r for r in recs if r.name == "engine.queued"]
    assert sorted(r.attrs["rid"] for r in queued) == [11, 12, 13]
    submits = {r.attrs["rid"]: r for r in recs if r.name == "engine.submit"}
    for r in queued:
        assert r.parent is None
        assert submits[r.attrs["rid"]].t0 <= r.t0 <= r.t1
    # FIFO: requests leave the queue in the order they entered it
    ends = [r.t1 for r in sorted(queued, key=lambda r: r.attrs["rid"])]
    assert ends == sorted(ends)
    assert len(eng.finished) == 3


@pytest.mark.parametrize("batch", [1, 2])
@pytest.mark.parametrize("shape", SHAPES.values(), ids=SHAPES.keys())
def test_served_counters_equal_the_prediction(params, shape, batch):
    eng, _ = _serve(params, [(1, shape)], batch=batch)
    ex = eng.executor
    pred = ex.predict_counts(shape)
    stats = ex.last_stats
    assert ex.bucket_shape(shape) == shape
    assert (stats["os_seg_fft"], stats["os_seg_hits"], stats["os_mad_segments"],
            stats["deep_strip_patches"], stats["deep_full_patches"]) == (
        pred.seg_fft, pred.seg_hits, pred.mad_segments, pred.strip_patches, pred.full_patches)
    assert 0 < pred.strip_patches < pred.n_patches
    # the benchmark's strip_share reader still finds the private counters
    assert (ex._deep_strips, ex._deep_fulls) == (pred.strip_patches, pred.full_patches)


def test_programs_name_their_layers(params):
    """The jitted steps trace under ``os_step`` / ``os_strip_step`` with a
    ``L<i>.<prim>`` scope per layer, carried into the compiled ops."""
    eng = VolumeEngine(params, NET, prims=MIX, m=1, batch=2, tuned=None)
    ex = eng.executor
    calls = {}
    for top in ("os_step", "os_strip_step"):
        jitted = getattr(ex, f"_jit_{top}")

        def keep(*a, _top=top, _jitted=jitted, **kw):
            calls.setdefault(_top, (_jitted, a, kw))
            return _jitted(*a, **kw)

        setattr(ex, f"_jit_{top}", keep)
    eng.submit(VolumeRequest(1, _volume(SHAPES["interior"])))
    eng.run_until_drained()
    assert set(calls) == {"os_step", "os_strip_step"}
    for top, (program, a, kw) in calls.items():
        text = program.lower(*a, **kw).compile().as_text()
        scopes = set(re.findall(r'op_name="jit\([^)]*\)/([^"]*)"', text))
        walk = f"{top}/" if top == "os_strip_step" else f"{top}/os_walk/"
        for layer in ("L0.overlap_save", "L1.mpf", "L2.fft_cached", "L3.mpf", "L4.fft_cached",
                      "recombine"):
            assert any(s.startswith(walk + layer) for s in scopes), (top, layer)
        assert any(s.startswith(f"{top}/L0.spectra") for s in scopes)


def test_conv_flops_counts_each_dispatched_walk(params):
    """``conv_flops``: Σ 2·k³·f·f' × conv output voxels of every step the
    executor dispatched, an exact int, cumulative like the other
    ``counter_stats`` entries.  Two patches in x, one per tick: the first
    walks its whole 21³ extent (layer 0 to 19³, 8 MPF fragments of 7³ at
    layer 2, 64 of 1³ at layer 4), the second the strip path, whose convs
    output only its new core columns (4, then 2 and 1 per fragment)."""
    full = (2 * 27 * 1 * 4 * 19**3          # layer 0: 1 -> 4 maps
            + 2 * 27 * 4 * 4 * 8 * 7**3     # layer 2: 8 fragments
            + 2 * 27 * 4 * 2 * 64 * 1**3)   # layer 4: 64 fragments, 4 -> 2 maps
    strip = (2 * 27 * 1 * 4 * 4 * 19**2
             + 2 * 27 * 4 * 4 * 8 * 2 * 7**2
             + 2 * 27 * 4 * 2 * 64 * 1)
    assert (full, strip) == (3_880_008, 1_016_928)
    eng = VolumeEngine(params, NET, prims=MIX, m=1, batch=1, tuned=None)
    eng.submit(VolumeRequest(0, _volume((2 * CORE + FOV - 1,) + (CORE + FOV - 1,) * 2)))
    seen = []
    while eng.step():
        stats = eng.executor.last_stats
        seen.append((stats["deep_full_patches"], stats["deep_strip_patches"],
                     stats["conv_flops"]))
    assert seen == [(1, 0, full), (1, 1, full + strip)]
    assert type(eng.executor.counter_stats()["conv_flops"]) is int


@pytest.mark.parametrize("f,fp,n,kz", [(8, 8, 13, 3), (3, 8, 13, 1), (8, 8, 7, 1), (8, 2, 13, 1)])
def test_direct_conv3d_tiling_mark_records_the_stacking(f, fp, n, kz):
    """``direct_conv3d.tiling`` records the picker's stacking: the dz taps
    a dot holds (``taps_per_dot``: all three at k=3 where f fills whole
    staged tiles, f' is 8 or more and a row spans more than 128 lanes,
    else one) and the contraction they make (``contract``: taps × f)."""
    from repro.kernels.direct_conv3d import ops as conv_ops

    k = 3
    x = jnp.ones((1, f, n, n, n), jnp.float32)
    w = jnp.ones((fp, f, k, k, k), jnp.float32)
    t0 = time.perf_counter()
    jax.eval_shape(lambda x, w: conv_ops.conv3d(x, w, use_pallas=True, interpret=True), x, w)
    marks = [r for r in obs.spans(t0)[0] if r.name == "direct_conv3d.tiling"]
    assert len(marks) == 1
    b = conv_ops._pick_blocking(n - k + 1, k, f, fp, n, n * n, (n - k + 1) * n,
                                conv_ops.stage_dtype(f, interpret=True))
    attrs = marks[0].attrs
    assert (attrs["taps_per_dot"], attrs["contract"]) == (b.kz, b.kz * f) == (kz, kz * f)
    assert (attrs["tx"], attrs["dx_steps"], attrs["vmem_bytes"]) == (b.tx, k // b.kd, b.vmem)
    assert attrs["stage"] == "float32"
