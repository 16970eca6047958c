"""One run of one benchmark cell: set-up, the measured window, the check.

Everything that belongs to one cell is found by name: the cell's entry in
``BENCHMARK.json`` names its configuration (``bench/configs/<name>.json``)
and its traffic mix (``bench/traffic/<name>.json``); each metric it reports
is read by ``bench/metrics/<metric>.py``.

A run:

1. builds the system under test from the configuration: the net, the
   pinned plan (``planner.plan_fixed``), weights and biases made on the
   device from the seed, one ``VolumeEngine``;
2. makes the mix's input volumes from the seed and serves the mix's
   warm-up sequences, so that every program the window reaches is
   compiled (all of this is ``setup_s``);
3. drives ``VolumeEngine.submit``/``step`` for ``seconds``: each closed
   stream of the mix keeps requests queued behind the one in service, each
   open stream submits its requests when they are due;
4. after the window: finishes the requests the window started, reads the
   device's memory peak, frees the engine, and compares a sample of the
   window's outputs, drawn from the seed, with the plain reference
   (``bench/reference.py``).
"""

from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

from . import generator, reference

BENCH = Path(__file__).resolve().parent
REPO = BENCH.parent
GRACE_S = 60.0  # how long a request due in the window may take after it


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# -- the cell, found by name ----------------------------------------------------


def load_cell(name: str, benchmark: Optional[dict] = None) -> dict:
    """The cell ``name`` with its configuration, mix and metric entries."""
    bm = benchmark or json.loads((REPO / "BENCHMARK.json").read_text())
    entry = next((w for w in bm["workloads"] if w["name"] == name), None)
    if entry is None:
        known = [w["name"] for w in bm["workloads"]]
        raise SystemExit(f"unknown workload {name!r}; known: {known}")
    e2e = [
        m for m in bm["end_to_end"]
        if "workloads" not in m or name in m["workloads"]
    ]
    reported = {m["name"] for m in e2e}
    per_layer = [
        m for m in bm["per_layer"]
        if (name in m["workloads"] if "workloads" in m else m["moves"] in reported)
    ]
    return {
        "name": name,
        "chips": entry["chips"],
        "config": json.loads((BENCH / "configs" / f"{entry['config']}.json").read_text()),
        "traffic": json.loads((BENCH / "traffic" / f"{entry['traffic']}.json").read_text()),
        "end_to_end": e2e,
        "per_layer": per_layer,
    }


def load_metric(metric: str):
    """The reader ``bench/metrics/<metric>.py``: ``read(run)`` gives the
    number or None, and ``COUNTERS``, where present, names the program
    counters it reads from each tick."""
    path = BENCH / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{len(sys.modules)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def counter_table(cell: dict) -> Dict[str, str]:
    """Every counter the cell's metric readers read, by its ``last_stats``
    name, with the executor attribute that holds it.  The served tick loop
    does not publish these in ``last_stats`` (an offline ``run()`` does,
    under the same names), so the harness reads the attributes ``run()``
    publishes, and only those the readers name."""
    table: Dict[str, str] = {}
    for m in cell["per_layer"] + cell["end_to_end"]:
        table.update(getattr(load_metric(m["name"]), "COUNTERS", {}))
    return table


# -- what a run records ---------------------------------------------------------


@dataclass
class Tick:
    t0: float
    t1: float
    vox: int  # dense output voxels the tick wrote, cropped to each request
    counters: Dict[str, int]  # how far each counter the readers name moved in the tick
    rids: tuple


@dataclass
class Request:
    rid: int
    size: int
    due: Optional[float]  # host clock; None for a closed stream
    submitted: float
    stream: int = 0
    done: Optional[float] = None
    req: object = None  # the engine's VolumeRequest
    volume: Optional[np.ndarray] = None


@dataclass
class Run:
    """Everything a metric reader may read."""

    cell: dict
    seconds: float
    setup_s: float = 0.0
    t_open: float = 0.0  # first tick's start
    t_close: float = 0.0  # end of the window's last tick (closed streams only) or its close
    ticks: List[Tick] = field(default_factory=list)
    requests: Dict[int, Request] = field(default_factory=dict)
    compiles_in_window: int = 0
    trace: Optional[dict] = None  # bench/trace.py's reduction (--trace 1)
    device_kind: str = ""

    @property
    def window_ticks(self) -> List[Tick]:
        return [t for t in self.ticks if t.t0 < self.t_close]

    def attempted(self) -> List[Request]:
        """Requests the window owes an answer for: due in it (open stream)
        or served in it (closed stream)."""
        served = {rid for t in self.window_ticks for rid in t.rids}
        return [
            r for rid, r in sorted(self.requests.items())
            if (r.due < self.t_close if r.due is not None else rid in served)
        ]


# -- building the system under test ----------------------------------------------


def build(cell: dict, seed: int, hw):
    """(engine, params, net dict) for ``cell`` with weights from ``seed``."""
    from repro.configs.base import ConvLayerSpec, ConvNetConfig
    from repro.core import planner
    from repro.serving import VolumeEngine

    config, traffic = cell["config"], cell["traffic"]
    net_d = config["net"]
    net = ConvNetConfig(
        name=config["name"],
        in_channels=net_d["in_channels"],
        layers=tuple(
            ConvLayerSpec("conv", l[1], l[2]) if l[0] == "conv" else ConvLayerSpec("pool", l[1])
            for l in net_d["layers"]
        ),
    )
    p = config["plan"]
    largest = tuple(max(traffic["sizes"], key=math.prod))
    plan = planner.plan_fixed(
        net, hw, p["prims"], m=p["m"], batch=p["batch"], volume_shape=largest,
        sweep_axis=0, strategy_name="bench",
    )
    if plan is None:
        raise SystemExit(f"plan {p} for {config['name']} is infeasible on {hw.name}")
    params = reference.make_params(net_d, seed, config["bias_std"])
    engine = VolumeEngine(params, net, plan, tuned=None)
    if "sweeps_open" in traffic:
        # room for this many of the largest request's sweeps beside the
        # prepared states: later requests wait for the open ones to drain
        ex = engine.executor
        mem = ex.predict_memory(largest)
        engine.device_budget = mem.spectra_bytes + traffic["sweeps_open"] * ex.sweep_bytes_estimate(largest)
    sweep = engine.executor.predict_counts(largest)
    log(f"[plan] {config['name']} prims={list(plan.prims)} m={plan.m_final} "
        f"batch={plan.batch} core={plan.core} fov={plan.fov} "
        f"predicted {plan.throughput:,.0f} vox/s (planner, not a metric); "
        f"{largest}: {sweep.strip_patches} strip, {sweep.full_patches} full patches")
    return engine, params, net_d


# -- the client ------------------------------------------------------------------


class Client:
    """Submits requests and ticks the engine, recording what happened."""

    def __init__(self, engine, run: Run, pools, spans, counters: Dict[str, str]):
        self.engine = engine
        self.ex = engine.executor
        self.run = run
        self.pools = pools
        self.span = spans  # a context-manager factory: name -> span
        self.priority = [s.get("priority", 0) for s in run.cell["traffic"]["streams"]]
        self.n_finished = len(engine.finished)
        self._tilings: Dict[tuple, object] = {}
        self._by_req: Dict[int, Request] = {}
        missing = {k: a for k, a in counters.items()
                   if type(getattr(self.ex, a, None)) is not int}
        if missing:
            raise RuntimeError(f"the executor no longer keeps the counters {missing} "
                               "(last_stats name: attribute) that the metric readers read")
        self.counter_attrs = counters

    def counters(self) -> Dict[str, int]:
        return {k: getattr(self.ex, a) for k, a in self.counter_attrs.items()}

    def submit(self, rid: int, arrival, due: Optional[float]) -> None:
        from repro.serving import VolumeRequest

        vol = self.pools[arrival.size][arrival.pool]
        req = VolumeRequest(rid, vol, priority=self.priority[arrival.stream])
        with self.span("submit"):
            self.engine.submit(req)
        rec = Request(rid, arrival.size, due, time.perf_counter(), arrival.stream,
                      req=req, volume=vol)
        self.run.requests[rid] = rec
        self._by_req[id(req)] = rec

    def _voxels(self, req, idx: int) -> int:
        """Dense output voxels patch ``idx`` writes into ``req.out``."""
        true_shape = req.volume.shape[1:]
        key = tuple(true_shape)
        tiling = self._tilings.get(key)
        if tiling is None:
            tiling = self.ex.tiling_for(self.ex.bucket_shape(true_shape))
            self._tilings[key] = tiling
        out = req.out.shape[1:]
        start = tiling.patches[idx].start
        n = 1
        for i, s in enumerate(start):
            n *= max(0, min(s + tiling.core, out[tiling.perm[i]]) - s)
        return n

    def tick(self) -> int:
        before: Dict[int, list] = {}
        for req, idx in self.engine.queue:
            before.setdefault(id(req), []).append((req, idx))
        c0 = self.counters()
        t0 = time.perf_counter()
        with self.span("step"):
            n = self.engine.step()
        t1 = time.perf_counter()
        if n == 0:
            return 0
        after: Dict[int, int] = {}
        for req, _ in self.engine.queue:
            after[id(req)] = after.get(id(req), 0) + 1
        vox, rids = 0, []
        for key, items in before.items():
            done = len(items) - after.get(key, 0)
            if done:
                rids.append(self._by_req[key].rid)
                vox += sum(self._voxels(req, idx) for req, idx in items[:done])
        c1 = self.counters()
        self.run.ticks.append(Tick(t0, t1, vox, {k: v - c0.get(k, 0) for k, v in c1.items()},
                                   tuple(rids)))
        for req in self.engine.finished[self.n_finished:]:
            self._by_req[id(req)].done = t1
        self.n_finished = len(self.engine.finished)
        return n

    def warm(self, sequences) -> None:
        """Serve each sequence of size indices, all submitted at once."""
        rid = -1
        for seq in sequences:
            for size in seq:
                self.submit(rid, generator.Arrival(size, 0, None), None)
                rid -= 1
            while self.tick():
                pass
        self.run.requests.clear()
        self.run.ticks.clear()

    def serve(self, streams, seconds: float) -> None:
        """Drive the mix's ``streams`` (``generator.streams``) for ``seconds``.

        Closed streams are kept topped up to their depth, open ones submit
        each request when it is due, all into one queue.  With closed
        streams alone the window runs from the first tick's start to the
        end of the tick that completes ``seconds``.  Otherwise it closes
        ``seconds`` after it opens, closed streams send no more, and the
        ticks go on until every request due in it is served.
        """
        run = self.run
        closed = [s for s in streams if s.spec["loop"] == "closed"]
        timed = sorted((a for s in streams if s.spec["loop"] == "open" for a in s.arrivals),
                       key=lambda a: a.due)
        rid = 0

        def submit(arrival, due):
            nonlocal rid
            self.submit(rid, arrival, due)
            rid += 1

        def top_up():
            for s in closed:
                depth = 1 + s.spec["queued"]
                while sum(self._by_req[id(r)].stream == s.index for r in self.engine.active) < depth:
                    submit(next(s.arrivals), None)

        if not timed:
            top_up()
            while True:
                self.tick()
                top_up()
                if run.ticks and run.ticks[-1].t1 - run.ticks[0].t0 >= seconds:
                    break
            run.t_open = run.ticks[0].t0
            run.t_close = run.ticks[-1].t1
            return
        t_open = time.perf_counter()
        dues = [t_open + a.due for a in timed]
        run.t_open, run.t_close = t_open, t_open + seconds
        i, n = 0, len(timed)
        while True:
            now = time.perf_counter()
            while i < n and dues[i] <= now:
                submit(timed[i], dues[i])
                i += 1
            if now < run.t_close:
                top_up()
            elif i >= n and all(r.done is not None for r in run.requests.values()
                                if r.due is not None):
                break  # every due request served; closed streams have stopped
            if self.tick():
                continue
            if i >= n:
                break  # every request submitted, every one served
            if now > run.t_close + GRACE_S:
                break
            with self.span("wait"):
                time.sleep(max(0.0, min(dues[i] - time.perf_counter(), 0.01)))

    def finish(self, wanted: List[Request]) -> None:
        """Tick until every request in ``wanted`` is done (or the grace ends)."""
        limit = time.perf_counter() + GRACE_S
        while any(r.done is None for r in wanted) and time.perf_counter() < limit:
            if not self.tick():
                break


# -- the check ---------------------------------------------------------------------


def sample_boxes(run: Run, check: dict, fov: int, seed: int):
    """[(request, offset, edge)] drawn from the seed among answered requests;
    the largest answered request is always among them."""
    rng = generator.rng_for(seed, 3)
    done = [r for r in run.attempted() if r.done is not None]
    if not done:
        return []
    done.sort(key=lambda r: (-math.prod(r.req.out.shape[1:]), r.rid))
    picks = [done[0]] + [done[int(rng.integers(len(done)))] for _ in range(check["boxes"] - 1)]
    boxes = []
    for r in picks:
        out = r.req.out.shape[1:]
        edge = tuple(min(check["edge"], n) for n in out)
        off = tuple(int(rng.integers(0, n - e + 1)) for n, e in zip(out, edge))
        boxes.append((r, off, edge))
    return boxes


def compare(boxes, params, net: dict, fov: int, sources=("program",)) -> Dict[str, list]:
    """The number compared, per box and per source: the largest deviation
    from the reference at ``highest``, over the largest deviation of the
    reference computed at the configurations' precision (bfloat16
    products).  ``"program"`` is what the window served; any other source
    is a control, the reference at that precision put in the program's
    place.  A sound program reads about 1: it rounds as a bfloat16-product
    computation does; a fault or a lower precision reads several times that.
    """
    ratios: Dict[str, list] = {s: [] for s in sources}
    for r, off, edge in boxes:
        box = r.volume[(slice(None),) + tuple(slice(o, o + e + fov - 1) for o, e in zip(off, edge))]
        want = reference.dense(params, net, box, "highest")
        base = reference.gap(reference.dense(params, net, box, "bf16_products"), want)
        for src in sources:
            if src == "program":
                got = r.req.out[(slice(None),) + tuple(slice(o, o + e) for o, e in zip(off, edge))]
            else:
                got = reference.dense(params, net, box, src)
            g = reference.gap(got, want)
            ratios[src].append(g / base if base > 0 else float("inf"))
            log(f"[check] {src} box {r.rid}@{off}+{edge}: gap {g:.4e}, bf16-product gap {base:.4e}")
    return ratios


# -- one run -----------------------------------------------------------------------


def _null_span(name):
    return contextlib.nullcontext()


class GcPauses:
    """Python's garbage-collector pauses, (start, end, generation), while
    registered: a long tick with a collection inside it is the host's."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t0: Optional[float] = None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((self._t0, time.perf_counter(), info["generation"]))
            self._t0 = None

    def within(self, t0: float, t1: float) -> float:
        return sum(max(0.0, min(b, t1) - max(a, t0)) for a, b, _ in self.pauses)


def _retraces(engine) -> int:
    """Distinct jit specialisations the executor has dispatched."""
    return engine.executor.last_stats.get("retraces", 0)


def run_cell(cell: dict, seed: int, seconds: float, traced: bool, *, hw, t_start: float,
             devices=None, controls=()) -> dict:
    """Run ``cell`` once and return the result line's object.  ``controls``
    (precisions of ``bench/reference.py``) are compared on the same boxes
    as the program; the benchmark's own runs pass none."""
    import jax

    devices = devices or jax.devices()
    dev = devices[0]
    config, traffic = cell["config"], cell["traffic"]
    run = Run(cell, seconds, device_kind=dev.device_kind)

    engine, params, net = build(cell, seed, hw)
    fov = reference.field_of_view(net)
    pools = generator.make_pools(traffic, seed, net["in_channels"])
    spans = jax.profiler.TraceAnnotation if traced else _null_span
    drv = Client(engine, run, pools, spans, counter_table(cell))
    t = time.perf_counter()
    drv.warm(traffic["warmup"])
    log(f"[setup] warm-up {time.perf_counter() - t:.3f} s; jit specialisations "
        f"{_retraces(engine)}")

    compiles = []
    import jax.monitoring as monitoring

    def on_event(event, duration, **kw):
        if "compile" in event or "trace" in event:
            compiles.append(event)

    monitoring.register_event_duration_secs_listener(on_event)
    tdir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    if traced:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the harness's own spans suffice
        jax.profiler.start_trace(tdir, profiler_options=options)
    n0 = len(compiles)
    specs0 = _retraces(engine)
    gc_pauses = GcPauses()
    gc.callbacks.append(gc_pauses)
    drv.serve(generator.streams(traffic, seed, seconds), seconds)
    gc.callbacks.remove(gc_pauses)
    run.compiles_in_window = len(compiles) - n0
    monitoring.unregister_event_duration_listener(on_event)
    run.setup_s = run.t_open - t_start
    if traced:
        jax.profiler.stop_trace()
    log(f"[window] {len(run.window_ticks)} ticks in {run.t_close - run.t_open:.3f} s; "
        f"compile events in the window: {run.compiles_in_window}; jit specialisations "
        f"{specs0} -> {_retraces(engine)}")
    ticks = run.window_ticks
    if ticks:
        spans_s = [t.t1 - t.t0 for t in ticks]
        between = sum(b.t0 - a.t1 for a, b in zip(ticks, ticks[1:]))
        slow = max(ticks, key=lambda t: t.t1 - t.t0)
        log(f"[window] tick seconds: median {float(np.median(spans_s)):.4f}, max "
            f"{max(spans_s):.4f} at +{slow.t0 - run.t_open:.3f} s with "
            f"{gc_pauses.within(slow.t0, slow.t1):.4f} s of garbage collection in it; "
            f"host time between ticks {between:.3f} s")
    log(f"[window] garbage collections: {len(gc_pauses.pauses)}, "
        f"{sum(b - a for a, b, _ in gc_pauses.pauses):.4f} s in all, longest "
        f"{max((b - a for a, b, _ in gc_pauses.pauses), default=0.0):.4f} s")
    lags = [r.submitted - r.due for r in run.requests.values() if r.due is not None]
    if lags:
        # how late the generator submitted: a request waits here while a tick runs
        log(f"[window] generator lag over {len(lags)} requests: median "
            f"{float(np.median(lags)):.4f} s, max {max(lags):.4f} s")

    if traced:
        from . import trace as trace_mod

        try:
            run.trace = trace_mod.reduce_dir(tdir)
        finally:
            shutil.rmtree(tdir, ignore_errors=True)

    attempted = run.attempted()
    drv.finish(attempted)
    failed = sum(1 for r in attempted if r.done is None)
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devices)

    check = config["check"]
    boxes = sample_boxes(run, check, fov, seed)
    for r in run.requests.values():
        if all(r is not b[0] for b in boxes):
            r.req = r.volume = None
    engine = drv = None  # free the program's device state before the reference runs
    gc.collect()

    t = time.perf_counter()
    ratios = compare(boxes, params, net, fov, ("program",) + tuple(controls))
    log(f"[check] {len(boxes)} boxes against the reference in {time.perf_counter() - t:.3f} s")
    worst = {src: (max(v) if v else float("inf")) for src, v in ratios.items()}
    limit = config["limits"]["err_ratio"]
    correct = bool(boxes) and worst["program"] <= limit

    metrics = {}
    for m in (cell["per_layer"] if traced else cell["end_to_end"]):
        value = load_metric(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": dev.platform, "kind": dev.device_kind, "count": len(devices),
        "memory_peak_bytes": int(peak),
    }
    result = {"correct": correct, "attempted": len(attempted), "failed": failed,
              "metrics": metrics, "device": device}
    if traced:
        device["busy_s"] = run.trace["busy_s"]
        device["window_s"] = run.trace["window_s"]
        result["breakdown"] = run.trace["breakdown"]
    if controls:
        result["controls"] = {src: worst[src] for src in controls}
    checks = {"err_ratio": {"value": worst["program"], "limit": limit}}
    result["checks"] = checks
    for name, c in checks.items():
        log(f"[check] {name} {c['value']!r} limit {c['limit']!r}")
    return result
