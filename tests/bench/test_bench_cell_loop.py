"""The harness's cell loop on the CPU, at a tiny size.

Checks the parts of a run that need no chip: the generator repeats from
the seed, a run's last line has the contract's keys (``checks`` last), and
``bench/run.py`` itself refuses to measure without a TPU or without the
system under test.
"""

import itertools
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

import bench_tiny_cell as tiny
from bench import generator, harness

REPO = Path(__file__).resolve().parents[2]


def _first(traffic, seed, n=12, seconds=3.0):
    """The first ``n`` requests of the mix's first stream."""
    return list(itertools.islice(generator.streams(traffic, seed, seconds)[0].arrivals, n))


@pytest.mark.parametrize("name", ["n337.bulk", "n337.roi"])
def test_generator_repeats_from_the_seed(name):
    traffic = tiny.cell(name)["traffic"]
    n = 60 if traffic["streams"][0]["loop"] == "open" else 12
    a = _first(traffic, tiny.SEED, n)
    assert a == _first(traffic, tiny.SEED, n)
    pa = generator.make_pools(traffic, tiny.SEED, 1)
    pb = generator.make_pools(traffic, tiny.SEED, 1)
    assert all(np.array_equal(x, y) for p, q in zip(pa, pb) for x, y in zip(p, q))
    other = _first(traffic, tiny.SEED + 1, n)
    # another seed changes the order, never the amount of work
    assert sorted(x.size for x in a) == sorted(x.size for x in other)
    if traffic["streams"][0]["loop"] == "open":
        # one arrival trace, rotated by the seed: the same sizes and gaps
        assert a != other
        assert len(_first(traffic, tiny.SEED, 1000)) == 60
        assert all(0 <= x.due < 3.0 for x in a)
        gaps = lambda xs: list(np.diff([x.due for x in xs] + [3.0]))  # noqa: E731
        turn = next(t for t in range(60) if [x.size for x in a[t:] + a[:t]] == [x.size for x in other]
                    and np.allclose(np.roll(gaps(a), -t)[:-1], gaps(other)[:-1]))
        assert 0 < turn < 60


@pytest.mark.parametrize("name", ["n337.bulk", "n337.roi"])
def test_last_line_keys_and_metrics(name):
    r = tiny.run(name)
    assert list(r) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    expect = {"n337.bulk": {"vox_per_s", "setup_s"},
              "n337.roi": {"latency_p50_s", "latency_p95_s", "setup_s"}}[name]
    assert set(r["metrics"]) == expect
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert r["checks"]["err_ratio"]["value"] <= r["checks"]["err_ratio"]["limit"]
    json.dumps(r)


def test_bulk_readers_match_the_planners_counts(monkeypatch):
    """The bulk cell's per-tick counters: ``strip_share`` over the window
    equals the share of strip-path patches that the executor's own sweep
    simulation (the planner's ``SweepCounts``) predicts for the block."""
    from repro.core.hw import TPU_V5E

    seen = {}
    attempted = harness.Run.attempted

    def keep(run):
        seen["run"] = run
        return attempted(run)

    monkeypatch.setattr(harness.Run, "attempted", keep)
    tiny.run("n337.bulk", seconds=1.0)
    run = seen["run"]
    assert all(set(t.counters) == {"deep_strip_patches", "deep_full_patches"} for t in run.ticks)
    engine, _, _ = harness.build(tiny.cell("n337.bulk"), tiny.SEED, TPU_V5E)
    want = engine.executor.predict_counts(tuple(tiny.SIZES["n337.bulk"][0]))
    assert 0 < want.strip_patches < want.n_patches
    share = harness.load_metric("strip_share").read(run)
    assert share == pytest.approx(100.0 * want.strip_patches / want.n_patches)
    assert harness.load_metric("vox_per_s").read(run) > 0


def test_a_renamed_counter_fails_loudly(monkeypatch):
    """A reader's counter that the executor no longer keeps stops the run
    before the window, instead of reading as nought."""
    from repro.volume.executor import PlanExecutor

    init = PlanExecutor.__init__

    def renamed(self, *a, **kw):
        init(self, *a, **kw)
        self._strips_renamed = self.__dict__.pop("_deep_strips")

    monkeypatch.setattr(PlanExecutor, "__init__", renamed)
    with pytest.raises(RuntimeError, match="deep_strip_patches"):
        tiny.run("n337.bulk")


# Two mixes the benchmark has no cell for yet, written as data only: on/off
# bursts at the ROI mix's mean rate, and ROI requests first in the queue of a
# bulk job.  The generator and the client serve them with no new code.
BURST = {"sizes": [[20, 20, 20], [24, 24, 24]], "pool": [2, 2],
         "streams": [{"loop": "open", "rate_per_s": 20.0, "mix": [3, 1],
                      "bursts": {"on_s": 0.25, "off_s": 0.75}}],
         "warmup": [[0], [0, 0], [0, 1], [1], [1, 0], [1, 1]]}
MIXED = {"sizes": [[24, 24, 24], [20, 20, 20]], "pool": [2, 2],
         "streams": [{"loop": "closed", "mix": [1, 0], "queued": 1},
                     {"loop": "open", "rate_per_s": 8.0, "mix": [0, 1], "priority": 1}],
         "sweeps_open": 2,
         "warmup": [[0], [0, 0], [0, 1], [1], [1, 0], [1, 1]]}


@pytest.mark.parametrize("mix", [BURST, MIXED], ids=["roi_burst", "mixed_priority"])
def test_later_mixes_are_data_only(mix):
    cell = dict(tiny.cell("n337.roi"), traffic=mix)
    timed = [a for s in generator.streams(mix, tiny.SEED, 2.0) if s.spec["loop"] == "open"
             for a in s.arrivals]
    assert len(timed) == round(mix["streams"][-1]["rate_per_s"] * 2.0)
    if "bursts" in mix["streams"][0]:
        # every request falls in the first quarter of a second of each second
        assert all((a.due % 1.0) < 0.25 for a in timed) and max(a.due for a in timed) > 1.0
    from repro.core.hw import TPU_V5E
    from repro.serving import VolumeEngine

    seen, priorities = {}, {}
    attempted, submit = harness.Run.attempted, VolumeEngine.submit

    def keep(run):
        seen["run"] = run
        return attempted(run)

    def note(engine, req):
        priorities[req.rid] = req.priority
        submit(engine, req)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness.Run, "attempted", keep)
        mp.setattr(VolumeEngine, "submit", note)
        r = harness.run_cell(cell, tiny.SEED, 2.0, False, hw=TPU_V5E, t_start=0.0)
    assert r["correct"] is True and r["failed"] == 0
    assert r["metrics"]["latency_p95_s"]["value"] > 0
    run = seen["run"]
    assert {q.stream for q in run.attempted()} == set(range(len(mix["streams"])))
    # each request reaches the engine with its stream's priority
    want = [s.get("priority", 0) for s in mix["streams"]]
    assert all(priorities[q.rid] == want[q.stream] for q in run.requests.values())


def _run_py(cwd, env=None):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "n337.bulk", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})),
    )


def test_run_py_refuses_without_a_tpu():
    out = _run_py(REPO)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs a TPU" in out.stderr


def test_run_py_refuses_without_the_system_under_test(tmp_path):
    bm = json.loads((REPO / "BENCHMARK.json").read_text())
    shutil.copy(REPO / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    for p in bm["paths"]:
        shutil.copytree(REPO / p, tmp_path / p, ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path, {"PYTHONPATH": ""})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "system under test is missing" in out.stderr
