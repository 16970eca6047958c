"""The readers of the program's spans: ``host_share.*`` and ``queue_wait_p95_s``.

Each reads ``repro.obs`` over a run's window.  Hand-worked spans give
hand-worked answers; a window the recorder holds nothing for, a recorder
that dropped what the window needs, or a program with no recorder at all
reads None, so the readers run against a program that lacks the spans.
A tiny cell on the CPU shows them reading the spans a real run records.
"""

import sys

import pytest

import bench_tiny_cell as tiny
from bench import harness

READERS = ("host_share.bulk", "host_share.roi", "queue_wait_p95_s")


def _run(requests=()):
    """A window [10, 20) with requests (rid, due) due in it or after it."""
    run = harness.Run(cell={}, seconds=10.0, t_open=10.0, t_close=20.0)
    for rid, due in requests:
        run.requests[rid] = harness.Request(rid, 0, due, due)
    return run


@pytest.fixture
def recorder(monkeypatch):
    from repro import obs

    rec = obs.Recorder()
    monkeypatch.setattr(obs, "spans", rec.spans)
    return rec


def test_bench_lists_each_reader_for_its_cell():
    for cell, names in (("n337.bulk", {"host_share.bulk"}),
                        ("n337.roi", {"host_share.roi", "queue_wait_p95_s"})):
        listed = {m["name"] for m in harness.load_cell(cell)["per_layer"]}
        assert names <= listed and not (set(READERS) - names) & listed
    for name in READERS:
        assert callable(harness.load_metric(name).read)


@pytest.mark.parametrize("name", ["host_share.bulk", "host_share.roi"])
def test_host_share_by_hand(recorder, name):
    m = recorder.mark
    m("engine.tick", 8.0, 9.0)  # before the window
    m("engine.tick", 10.5, 12.5)
    m("executor.fetch", 11.0, 12.0)
    m("engine.submit", 12.6, 12.8)
    m("engine.schedule", 12.9, 13.0)  # inside no tick here, and never counted
    m("engine.tick", 19.0, 21.0)  # clipped to [19, 20]
    m("executor.fetch", 19.5, 20.5)  # clipped to [19.5, 20]
    # host: 2 + 0.2 + 1 = 3.2 s; waiting on the device: 1 + 0.5 = 1.5 s
    assert harness.load_metric(name).read(_run()) == pytest.approx(100.0 * 1.7 / 10.0)


def test_queue_wait_p95_by_hand(recorder):
    m = recorder.mark
    m("engine.queued", 4.0, 9.0, rid=0)  # an earlier run's rid 0, before the window
    m("engine.queued", 9.5, 9.9, rid=-1)  # a warm-up request
    for rid, due, wait in ((0, 10.0, 0.1), (1, 11.0, 0.8), (2, 12.0, 0.2), (3, 19.0, 0.4),
                           (4, 21.0, 3.0)):  # rid 4 falls due after the window
        m("engine.queued", due + 0.01, due + 0.01 + wait, rid=rid)
    run = _run([(0, 10.0), (1, 11.0), (2, 12.0), (3, 19.0), (4, 21.0), (5, 15.0)])
    # rid 5 was never popped: no mark, left out; the inclusive 95th
    # percentile of [0.1, 0.2, 0.4, 0.8] is 0.4 + 0.85 * 0.4
    assert harness.load_metric("queue_wait_p95_s").read(run) == pytest.approx(0.74)


def test_nothing_recorded_reads_none(recorder):
    run = _run([(0, 11.0)])
    recorder.mark("engine.tick", 1.0, 2.0)
    recorder.mark("engine.queued", 1.0, 2.0, rid=0)
    assert [harness.load_metric(n).read(run) for n in READERS] == [None] * 3


def test_a_ring_that_dropped_the_window_reads_none(monkeypatch):
    from repro import obs

    monkeypatch.setattr(obs, "RING", 2)
    rec = obs.Recorder()
    monkeypatch.setattr(obs, "spans", rec.spans)
    rec.mark("engine.queued", 10.1, 10.5, rid=0)  # falls out of the ring
    rec.mark("engine.tick", 11.0, 12.0)
    rec.mark("engine.queued", 12.0, 12.5, rid=1)
    run = _run([(0, 10.0), (1, 11.9)])
    assert [harness.load_metric(n).read(run) for n in READERS] == [None] * 3


def test_a_program_without_spans_reads_none(recorder, monkeypatch):
    import repro

    recorder.mark("engine.tick", 11.0, 12.0)
    recorder.mark("engine.queued", 11.0, 12.0, rid=0)
    run = _run([(0, 11.0)])
    assert None not in [harness.load_metric(n).read(run) for n in READERS]
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # the import fails, as at the parent
    assert [harness.load_metric(n).read(run) for n in READERS] == [None] * 3


@pytest.mark.parametrize("name", ["n337.bulk", "n337.roi"])
def test_readers_read_a_tiny_run(monkeypatch, name):
    seen = {}
    attempted = harness.Run.attempted

    def keep(run):
        seen["run"] = run
        return attempted(run)

    monkeypatch.setattr(harness.Run, "attempted", keep)
    tiny.run(name, seconds=1.0)
    run = seen["run"]
    share = harness.load_metric(f"host_share.{name.split('.')[1]}").read(run)
    assert 0 < share <= 100
    if name == "n337.roi":
        wait = harness.load_metric("queue_wait_p95_s").read(run)
        latency = harness.load_metric("latency_p95_s").read(run)
        assert 0 <= wait < latency
