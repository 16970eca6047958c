"""bench/trace.py on a small synthetic trace, with answers worked by hand.

The trace (times in ns; TPU events carry their HLO text as their name):

    host  python3: submit [0,400)  step [400,4800)  wait [4800,8000)
                   step [8000,10000)   (and an unrelated span [0,20000))
    TPU:0 XLA Ops: direct_conv3d [1000,3000)  fusion [2000,4000)
                   os_segment [6000,7000)     fusion [9500,10500)
    TPU:1 XLA Ops: fusion [0,1000)

The window is the harness's spans, [0,10000).  TPU:0 is busy on
[1000,4000) ∪ [6000,7000) ∪ [9500,10000) = 4500 ns, TPU:1 on 1000 ns;
busy time is their mean, 2750 ns.  TPU:0's idle gaps are [0,1000) (600 ns
of it in step), [4000,6000) (1200 in wait) and [7000,9500) (1500 in step).
"""

import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import counts, metrics_common, trace  # noqa: E402

CONV = (
    '%direct_conv3d.2 = f32[1,2,8,8]{3,2,1,0:T(8,128)} custom-call('
    'f32[1,4,8,16]{3,2,1,0:T(8,128)} %a, f32[1,4,8,16]{3,2,1,0:T(8,128)} %a, '
    'f32[27,8,8]{2,1,0} %w), custom_call_target=\\"tpu_custom_call\\"'
)
SEG = (
    '%os_segment.1 = f32[1,1,8,4,8,128]{5,4,3,2,1,0:T(8,128)} custom-call('
    'f32[1,1,8,1,8,128]{5,4,3,2,1,0} %fr, f32[1,1,8,1,8,128]{5,4,3,2,1,0} %fi, '
    'f32[8,8,1,8,128]{4,3,2,1,0} %wr, f32[8,8,1,8,128]{4,3,2,1,0} %wi, '
    'f32[8,1,1]{2,1,0} %nb, f32[1,4]{1,0} %ear, f32[1,4]{1,0} %eai, '
    'f32[8,8]{1,0} %ebr, f32[8,8]{1,0} %ebi, f32[128,128]{1,0} %mr, '
    'f32[128,128]{1,0} %mi), custom_call_target=\\"tpu_custom_call\\"'
)


def _plane(pid, name, line, events, names):
    evs = "\n".join(
        f"    events {{ metadata_id: {m} offset_ps: {a * 1000} duration_ps: {(b - a) * 1000} }}"
        for m, a, b in events
    )
    meta = "\n".join(
        f'  event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}'
        for i, n in names.items()
    )
    return (f'planes {{\n  id: {pid}\n  name: "{name}"\n  lines {{\n    id: 1\n'
            f'    name: "{line}"\n    timestamp_ns: 0\n{evs}\n  }}\n{meta}\n}}\n')


def _profile():
    from jax.profiler import ProfileData

    fusion = "%fusion.3 = f32[4]{0} fusion(f32[4]{0} %p), kind=kLoop"
    text = (
        _plane(1, "/device:TPU:0", "XLA Ops",
               [(1, 1000, 3000), (2, 2000, 4000), (3, 6000, 7000), (2, 9500, 10500)],
               {1: CONV, 2: fusion, 3: SEG})
        + _plane(2, "/device:TPU:1", "XLA Ops", [(1, 0, 1000)], {1: fusion})
        + _plane(3, "/host:CPU", "python3",
                 [(1, 0, 400), (2, 400, 4800), (3, 4800, 8000), (2, 8000, 10000),
                  (4, 0, 20000)],
                 {1: "submit", 2: "step", 3: "wait", 4: "other"})
    )
    return ProfileData.from_text_proto(text)


def test_busy_window_and_idle_gaps():
    r = trace.reduce(_profile())
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(2750e-9)
    gaps = r["breakdown"]["idle_gaps"]
    assert [g[0] for g in gaps] == ["step", "wait", "step"]
    assert [g[1] for g in gaps] == pytest.approx([2500e-9, 2000e-9, 1000e-9])


def test_device_ops_by_name():
    ops = trace.reduce(_profile())["breakdown"]["device_ops"]
    assert [o[0] for o in ops] == ["fusion", "direct_conv3d", "os_segment"]
    # fusion: 2000 on TPU:0, 500 of [9500,10500) inside the window, 1000 on TPU:1
    assert [o[1] for o in ops] == pytest.approx([3500e-9, 2000e-9, 1000e-9])


def test_kernel_calls_carry_their_shapes():
    k = trace.reduce(_profile())["kernels"]
    assert set(k) == {"direct_conv3d", "os_segment"}  # the Pallas calls, not the fusions
    (dur, operands, out), = k["direct_conv3d"]
    assert dur == pytest.approx(2000e-9)
    assert operands == [(1, 4, 8, 16), (1, 4, 8, 16), (27, 8, 8)]
    assert out == (1, 2, 8, 8)
    (dur, operands, out), = k["os_segment"]
    assert len(operands) == 11 and out == (1, 1, 8, 4, 8, 128)


def test_roofline_of_the_traced_conv():
    """x (1, 4, 8, 4·4), out (1, 2, 8, 2·4), k=3: 1·2·2·2 output voxels, so
    ops = 2·27·8·8·8 = 27648; bytes = 4·(512 + 1728 + 128) = 9472; bytes
    bound it: 9472 / 819e9 s over 2000 ns of device time."""

    class R:
        device_kind = "TPU v5 lite"
        trace = trace.reduce(_profile())

    share = metrics_common.roofline(R, "direct_conv3d", counts.direct_conv3d)
    assert share == pytest.approx(100 * (9472 / 819e9) / 2000e-9)
    assert metrics_common.roofline(R, "mpf_window_max", counts.direct_conv3d) is None


def test_hlo_shapes_of_tuple_and_layouts():
    assert trace.op_name(CONV.replace('\\"', '"')) == "direct_conv3d"
    assert trace.op_name("jit__os_step(123)") == "jit__os_step(123)"
    ops, out = trace.hlo_shapes(
        "%f.1 = (s32[8,1]{0,1:T(1,128)}, s32[8]{0}) fusion(s32[8,3]{0,1:T(4,128)} %s)"
    )
    assert ops == [(8, 3)] and out == (8, 1)
