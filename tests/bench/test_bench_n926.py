"""The n926 cell's configuration, checked on the CPU.

Its net is the paper's n926, its pinned plan is feasible on a v5e at the
cell's 250³ blocks, and the net's layer pattern and kernel sizes, served at
one hidden map through the volume service with the pinned prims, match the
plain reference (``bench/reference.py``) on a volume two patches long in x,
so that the second patch takes the strip path.
"""

import json
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(REPO), str(REPO / "src")]

from bench import counts, reference  # noqa: E402

CONFIG = json.loads((REPO / "bench" / "configs" / "n926.json").read_text())
TRAFFIC = json.loads((REPO / "bench" / "traffic" / "bulk250.json").read_text())


def _net(net: dict, maps: int = 0):
    """The configuration's net as a ``ConvNetConfig``; ``maps`` replaces the
    hidden layers' 80 feature maps."""
    from repro.configs.base import ConvLayerSpec, ConvNetConfig

    last = max(i for i, l in enumerate(net["layers"]) if l[0] == "conv")
    return ConvNetConfig(
        name=CONFIG["name"],
        in_channels=net["in_channels"],
        layers=tuple(
            ConvLayerSpec("conv", l[1], maps if maps and i != last else l[2])
            if l[0] == "conv" else ConvLayerSpec("pool", l[1])
            for i, l in enumerate(net["layers"])
        ),
    )


def test_net_is_table_iii_n926():
    from repro.configs.znni_nets import ZNNI_NETS

    assert _net(CONFIG["net"]) == ZNNI_NETS["n926"]
    assert counts.model_flops_per_voxel(CONFIG["net"]) == 37_756_640
    assert reference.field_of_view(CONFIG["net"]) == 155


def test_pinned_plan_is_feasible_on_a_v5e_at_the_cell_block():
    """m=12: core 48, so a 250³ block is 96³ of output in 2×2×2 patches,
    the 4 of the second x-plane on the strip path; the planner's memory
    model admits it on one v5e's 16 GB."""
    from repro.core import planner
    from repro.core.hw import TPU_V5E

    (shape,) = TRAFFIC["sizes"]
    p = CONFIG["plan"]
    plan = planner.plan_fixed(
        _net(CONFIG["net"]), TPU_V5E, p["prims"], m=p["m"], batch=p["batch"],
        volume_shape=tuple(shape), sweep_axis=0, strategy_name="bench",
    )
    assert plan is not None
    assert tuple(plan.prims) == tuple(p["prims"])
    assert (plan.core, plan.fov) == (48, 155)
    assert shape == [2 * plan.core + plan.fov - 1] * 3
    assert (plan.sweep.strip_patches, plan.sweep.full_patches) == (4, 4)
    assert plan.memory.device_bytes < TPU_V5E.hbm_bytes


def test_served_at_one_map_matches_the_reference():
    """Both patches through ``VolumeEngine`` (layer 0 by overlap-save, k=9
    direct convs, MPF pools), against the reference at HIGHEST.  On the
    CPU both compute in float32, the program with layer 0 through FFTs and
    its sums in another order: they differ by float32 rounding, about 1e-6
    of the output's scale, where a wrong tap, halo or fragment reads of
    order 1.  The tolerance, 1e-4, sits between."""
    from repro.serving import VolumeEngine, VolumeRequest

    net_d = dict(CONFIG["net"], layers=[
        l if l[0] == "pool" or l[2] == 3 else ["conv", l[1], 1] for l in CONFIG["net"]["layers"]
    ])
    params = reference.make_params(net_d, 3_000_000_019, CONFIG["bias_std"])
    eng = VolumeEngine(params, _net(net_d), prims=CONFIG["plan"]["prims"], m=1, batch=1,
                       tuned=None)
    fov, core = eng.executor.fov, eng.executor.core
    vol = np.random.default_rng(7).normal(
        size=(1, 2 * core + fov - 1, core + fov - 1, core + fov - 1)).astype(np.float32)
    req = VolumeRequest(0, vol)
    eng.submit(req)
    eng.run_until_drained()
    stats = eng.executor.last_stats
    assert (stats["deep_strip_patches"], stats["deep_full_patches"]) == (1, 1)
    want = reference.dense(params, net_d, vol, "highest")
    assert req.out.shape == want.shape == (3, 2 * core, core, core)
    assert reference.gap(req.out, want) < 1e-4


def test_work_ratio_reads_the_executor_counter():
    """``work_ratio.n926``: the window's dispatched conv FLOPs over the
    model's FLOPs for the voxels written, a tick that wrote nothing
    included; a program without the counter reads nothing."""
    from bench import harness

    reader = harness.load_metric("work_ratio.n926")
    assert reader.COUNTERS == {"conv_flops": "_conv_flops"}
    run = harness.Run(harness.load_cell("n926.bulk"), 1.0, t_open=0.0, t_close=3.0)
    per_vox = counts.model_flops_per_voxel(CONFIG["net"])
    run.ticks = [harness.Tick(0.0, 1.0, 1000, {"conv_flops": 11 * per_vox * 1000}, (0,)),
                 harness.Tick(1.0, 2.0, 0, {"conv_flops": per_vox * 1000}, (0,))]
    assert reader.read(run) == 12.0
    run.ticks = [harness.Tick(0.0, 1.0, 1000, {}, (0,))]
    assert reader.read(run) is None


def test_the_served_executor_keeps_every_counter_the_cell_reads():
    """The harness stops a run before warm-up where the executor lacks a
    counter the cell's readers name; this program keeps them all."""
    from bench import harness
    from repro.serving import VolumeEngine

    cell = harness.load_cell("n926.bulk")
    table = harness.counter_table(cell)
    assert table == {"conv_flops": "_conv_flops"}
    net_d = {"in_channels": 1, "layers": [["conv", 2, 2], ["pool", 2], ["conv", 3, 3]]}
    eng = VolumeEngine(reference.make_params(net_d, 1, 0.5), _net(net_d), m=1, batch=1,
                       prims=["overlap_save", "mpf", "direct"], tuned=None)
    harness.Client(eng, harness.Run(cell, 1.0), {}, harness._null_span, table)
