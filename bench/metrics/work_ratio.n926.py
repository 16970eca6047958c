"""Convolution work the executor dispatched per unit of model work: the
conv FLOPs of the steps in the window's ticks (the executor's own
counter, Σ 2·k³·f·f' × conv output voxels computed) over the net's FLOPs
per dense output voxel times the voxels those ticks wrote.  1 would mean
no patch recomputed any of its field of view; larger patches and the
strip path bring it down."""

from bench import counts

# last_stats name -> the PlanExecutor attribute it is published from
COUNTERS = {"conv_flops": "_conv_flops"}


def read(run):
    ticks = [t for t in run.window_ticks if "conv_flops" in t.counters]
    vox = sum(t.vox for t in ticks)
    if not vox:
        return None
    model = counts.model_flops_per_voxel(run.cell["config"]["net"]) * vox
    return sum(t.counters["conv_flops"] for t in ticks) / model
