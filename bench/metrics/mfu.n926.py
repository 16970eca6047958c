"""The whole step's share of the chip's peak in the n926 cell: the net's
FLOPs per dense output voxel (Σ 2·k³·f·f') times the traced window's
vox/s, over the peak FLOP/s of the chips the cell uses."""

from bench import counts


def read(run):
    ticks = run.window_ticks
    if not ticks:
        return None
    voxps = sum(t.vox for t in ticks) / (run.t_close - run.t_open)
    flops = counts.model_flops_per_voxel(run.cell["config"]["net"]) * voxps
    peak = counts.peaks_for(run.device_kind)["flops_per_s"] * run.cell["chips"]
    return 100.0 * flops / peak
