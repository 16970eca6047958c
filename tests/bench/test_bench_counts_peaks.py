"""The benchmark's yardstick: model FLOPs per voxel, kernel counts, peaks."""

import json
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(REPO))

from bench import counts  # noqa: E402


def _net(name):
    if name == "n337":
        return json.loads((REPO / "bench" / "configs" / "n337.json").read_text())["net"]
    # n926 (CPCPCCCC, kernels 8,9,9,9,9,9, 80 maps, 3 outputs), which no cell
    # runs yet: the program's direct_conv3d cannot compile it on a v5e
    convs = [["conv", 8, 80], ["pool", 2], ["conv", 9, 80], ["pool", 2],
             ["conv", 9, 80], ["conv", 9, 80], ["conv", 9, 80], ["conv", 9, 3]]
    return {"in_channels": 1, "layers": convs}


@pytest.mark.parametrize("name,flops", [("n337", 1_742_240), ("n926", 37_756_640)])
def test_model_flops_per_voxel_pinned(name, flops):
    """Σ 2·k³·f·f' over the convs of each Table III net at 80 maps."""
    assert counts.model_flops_per_voxel(_net(name)) == flops


def test_direct_conv3d_hand_count():
    """One n337 L4 call: 128 fragments of 27³ planes, 80 -> 80 maps, k=3.

    Planes (S, nx, f, ny·nz) = (128, 27, 80, 729); w (27, 80, 80); out
    (128, 25, 80, 25·27).  The call writes 128·25·25·25 output voxels (the
    two wrap columns of each row are cropped, not counted), each a 27·80-
    term dot per output map: 2·27·80·80·128·25·25·25 operations; bytes are
    x, w and out once, 4 bytes each.
    """
    x = (128, 27, 80, 729)
    w = (27, 80, 80)
    out = (128, 25, 80, 675)
    ops, nbytes = counts.direct_conv3d([x, x, w], out)
    assert ops == 2 * 27 * 80 * 80 * 128 * 25 * 25 * 25
    assert ops == 691_200_000_000
    assert nbytes == 4 * (128 * 27 * 80 * 729 + 27 * 80 * 80 + 128 * 25 * 80 * 675)
    with pytest.raises(ValueError, match="not a direct_conv3d call"):
        counts.direct_conv3d([x, x, (27, 80, 64)], out)


def test_os_segment_count_from_shapes():
    """A one-segment, one-bin call: every term of the count by hand."""
    N, Q, f, A, B, C = 1, 1, 8, 1, 8, 128
    fp, s, oy, oz = 8, 4, 8, 128
    ops_ = [(N, Q, f, A, B, C)] * 2 + [(fp, f, A, B, C)] * 2 + [(fp, 1, 1)]
    ops_ += [(A, s)] * 2 + [(oy, B)] * 2 + [(C, oz)] * 2
    ops, nbytes = counts.os_segment(ops_, (N, Q, fp, s, oy, oz))
    mad = 8 * fp * f * B * C
    inv_c = 8 * fp * B * C * oz
    inv_b = 8 * fp * oy * B * oz
    inv_a = 4 * s * fp * oy * oz
    assert ops == mad + inv_c + inv_b + inv_a
    assert nbytes == 4 * (2 * 8 * 8 * 128 + 2 * 8 * 8 * 8 * 128 + 8 + 2 * 4 + 2 * 64
                          + 2 * 128 * 128 + 8 * 4 * 8 * 128)


def test_v5e_peaks_and_unknown_kind_raises():
    p = counts.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(ValueError, match="no peaks for device kind"):
        counts.peaks_for("TPU v9 imaginary")


def test_mfu_ceiling_rates():
    """vox/s at which mfu would read 105% of the v5e's peak: the rates a
    plan must stay under before the count needs recounting."""
    peak = counts.peaks_for("TPU v5 lite")["flops_per_s"]
    for name, ceiling in (("n337", 118.7e6), ("n926", 5.478e6)):
        rate = 1.05 * peak / counts.model_flops_per_voxel(_net(name))
        assert rate == pytest.approx(ceiling, rel=1e-3)
