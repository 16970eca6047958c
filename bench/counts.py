"""Operations and bytes, counted from shapes, and the peaks they are held to.

Every count here is what the algorithm needs for one call, worked out from
the operand shapes alone (float32 operands, 4 bytes an element):

* ``direct_conv3d``: x (S, nx, f, ny·nz) planes, w (k³, f', f), out
  (S, npx, f', npy·nz).  Each of the S·npx·npy·npz output voxels is a
  k³·f-term dot per output channel: 2·k³·f·f'·S·npx·npy·npz operations.
* ``os_segment``: spectra (N, Q, f, A, B, C) real and imaginary, kernel
  spectra (f', f, A, B, C) likewise, inverse matrices ea (A, s), eb (oy, B),
  m (C, oz), out (N, Q, f', s, oy, oz).  Per segment and a-bin: the complex
  MAD (8 operations per f'·f·B·C term), the c-axis inverse (4 real GEMMs
  of f'·B × C × oz), the b-axis inverse (4 batched GEMMs of f'·oy × B × oz)
  and the a-axis term (4 operations per s·f'·oy·oz output).

Bytes are each operand read once and the output written once: the least
traffic the call can have.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

F32 = 4
PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def peaks_for(device_kind: str) -> dict:
    """The published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    try:
        return table[device_kind]
    except KeyError:
        raise ValueError(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        ) from None


def model_flops_per_voxel(net: dict) -> int:
    """Σ 2·k³·f·f' over the convs of a configuration's ``net``: its
    arithmetic per dense output voxel when no work is repeated (MPF adds
    none)."""
    total, f = 0, net["in_channels"]
    for layer in net["layers"]:
        if layer[0] == "conv":
            k, fo = layer[1], layer[2]
            total += 2 * k**3 * f * fo
            f = fo
    return total


def direct_conv3d(operands, out):
    """(ops, bytes) of one ``direct_conv3d`` call.

    ``operands`` are the pallas_call's operand shapes (x, x again for the
    halo view, w); ``out`` its output shape.  The kernel also computes
    ``k - 1`` wrap columns per output row and crops them afterwards; they
    are waste, not work the convolution needs, so they are not counted.
    """
    x, w = operands[0], operands[-1]
    S, _, f, lx = x
    k3, fp, f_w = w
    k = round(k3 ** (1 / 3))
    _, npx, fp_o, lo = out
    if f_w != f or fp_o != fp or k**3 != k3:
        raise ValueError(f"not a direct_conv3d call: x {x}, w {w}, out {out}")
    if k > 1:
        nz = (lx - lo) // (k - 1)  # x rows hold ny·nz, out rows (ny-k+1)·nz
        voxels = S * npx * (lo // nz) * (nz - k + 1)
    else:
        voxels = S * npx * lo
    ops = 2 * k3 * f * fp * voxels
    nbytes = F32 * (math.prod(x) + math.prod(w) + math.prod(out))
    return ops, nbytes


def os_segment(operands, out):
    """(ops, bytes) of one ``os_segment`` call.

    Operands in call order: fr, fi, wr, wi, nb, ear, eai, ebr, ebi, mr, mi.
    """
    fr, _, wr = operands[0], operands[1], operands[2]
    ear, ebr, mr = operands[5], operands[7], operands[9]
    N, Q, f, A, B, C = fr
    fp = wr[0]
    s = ear[1]
    oy = ebr[0]
    oz = mr[1]
    if tuple(out) != (N, Q, fp, s, oy, oz):
        raise ValueError(f"not an os_segment call: {operands} -> {out}")
    per_bin = (
        8 * fp * f * B * C  # complex MAD
        + 4 * 2 * fp * B * C * oz  # inverse c: four real GEMMs
        + 4 * 2 * fp * oy * B * oz  # inverse b: four batched GEMMs
        + 4 * s * fp * oy * oz  # inverse a: this bin's term
    )
    ops = N * Q * A * per_bin
    nbytes = F32 * (sum(math.prod(o) for o in operands) + math.prod(out))
    return ops, nbytes

