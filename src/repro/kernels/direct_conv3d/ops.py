"""Jitted wrapper for direct 3D conv: lays out planes, sizes tiles, dispatches."""

from __future__ import annotations

import time
from functools import partial
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ... import obs
from ..dispatch import resolve_use_pallas
from . import kernel as _k
from . import ref as _ref

FP_TILE = 128  # output channels per block when f' exceeds one MXU tile
VMEM_BUDGET = 40 * 2**20  # what the tile picker lets one grid step hold
MAX_TAPS = 81  # taps in one grid step's weight block: kd·k²
MAX_BODY = 768  # MXU passes × 128-lane chunks one row body unrolls
TX_MAX = 8  # output planes a grid step computes


class Blocking(NamedTuple):
    tx: int  # output planes of one grid step
    kd: int  # dx taps of one grid step
    kz: int  # dz taps stacked along one dot's contraction
    lb: int  # output lanes of one grid step
    stage: Any  # dtype the staged planes and taps are rounded to
    vmem: int  # scoped VMEM bytes one grid step holds


def _cdiv(n: int, m: int) -> int:
    return -(-n // m)


def stage_dtype(f: int, interpret: bool):
    """What the staged operands are rounded to: the rounding the dot itself
    applies.  The compiled kernel's default-precision dot multiplies in one
    bfloat16 pass, so it stages bfloat16 where f fills its packed (16, 128)
    tiles; the interpreter's dot is exact, so it stages float32."""
    return jnp.bfloat16 if not interpret and f % 16 == 0 else jnp.float32


def _taps_per_dot(k: int, f: int, fpb: int, lo: int, stage) -> int:
    """The dz taps one dot stacks: the fewest whose contraction fills its
    128-row MXU passes best (3 at f=80: 240 of 256 rows, against 80 of 128
    alone), since each stacked tap adds an unaligned shift to every staged
    plane.  One where a stacked tap's rows would land off the staged
    dtype's sublane tiles, and where a row's dots are too small for the
    passes saved to repay the shifts: under 8 output channels, or a row of
    one 128-lane chunk (measured on a v5e, ``PERF.md``)."""
    if f % (32 // jnp.dtype(stage).itemsize) or fpb < 8 or lo <= 128:
        return 1
    fill = lambda kz: kz * f / (128 * _cdiv(kz * f, 128))
    return max((kz for kz in range(1, k + 1) if k % kz == 0), key=lambda kz: (fill(kz), -kz))


def _pick_blocking(npx: int, k: int, f: int, fpb: int, nz: int, L: int, lo: int,
                   stage) -> Blocking:
    """Output planes, dx taps, stacked dz taps and output lanes of one grid
    step, and the scoped VMEM it holds.

    ``kz`` is ``_taps_per_dot``.  ``kd`` is the largest divisor of k whose
    ``kd·k²`` taps stay within MAX_TAPS.  A row body unrolls its
    ``kd·k·(k/kz)`` dots, each ``⌈kz·f/128⌉`` MXU passes deep, over every
    128-lane chunk of its lanes, so a block holds at most MAX_BODY passes:
    the whole plane where it fits, else the block count under that cap that
    stages the fewest window lanes (each block re-reads the
    ``(k-1)·(nz+1)``-lane halo).  Then, of the x tiles of at most TX_MAX
    planes that fit VMEM_BUDGET, the one that computes the fewest padded
    planes past ``npx``, the larger among equals; lane blocks halve until
    one plane fits.
    """
    kz = _taps_per_dot(k, f, fpb, lo, stage)
    kd = max(d for d in range(1, k + 1) if k % d == 0 and d * k * k <= MAX_TAPS)
    passes = kd * k * (k // kz) * _cdiv(kz * f, 128)
    chunks, per = _cdiv(lo, 128), max(1, MAX_BODY // passes)
    if chunks <= per:
        lb = lo
    else:
        def staged(n):
            n_b, _, _, window = _k.lane_geometry(k, nz, L, 128 * _cdiv(chunks, n))
            return n_b * window, n_b

        lb = 128 * _cdiv(chunks, min(range(_cdiv(chunks, per), chunks + 1), key=staged))

    def vmem(tx):
        return _k.plan_vmem_bytes(k=k, kd=kd, kz=kz, tx=tx, f=f, fpb=fpb, nz=nz, L=L,
                                  lb=lb, stage=stage)

    while True:
        fits = [tx for tx in range(1, min(TX_MAX, npx) + 1) if vmem(tx) <= VMEM_BUDGET]
        if fits or lb <= 128:
            break
        lb = 128 * _cdiv(_cdiv(min(lb, lo), 2), 128)
    tx = min(fits, key=lambda t: (_cdiv(npx, t) * t, -t), default=1)
    return Blocking(tx, kd, kz, lb, stage, vmem(tx))


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def conv3d(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    use_pallas: bool | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """'valid' cross-correlation; see ref.py for semantics."""
    if not resolve_use_pallas(use_pallas):
        return _ref.conv3d(x, w)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    S, f, nx, ny, nz = x.shape
    fp, _, k = w.shape[:3]
    npx, npy, npz = nx - k + 1, ny - k + 1, nz - k + 1
    fpb = fp if fp <= FP_TILE else FP_TILE
    padF = (-fp) % fpb
    # (f', f, kx, ky, kz) -> (k³, f', f): one (f'×f) matrix per tap
    wt = jnp.transpose(w.astype(jnp.float32), (2, 3, 4, 0, 1)).reshape(k**3, fp, f)
    if padF:
        wt = jnp.pad(wt, ((0, 0), (0, padF), (0, 0)))
    b = _pick_blocking(npx, k, f, fpb, nz, ny * nz, npy * nz, stage_dtype(f, interpret))
    now = time.perf_counter()
    obs.mark(
        "direct_conv3d.tiling", now, now, k=k, S=S, f=f, fp=fp, n=(nx, ny, nz), tx=b.tx,
        lanes=min(b.lb, npy * nz), dx_steps=k // b.kd, taps_per_dot=b.kz,
        contract=b.kz * f, stage=jnp.dtype(b.stage).name, vmem_bytes=b.vmem,
    )
    # x-major planes, (y, z) flattened onto lanes
    xt = jnp.transpose(x.astype(jnp.float32), (0, 2, 1, 3, 4)).reshape(
        S, nx, f, ny * nz
    )
    o = _k.conv3d_planes(
        xt, wt, k=k, nz=nz, tx=b.tx, kd=b.kd, kz=b.kz, lb=b.lb, fpb=fpb, stage=b.stage,
        interpret=interpret,
    )  # (S, npx, f'', npy·nz)
    o = o[:, :, :fp].reshape(S, npx, fp, npy, nz)[..., :npz]
    return jnp.transpose(o, (0, 2, 1, 3, 4))
