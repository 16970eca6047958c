"""Jitted wrapper for direct 3D conv: lays out planes, sizes tiles, dispatches."""

from __future__ import annotations

import time
from functools import partial

import jax
import jax.numpy as jnp

from ... import obs
from ..dispatch import resolve_use_pallas
from . import kernel as _k
from . import ref as _ref

FP_TILE = 128  # output channels per block when f' exceeds one MXU tile
VMEM_BUDGET = 40 * 2**20  # what the tile picker lets one grid step hold
MAX_TAPS = 81  # taps in one grid step's weight block: kd·k²
MAX_BODY = 700  # unrolled dots × 128-lane chunks one row body holds
MIN_CHUNKS = 4  # 128-lane chunks of a lane block short of the whole plane
TX_MAX = 8  # output planes a grid step computes


def _cdiv(n: int, m: int) -> int:
    return -(-n // m)


def _pick_blocking(npx: int, k: int, f: int, fpb: int, nz: int, L: int, lo: int):
    """(tx, kd, lb, vmem): output planes, dx taps and output lanes of one
    grid step, and the scoped VMEM it holds.

    ``kd`` is the largest divisor of k whose ``kd·k²`` taps stay within
    MAX_TAPS.  A row body unrolls its taps over every 128-lane chunk of its
    lanes, so a block holds at most ``MAX_BODY // taps`` chunks: the whole
    plane where it fits, else the block count under that cap, with blocks
    of at least MIN_CHUNKS, that computes the fewest lanes (the fewest
    blocks among equals).  Then the most planes (at most TX_MAX) that fit
    VMEM_BUDGET; lane blocks halve until one plane fits.
    """
    kd = max(d for d in range(1, k + 1) if k % d == 0 and d * k * k <= MAX_TAPS)
    chunks, per = _cdiv(lo, 128), max(1, MAX_BODY // (kd * k * k))
    if chunks <= per:
        lb = lo
    else:
        fewest = _cdiv(chunks, per)
        n_b = min(range(fewest, max(fewest, chunks // MIN_CHUNKS) + 1),
                  key=lambda n: (n * _cdiv(chunks, n), n))
        lb = 128 * _cdiv(chunks, n_b)
    while True:
        for tx in range(min(TX_MAX, npx), 0, -1):
            vmem = _k.plan_vmem_bytes(k=k, kd=kd, tx=tx, f=f, fpb=fpb, nz=nz, L=L, lb=lb)
            if vmem <= VMEM_BUDGET:
                return tx, kd, lb, vmem
        if lb <= 128:
            return 1, kd, lb, vmem
        lb = 128 * _cdiv(_cdiv(min(lb, lo), 2), 128)


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
    tx, kd, lb, vmem = _pick_blocking(npx, k, f, fpb, nz, ny * nz, npy * nz)
    now = time.perf_counter()
    obs.mark(
        "direct_conv3d.tiling", now, now, k=k, S=S, f=f, fp=fp, n=(nx, ny, nz), tx=tx,
        lanes=min(lb, npy * nz), dx_steps=k // kd, vmem_bytes=vmem,
    )
    # x-major planes, (y, z) flattened onto lanes
    xt = jnp.transpose(x.astype(jnp.float32), (0, 2, 1, 3, 4)).reshape(
        S, nx, f, ny * nz
    )
    o = _k.conv3d_planes(
        xt, wt, k=k, nz=nz, tx=tx, kd=kd, lb=lb, fpb=fpb, interpret=interpret
    )  # (S, npx, f'', npy·nz)
    o = o[:, :, :fp].reshape(S, npx, fp, npy, nz)[..., :npz]
    return jnp.transpose(o, (0, 2, 1, 3, 4))
