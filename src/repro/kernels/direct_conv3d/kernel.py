"""Pallas TPU kernel: direct 3D conv as k³ offset-shifted matmuls.

TPU-native formulation (DESIGN.md §3): the channel dimension is the MXU
contraction.  For each kernel offset (dx,dy,dz):

    O[j, x,y,z] += W[j, i, dx,dy,dz] @ I[i, x+dx, y+dy, z+dz]

Layout: the wrapper hands the kernel x-major planes with (y, z) already
flattened onto the lane axis, ``(S, nx, f, ny·nz)``.  In that layout the
(dy, dz) offset is a static lane shift of ``dy·nz + dz``, so every tap is
one ``(f'_blk × f) @ (f × lanes)`` matmul of an unaligned lane slice — no
in-kernel reshape merges tiled dims.  Flat positions whose z lies in the
last k-1 columns of a row wrap into the next row; they are computed and
cropped by the wrapper (``ops.py``).

Blocking: grid over (batch, f' blocks, x-tiles, lane blocks, dx steps).
A step reads one element-indexed window of the planes: the ``tx`` output
rows' planes plus the ``kd - 1`` more its ``kd`` dx taps reach, and the
lane block plus the ``(k-1)·(nz+1)``-lane halo its (dy, dz) taps reach.
The window's edges past the array are padding whose values reach only the
cropped outputs.  The dx steps are a reduction axis: the weight block is
the ``kd·k²`` taps of one step, and the output block, revisited across
them, is the accumulator.  So neither the resident weights nor the staged
planes grow with k³ or with the whole (y, z) plane, and the unrolled row
body holds ``kd·k²`` dots.  With ``kd = k`` and one lane block holding the
whole plane this is a single pass over whole planes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

VMEM_LIMIT = 64 * 2**20  # scoped VMEM the tile sizing in ops.py budgets for


def lane_geometry(k: int, nz: int, L: int, lb: int):
    """(n_b, lb, lw, window) of planes of ``L = ny·nz`` lanes cut into output
    lane blocks of ``lb``: the block count, the block, the lanes a row
    computes in it, and the input lanes a block reads."""
    lo = L - (k - 1) * nz  # npy·nz
    if lb >= lo:
        # one lane block, the whole plane; its last k-1 lanes' taps would
        # read past it, and they are wrap-around columns the wrapper crops
        return 1, lo, lo - (k - 1), L
    window = lb + (k - 1) * (nz + 1)
    return -(-lo // lb), lb, lb, window + (-window) % 128


def _kernel(x_ref, w_ref, o_ref, *, k: int, kd: int, tx: int, nz: int, lw: int):
    # x_ref (tx+kd-1, f, window) planes; w_ref (kd·k², fpb, f) this step's
    # taps; o_ref (1, tx, fpb, lanes) the accumulator over the dx steps.
    first = pl.program_id(4) == 0

    def row(r, carry):
        acc = None
        for dx in range(kd):
            for dy in range(k):
                for dz in range(k):
                    xs = x_ref[r + dx, :, pl.ds(dy * nz + dz, lw)]
                    t = jnp.dot(
                        w_ref[(dx * k + dy) * k + dz], xs,
                        preferred_element_type=jnp.float32,
                    )
                    acc = t if acc is None else acc + t

        @pl.when(first)
        def _():
            o_ref[0, r, :, pl.ds(0, lw)] = acc

        @pl.when(jnp.logical_not(first))
        def _():
            o_ref[0, r, :, pl.ds(0, lw)] += acc

        return carry

    jax.lax.fori_loop(0, tx, row, 0)


def plan_vmem_bytes(*, k: int, kd: int, tx: int, f: int, fpb: int, nz: int, L: int,
                    lb: int) -> int:
    """Scoped VMEM one grid step holds: the double-buffered plane window,
    weight block and output block, and a row's slice, products and sum."""
    _, lb, lw, window = lane_geometry(k, nz, L, lb)

    def tile(rows, cols):
        return (rows + (-rows) % 8) * (cols + (-cols) % 128) * 4

    return (
        2 * (tx + kd - 1) * tile(f, window)
        + 2 * kd * k * k * tile(fpb, f)
        + 2 * tx * tile(fpb, lb)
        + tile(f, lw) + 3 * tile(fpb, lw)
    )


@functools.partial(
    jax.jit, static_argnames=("k", "nz", "tx", "kd", "lb", "fpb", "interpret")
)
def conv3d_planes(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    k: int,
    nz: int,
    tx: int,
    kd: int,
    lb: int,
    fpb: int,
    interpret: bool = True,
) -> jnp.ndarray:
    """x (S, nx, f, ny·nz) f32, w (k³, f', f) f32 -> (S, npx, f', npy·nz).

    ``kd`` divides k: the dx taps one grid step applies.  ``lb`` is the
    output lanes a step writes: the whole ``npy·nz`` plane, or a multiple
    of 128 below it.  ``fpb`` divides f' and is f' or a multiple of 8.
    Output lanes past ``npy·nz - (k-1)`` and z-columns past ``npz`` hold
    wrap-around garbage (ops.py crops them).
    """
    S, nx, f, L = x.shape
    fp = w.shape[1]
    npx = nx - k + 1
    n_b, lb, lw, window = lane_geometry(k, nz, L, lb)
    n_t = pl.cdiv(npx, tx)
    pad_x = n_t * tx - npx
    pad_l = max(0, (n_b - 1) * lb + window - L)
    grid = (S, fp // fpb, n_t, n_b, k // kd)
    x_spec = pl.BlockSpec(
        (
            pl.squeezed,
            pl.Element(tx + kd - 1, padding=(0, pad_x)),
            pl.Element(f),
            pl.Element(window, padding=(0, pad_l)),
        ),
        # the lane offset is a multiple of 128 the compiler must see as one
        lambda s, j, t, b, d: (s, t * tx + d * kd, 0, b * lb if n_b > 1 else 0),
    )
    w_spec = pl.BlockSpec((kd * k * k, fpb, f), lambda s, j, t, b, d: (d, j, 0))
    o_spec = pl.BlockSpec((1, tx, fpb, lb), lambda s, j, t, b, d: (s, t, j, b))
    return pl.pallas_call(
        functools.partial(_kernel, k=k, kd=kd, tx=tx, nz=nz, lw=lw),
        grid=grid,
        name="direct_conv3d",
        in_specs=[x_spec, w_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((S, npx, fp, L - (k - 1) * nz), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 4 + ("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, w)
