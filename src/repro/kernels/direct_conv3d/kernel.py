"""Pallas TPU kernel: direct 3D conv as shifted matmuls, its dz taps
stacked along the contraction.

TPU-native formulation (DESIGN.md §3): the channel dimension is the MXU
contraction.  For each kernel offset (dx,dy,dz):

    O[j, x,y,z] += W[j, i, dx,dy,dz] @ I[i, x+dx, y+dy, z+dz]

Layout: the wrapper hands the kernel x-major planes with (y, z) already
flattened onto the lane axis, ``(S, nx, f, ny·nz)``.  In that layout the
(dy, dz) offset is a static lane shift of ``dy·nz + dz``.  Flat positions
whose z lies in the last k-1 columns of a row wrap into the next row; they
are computed and cropped by the wrapper (``ops.py``).

Stacked contraction: ``kz`` consecutive dz taps (a divisor of k) share one dot.
Each input plane is staged once per grid step into VMEM as

    X[j·f + i, l] = x[i, l + j]        j = 0..kz-1

rounded once to the staged dtype, and the step's weight block is restacked
as ``W[(dx,dy,g)] (f'_blk × kz·f)``, column ``j·f + i`` holding tap
``dz = g·kz + j``.  A row is then ``Σ W[(dx,dy,g)] @ X[x+dx][:, dy·nz+g·kz
: +lanes]``: ``kd·k·(k/kz)`` dots of contraction ``kz·f`` instead of
``kd·k²`` of contraction f, and as many f32 accumulates.  The unaligned dz
shifts and the rounding are paid once per staged plane, not once per tap.

Blocking: grid over (f' blocks, batch, x-tiles, lane blocks, dx steps).
A step reads one element-indexed window of the planes: the ``tx`` output
rows' planes plus the ``kd - 1`` more its ``kd`` dx taps reach, and the
lane block plus the ``(k-1)·(nz+1)``-lane halo its (dy, dz) taps reach.
The window's edges past the array are padding whose values reach only the
cropped outputs.  The dx steps are a reduction axis: the weight block is
the ``kd·k²`` taps of one step, and the output block, revisited across
them, is the accumulator.  The staged planes are a ring of ``kd``: row r
stages plane ``r + kd - 1`` and reads planes ``r .. r + kd - 1``.
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


def _kernel(x_ref, w_ref, o_ref, xs_ref, ws_ref, *, k: int, kd: int, kz: int, tx: int,
            nz: int, lw: int):
    # x_ref (tx+kd-1, f, window) planes; w_ref (kd·k², fpb, f) this step's
    # taps; o_ref (1, tx, fpb, lanes) the accumulator over the dx steps;
    # xs_ref (kd, kz·f, wz) the staged planes' ring; ws_ref
    # (kd·k·groups, fpb, kz·f) the restacked taps.
    f, wz = x_ref.shape[1], xs_ref.shape[2]
    groups = k // kz
    first = pl.program_id(4) == 0

    def restack():
        for t in range(kd * k * groups):
            for j in range(kz):
                ws_ref[t, :, pl.ds(j * f, f)] = w_ref[t * kz + j].astype(ws_ref.dtype)

    if kd < k:
        restack()  # the weight block moves with every dx step
    else:
        # one weight block for the whole of this f' block's steps
        pl.when((pl.program_id(1) == 0) & (pl.program_id(2) == 0) & (pl.program_id(3) == 0))(
            restack)

    def stage(p):
        slot = p % kd
        for j in range(kz):
            xs_ref[slot, pl.ds(j * f, f), :] = x_ref[p, :, pl.ds(j, wz)].astype(xs_ref.dtype)

    for p in range(kd - 1):
        stage(p)

    def row(r, carry):
        stage(r + kd - 1)
        acc = None
        for dx in range(kd):
            slot = (r + dx) % kd
            for dy in range(k):
                for g in range(groups):
                    t = jnp.dot(
                        ws_ref[(dx * k + dy) * groups + g],
                        xs_ref[slot, :, pl.ds(dy * nz + g * kz, lw)],
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


def plan_vmem_bytes(*, k: int, kd: int, kz: int, tx: int, f: int, fpb: int, nz: int,
                    L: int, lb: int, stage) -> int:
    """Scoped VMEM one grid step holds: the double-buffered plane window,
    weight block and output block, the staged planes and taps, and a
    plane's staging load, a dot's slice and a row's products and sum."""
    _, lb, lw, window = lane_geometry(k, nz, L, lb)
    sb = jnp.dtype(stage).itemsize

    def tile(rows, cols, size=4):
        sub = 32 // size  # sublanes of one (sub, 128) tile: 8 at f32, 16 at bf16
        return (rows + (-rows) % sub) * (cols + (-cols) % 128) * size

    return (
        2 * (tx + kd - 1) * tile(f, window)
        + 2 * kd * k * k * tile(fpb, f)
        + 2 * tx * tile(fpb, lb)
        + kd * tile(kz * f, window - kz + 1, sb)
        + kd * k * (k // kz) * tile(fpb, kz * f, sb)
        + tile(f, window) + tile(kz * f, lw, sb) + 3 * tile(fpb, lw)
    )


@functools.partial(
    jax.jit,
    static_argnames=("k", "nz", "tx", "kd", "kz", "lb", "fpb", "stage", "interpret"),
)
def conv3d_planes(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    k: int,
    nz: int,
    tx: int,
    kd: int,
    kz: int,
    lb: int,
    fpb: int,
    stage,
    interpret: bool = True,
) -> jnp.ndarray:
    """x (S, nx, f, ny·nz) f32, w (k³, f', f) f32 -> (S, npx, f', npy·nz).

    ``kd`` divides k: the dx taps one grid step applies.  ``kz`` divides k:
    the dz taps one dot stacks.  ``lb`` is the output lanes a step writes:
    the whole ``npy·nz`` plane, or a multiple of 128 below it.  ``fpb``
    divides f' and is f' or a multiple of 8.  ``stage`` is the dtype the
    staged planes and taps are rounded to once.  Output lanes past
    ``npy·nz - (k-1)`` and z-columns past ``npz`` hold wrap-around garbage
    (ops.py crops them).
    """
    S, nx, f, L = x.shape
    fp = w.shape[1]
    npx = nx - k + 1
    n_b, lb, lw, window = lane_geometry(k, nz, L, lb)
    n_t = pl.cdiv(npx, tx)
    pad_x = n_t * tx - npx
    pad_l = max(0, (n_b - 1) * lb + window - L)
    # f' blocks outermost: with one dx step a block's restacked taps serve
    # every later step of it
    grid = (fp // fpb, S, n_t, n_b, k // kd)
    x_spec = pl.BlockSpec(
        (
            pl.squeezed,
            pl.Element(tx + kd - 1, padding=(0, pad_x)),
            pl.Element(f),
            pl.Element(window, padding=(0, pad_l)),
        ),
        # the lane offset is a multiple of 128 the compiler must see as one
        lambda j, s, t, b, d: (s, t * tx + d * kd, 0, b * lb if n_b > 1 else 0),
    )
    w_spec = pl.BlockSpec((kd * k * k, fpb, f), lambda j, s, t, b, d: (d, j, 0))
    o_spec = pl.BlockSpec((1, tx, fpb, lb), lambda j, s, t, b, d: (s, t, j, b))
    return pl.pallas_call(
        functools.partial(_kernel, k=k, kd=kd, kz=kz, tx=tx, nz=nz, lw=lw),
        grid=grid,
        name="direct_conv3d",
        in_specs=[x_spec, w_spec],
        out_specs=o_spec,
        out_shape=jax.ShapeDtypeStruct((S, npx, fp, L - (k - 1) * nz), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((kd, kz * f, window - kz + 1), stage),
            pltpu.VMEM((kd * k * (k // kz), fpb, kz * f), stage),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) + ("arbitrary",) * 4,
            vmem_limit_bytes=VMEM_LIMIT,
        ),
        interpret=interpret,
    )(x, w)
