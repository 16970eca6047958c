"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs ref.py oracles."""

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.cmul_mad import ops as cmul_ops, ref as cmul_ref
from repro.kernels.decode_attn import ops as da_ops, ref as da_ref
from repro.kernels.direct_conv3d import kernel as c3_kernel, ops as c3_ops, ref as c3_ref
from repro.kernels.mpf_pool import ops as mp_ops, ref as mp_ref


# --------------------------------------------------------------------------
# cmul_mad
# --------------------------------------------------------------------------


@pytest.mark.parametrize("S,f,fp,sp", [
    (1, 1, 1, (4, 4, 3)),
    (2, 3, 5, (5, 4, 3)),
    (1, 8, 16, (8, 8, 5)),
    (3, 2, 9, (7, 3, 2)),  # fp not multiple of FP_BLOCK
])
def test_cmul_mad_sweep(S, f, fp, sp, rng):
    X = jnp.asarray((rng.normal(size=(S, f) + sp) + 1j * rng.normal(size=(S, f) + sp)).astype(np.complex64))
    W = jnp.asarray((rng.normal(size=(fp, f) + sp) + 1j * rng.normal(size=(fp, f) + sp)).astype(np.complex64))
    got = cmul_ops.cmul_mad(X, W, use_pallas=True)
    want = cmul_ref.cmul_mad(X, W)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-4, rtol=1e-4)


# --------------------------------------------------------------------------
# direct_conv3d
# --------------------------------------------------------------------------


@pytest.mark.parametrize("S,f,fp,n,k", [
    (1, 1, 1, 6, 2),
    (2, 3, 5, 8, 3),
    # heavy cases (~20s combined): fp not multiple of FP_BLOCK / odd n'
    pytest.param(1, 4, 9, 9, 5, marks=pytest.mark.slow),
    pytest.param(1, 2, 8, 11, 7, marks=pytest.mark.slow),
])
def test_direct_conv3d_sweep(S, f, fp, n, k, rng):
    x = jnp.asarray(rng.normal(size=(S, f, n, n, n)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(fp, f, k, k, k)).astype(np.float32))
    got = c3_ops.conv3d(x, w, use_pallas=True)
    want = c3_ref.conv3d(x, w)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-4)


def _planes_conv(x, w, *, k, tx, kd, kz, lb, stage=jnp.float32):
    """``conv3d_planes`` on NCDHW ``x`` and OIDHW ``w`` at an explicit
    blocking, cropped back to NCDHW."""
    S, f, n = x.shape[:3]
    fp, m = w.shape[0], n - k + 1
    planes = jnp.asarray(x.transpose(0, 2, 1, 3, 4).reshape(S, n, f, n * n))
    taps = jnp.asarray(w.transpose(2, 3, 4, 0, 1).reshape(k**3, fp, f))
    o = c3_kernel.conv3d_planes(planes, taps, k=k, nz=n, tx=tx, kd=kd, kz=kz, lb=lb,
                                fpb=fp, stage=stage)
    return np.asarray(o).reshape(S, m, fp, m, n)[..., :m].transpose(0, 2, 1, 3, 4)


@pytest.mark.parametrize("fp", [8, 3])
def test_direct_conv3d_k9(fp, rng):
    """n926's kernel size at a toy width.  Through the wrapper: one lane
    block, nine dx steps accumulating into the output block.  Then the
    kernel with all nine dz taps stacked (a contraction of 72), lanes cut
    into 128-lane blocks (240 output lanes: two, the second ragged), each
    reading its (k-1)·(nz+1)-lane halo past the block, and x tiles of 5
    planes over 12 (the last ragged)."""
    S, f, k = 2, 8, 9
    x = rng.normal(size=(S, f, 14, 14, 14)).astype(np.float32)
    w = rng.normal(size=(fp, f, k, k, k)).astype(np.float32)
    got = c3_ops.conv3d(jnp.asarray(x), jnp.asarray(w), use_pallas=True)
    want = c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-4)

    n = 20
    x = rng.normal(size=(S, f, n, n, n)).astype(np.float32)
    assert c3_kernel.lane_geometry(k, n, n * n, 128)[0] == 2
    got = _planes_conv(x, w, k=k, tx=5, kd=1, kz=k, lb=128)
    want = c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("k,kd,kz,fp,lb", [
    (3, 3, 3, 5, 128),     # all dz taps in one dot, three dx a step, ragged lane blocks
    (3, 1, 3, 3, 10**6),   # one dx a step, the whole plane, f'=3
    (3, 3, 1, 5, 128),     # no stacking: one tap a dot
    (9, 3, 3, 3, 128),     # k=9 in three dz groups of 3, a ring of three staged planes
])
def test_direct_conv3d_stacked(k, kd, kz, fp, lb, rng):
    """The stacked contraction at toy widths (f=8) against the oracle, with
    x tiles over a ragged count of planes and, where ``lb`` is 128 at k=3,
    lane blocks whose last one is ragged."""
    S, f, n, tx = (2, 8, 13, 4) if k == 3 else (1, 8, 11, 2)
    x = rng.normal(size=(S, f, n, n, n)).astype(np.float32)
    w = rng.normal(size=(fp, f, k, k, k)).astype(np.float32)
    assert (n - k + 1) % tx
    got = _planes_conv(x, w, k=k, tx=tx, kd=kd, kz=kz, lb=lb)
    want = c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-3, rtol=1e-4)


@pytest.mark.parametrize("k,fp", [(3, 5), (9, 3)])
def test_direct_conv3d_bf16_staged(k, fp, rng):
    """The form the compiled kernel runs: planes and taps staged in
    bfloat16 (f=16 fills the packed tiles), products and sums in float32.
    It equals the oracle on bfloat16-rounded operands up to the order of
    the float32 sums."""
    S, f, n = 1, 16, 12 if k == 3 else 11
    x = rng.normal(size=(S, f, n, n, n)).astype(np.float32)
    w = rng.normal(size=(fp, f, k, k, k)).astype(np.float32)
    got = _planes_conv(x, w, k=k, tx=2, kd=3 if k == 3 else 1, kz=k, lb=128,
                       stage=jnp.bfloat16)
    r16 = lambda a: jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)
    want = c3_ref.conv3d(r16(x), r16(w))
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-3, rtol=1e-4)
    assert np.abs(got - np.asarray(c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w)))).max() > 1e-2


# (k, S, f, f', nx, n) of every direct layer the two benchmark nets run:
# full steps, then strip steps (fewer x planes)
NET_LAYERS = {
    "n926.conv2": (9, 8, 80, 80, 97, 97), "n926.conv4": (9, 64, 80, 80, 44, 44),
    "n926.conv5": (9, 64, 80, 80, 36, 36), "n926.conv6": (9, 64, 80, 80, 28, 28),
    "n926.conv7": (9, 64, 80, 3, 20, 20), "n926.strip.conv2": (9, 8, 80, 80, 32, 97),
    "n926.strip.conv4": (9, 64, 80, 80, 20, 44), "n926.strip.conv5": (9, 64, 80, 80, 20, 36),
    "n926.strip.conv6": (9, 64, 80, 80, 20, 28),
    "n337.L2": (3, 16, 80, 80, 57, 57), "n337.L4": (3, 128, 80, 80, 27, 27),
    "n337.L6": (3, 1024, 80, 80, 12, 12), "n337.L7": (3, 1024, 80, 80, 10, 10),
    "n337.L8": (3, 1024, 80, 80, 8, 8), "n337.L9": (3, 1024, 80, 3, 6, 6),
    "n337.strip.L2": (3, 16, 80, 80, 18, 57), "n337.strip.L4": (3, 128, 80, 80, 10, 27),
    "n337.strip.L6": (3, 1024, 80, 80, 6, 12),
}


@pytest.mark.parametrize("layer", NET_LAYERS.values(), ids=NET_LAYERS.keys())
def test_direct_conv3d_picker_counts_staged_operands(layer):
    """For the compiled kernel at every benchmark layer: the staged operand
    is bfloat16; three dz taps stack (a contraction of 240, 94% of two MXU
    passes) where the f' block and a row's lanes exceed one MXU tile's
    sublanes and lanes, else one; the planned VMEM holds the
    double-buffered input window and output block and the staged plane
    ring and taps, within VMEM_BUDGET."""
    k, S, f, fp, nx, n = layer
    stage = c3_ops.stage_dtype(f, interpret=False)
    assert stage == jnp.bfloat16
    b = c3_ops._pick_blocking(nx - k + 1, k, f, fp, n, n * n, (n - k + 1) * n, stage)
    assert b.kz == (3 if fp >= 8 and (n - k + 1) * n > 128 else 1) and b.stage == stage
    _, lb, _, window = c3_kernel.lane_geometry(k, n, n * n, b.lb)
    up = lambda v, m: v + (-v) % m
    staged = b.kd * up(b.kz * f, 16) * up(window - b.kz + 1, 128) * 2
    taps = b.kd * k * (k // b.kz) * up(fp, 16) * up(b.kz * f, 128) * 2
    held = (2 * (b.tx + b.kd - 1) * up(f, 8) * up(window, 128) * 4
            + 2 * b.tx * up(fp, 8) * up(lb, 128) * 4)
    assert b.vmem == c3_kernel.plan_vmem_bytes(
        k=k, kd=b.kd, kz=b.kz, tx=b.tx, f=f, fpb=fp, nz=n, L=n * n, lb=b.lb, stage=stage)
    assert held + staged + taps < b.vmem <= c3_ops.VMEM_BUDGET


# --------------------------------------------------------------------------
# mpf_pool
# --------------------------------------------------------------------------


@pytest.mark.parametrize("S,f,p,m", [
    (1, 1, 2, 2),
    (2, 3, 2, 3),
    (1, 9, 3, 1),  # f not multiple of F_BLOCK
])
def test_mpf_pool_sweep(S, f, p, m, rng):
    n = p * m + p - 1
    x = jnp.asarray(rng.normal(size=(S, f, n, n, n)).astype(np.float32))
    got = mp_ops.mpf_pool(x, p, use_pallas=True)
    want = mp_ref.mpf_pool(x, p)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_mpf_pool_rejects_bad_sizes(rng):
    x = jnp.zeros((1, 1, 4, 4, 4))
    with pytest.raises(ValueError):
        mp_ops.mpf_pool(x, 2)


# --------------------------------------------------------------------------
# decode_attn
# --------------------------------------------------------------------------


@pytest.mark.parametrize("B,H,Hkv,S,d,dtype", [
    (1, 4, 4, 128, 32, np.float32),      # MHA
    (2, 8, 2, 600, 16, np.float32),      # GQA, S not multiple of S_BLOCK
    (2, 8, 1, 1024, 64, np.float32),     # MQA
    (2, 4, 2, 513, 32, "bfloat16"),      # bf16 + ragged S
])
def test_decode_attn_sweep(B, H, Hkv, S, d, dtype, rng):
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rng.normal(size=(B, H, d)).astype(np.float32)).astype(dt)
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32)).astype(dt)
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32)).astype(dt)
    lengths = jnp.asarray(rng.integers(1, S + 1, size=(B,)).astype(np.int32))
    got = da_ops.decode_attn(q, k, v, lengths, use_pallas=True)
    want = da_ref.decode_attn(q, k, v, lengths)
    atol = 2e-2 if dt == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=atol, rtol=1e-2
    )


def test_decode_attn_masks_beyond_length(rng):
    """Entries past `lengths` must not affect the output."""
    B, H, Hkv, S, d = 1, 2, 2, 256, 16
    q = jnp.asarray(rng.normal(size=(B, H, d)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(B, S, Hkv, d)).astype(np.float32))
    lengths = jnp.array([100], jnp.int32)
    out1 = da_ops.decode_attn(q, k, v, lengths, use_pallas=True)
    k2 = k.at[:, 100:].set(1e6)
    v2 = v.at[:, 100:].set(-1e6)
    out2 = da_ops.decode_attn(q, k2, v2, lengths, use_pallas=True)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2), atol=1e-5)
