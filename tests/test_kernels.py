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


@pytest.mark.parametrize("fp", [8, 3])
def test_direct_conv3d_k9(fp, rng):
    """n926's kernel size at a toy width.  Through the wrapper: one lane
    block, nine dx steps accumulating into the output block.  Then the
    kernel with lanes cut into 128-lane blocks (240 output lanes: two, the
    second ragged), each reading its (k-1)·(nz+1)-lane halo past the
    block, and x tiles of 5 planes over 12 (the last ragged)."""
    S, f, k = 2, 8, 9
    x = rng.normal(size=(S, f, 14, 14, 14)).astype(np.float32)
    w = rng.normal(size=(fp, f, k, k, k)).astype(np.float32)
    got = c3_ops.conv3d(jnp.asarray(x), jnp.asarray(w), use_pallas=True)
    want = c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-3, rtol=1e-4)

    n = 20
    m = n - k + 1
    x = rng.normal(size=(S, f, n, n, n)).astype(np.float32)
    assert c3_kernel.lane_geometry(k, n, n * n, 128)[0] == 2
    planes = jnp.asarray(x.transpose(0, 2, 1, 3, 4).reshape(S, n, f, n * n))
    taps = jnp.asarray(w.transpose(2, 3, 4, 0, 1).reshape(k**3, fp, f))
    o = c3_kernel.conv3d_planes(planes, taps, k=k, nz=n, tx=5, kd=1, lb=128, fpb=fp)
    got = np.asarray(o).reshape(S, m, fp, m, n)[..., :m].transpose(0, 2, 1, 3, 4)
    want = c3_ref.conv3d(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-3, rtol=1e-4)


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
