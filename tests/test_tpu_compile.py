"""Every volume-path Pallas kernel compiles for a TPU v5e at n337 widths,
and ``direct_conv3d`` at n926's k=9 layers too.

Interpret mode (the other kernel tests) cannot see what the chip's
compiler refuses: unsupported layouts, lane-misaligned dynamic slices,
scoped-VMEM overruns.  Each case here lowers a kernel's op wrapper with
``interpret=False`` against one chip of a *described* ``v5e:2x2``
topology — the installed TPU compiler, no chip attached — at the shapes
``chip_smoke.py``'s one-chip plan for n337 gives (80 feature maps,
m=4, batch 2: 116³ patches, layer-0 overlap-save segments of 32 with an
FFT shape of (35, 120, 120)).  Nothing runs, so these say nothing about
values; the interpret-mode suites do.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and the test workers all
import this file.  The persistent compilation cache is off around the
compiles: an entry written for a described chip cannot be read back
without one.
"""

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.overlap_save import (
    os_input_spectra, plan_overlap_save, tail_segments,
)
from repro.kernels.cmul_mad import ops as cmul_ops
from repro.kernels.direct_conv3d import ops as conv_ops
from repro.kernels.mpf_pool import ops as mpf_ops
from repro.kernels.os_segment import ops as seg_ops

F = 80  # n337 feature maps
OS_SPEC = plan_overlap_save((116, 116, 116), (2, 2, 2), 32)
C64 = jnp.complex64


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _sds(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    return compiled


def _spectra_shape(lead, f):
    A, B, C = OS_SPEC.fft_shape
    return lead + (f, A, B, C // 2 + 1)


@pytest.mark.parametrize("q", ["full", "strip"])
def test_os_segment_fused_compiles(one_chip, q):
    """Layer 0 from cached spectra: the full segment grid of a patch pair
    and the strip path's trailing segments."""
    spec = OS_SPEC
    if q == "full":
        n_q, cols = spec.n_segments, None
    else:
        cols = spec.seg_core
        n_q = tail_segments(spec, cols)
    _compile(
        lambda Fs, W, b: seg_ops.os_segment_fused(
            Fs, W, b, spec, out_cols=cols, use_pallas=True, interpret=False
        ),
        _sds(one_chip, _spectra_shape((2, n_q), 1), C64),
        _sds(one_chip, _spectra_shape((F,), 1), C64),
        _sds(one_chip, (F,)),
    )


def test_os_segment_conv_compiles(one_chip):
    """``overlap_save_conv``'s Pallas form from raw input: XLA's segment
    FFT, then the fused kernel."""
    _compile(
        lambda x, W, b: seg_ops.os_segment_fused(
            os_input_spectra(x, OS_SPEC), W, b, OS_SPEC,
            use_pallas=True, interpret=False,
        ),
        _sds(one_chip, (2, 1) + OS_SPEC.n),
        _sds(one_chip, _spectra_shape((F,), 1), C64),
        _sds(one_chip, (F,)),
    )


@pytest.mark.parametrize(
    "S,n,fp", [(16, 57, F), (128, 27, F), (1024, 12, F), (1024, 6, 3), (2, 116, F)],
    ids=["L2", "L4", "L6", "L9", "L0-direct"],
)
def test_direct_conv3d_compiles(one_chip, S, n, fp):
    f = 1 if n == 116 else F
    k = 2 if n == 116 else 3
    _compile(
        lambda x, w: conv_ops.conv3d(x, w, use_pallas=True, interpret=False),
        _sds(one_chip, (S, f, n, n, n)),
        _sds(one_chip, (fp, f, k, k, k)),
    )


@pytest.mark.parametrize(
    "S,n,fp", [(8, 97, F), (64, 44, F), (64, 20, 3)], ids=["conv2", "conv4", "conv7"],
)
def test_direct_conv3d_n926_compiles(one_chip, S, n, fp):
    """n926's k=9 convs at 80 maps (m=12, batch 1: 202³ patches), conv 7
    at f'=3.  The weight block holds one dx of the taps; each plane of a
    lane block and its halo is staged once in bfloat16 with three dz
    shifts stacked (a contraction of 240; one tap a dot at f'=3); the row
    body is 27 dots of 2 MXU passes over at most fourteen 128-lane chunks,
    so each compiles in seconds and within the scoped VMEM limit."""
    _compile(
        lambda x, w: conv_ops.conv3d(x, w, use_pallas=True, interpret=False),
        _sds(one_chip, (S, F, n, n, n)),
        _sds(one_chip, (fp, F, 9, 9, 9)),
    )


@pytest.mark.parametrize("S,n", [(2, 115), (16, 55), (128, 25)], ids=["L1", "L3", "L5"])
def test_mpf_pool_compiles(one_chip, S, n):
    _compile(
        lambda x: mpf_ops.mpf_pool(x, 2, use_pallas=True, interpret=False),
        _sds(one_chip, (S, F, n, n, n)),
    )


def test_mpf_pool_window_compiles(one_chip):
    """The fused pair's pool over an inverse transform's uncropped rows."""
    _compile(
        lambda x: mpf_ops.mpf_pool_window(
            x, 2, (55, 55, 55), use_pallas=True, interpret=False
        ),
        _sds(one_chip, (16, F, 55, 55, 64)),
    )


# fft_cached at n337's deep layers: spectra of 10³ inputs (6 rfft bins)
SP = (10, 10, 6)


def test_cmul_mad_planes_compiles(one_chip):
    _compile(
        lambda X, W: cmul_ops.cmul_mad(X, W, use_pallas=True, interpret=False),
        _sds(one_chip, (512, F) + SP, C64),
        _sds(one_chip, (F, F) + SP, C64),
    )


def test_cmul_mad_bias_planes_compiles(one_chip):
    _compile(
        lambda X, W, b: cmul_ops.cmul_mad_bias(
            X, W, b, fft_shape=(10, 10, 10), use_pallas=True, interpret=False
        ),
        _sds(one_chip, (512, F) + SP, C64),
        _sds(one_chip, (F, F) + SP, C64),
        _sds(one_chip, (F,)),
    )
