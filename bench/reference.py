"""The plain reference: a dense sliding-window ConvNet in ``jax.numpy``.

It imports nothing of the system under test.  A net is the configuration
file's ``net``: ``in_channels`` and ``layers``, each ``["conv", k, f_out]``
or ``["pool", p]``.  The dense output of a max-pooling ConvNet swept over
every window position is the net with its pools turned into max filters
of stride 1 and every later layer dilated by the product of the pools
before it.  ReLU follows every conv but the last.

``precision`` selects the arithmetic:

* ``"highest"`` — float32 with ``Precision.HIGHEST``: the reference.
* ``"bf16_products"`` — the precision the configurations state: every
  conv's operands rounded to bfloat16, products summed and activations
  kept in float32.  This is what a TPU computes for a float32 conv at its
  default matmul precision (one bfloat16 pass), made explicit so that it
  means the same on any backend.
* ``"bf16"`` — operands, activations and outputs in bfloat16.
* ``"int8"`` / ``"fp8"`` — every conv's operands rounded to int8 or to
  float8 e4m3, each tensor scaled by its largest magnitude, products summed
  and activations kept in float32.

The last three are controls: the reference put in the program's place at
a precision below the configuration's.
"""

from __future__ import annotations

import functools
import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

PRECISIONS = ("highest", "bf16_products", "bf16", "int8", "fp8")


def make_params(net: dict, seed: int, bias_std: float):
    """He-scaled normal weights and N(0, bias_std²) biases, from ``seed``,
    made on the device in one jitted call.  Pools get ``None``."""
    key = jax.random.PRNGKey(0)
    key = jax.random.fold_in(key, int(seed) % 2**32)
    key = jax.random.fold_in(key, (int(seed) // 2**32) % 2**32)
    return _make_params(key, _net_key(net), float(bias_std))


def _net_key(net: dict):
    return (int(net["in_channels"]), tuple(tuple(layer) for layer in net["layers"]))


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make_params(key, net_key, bias_std):
    f, layers = net_key
    params = []
    for i, layer in enumerate(layers):
        if layer[0] != "conv":
            params.append(None)
            continue
        k, fo = layer[1], layer[2]
        kw, kb = jax.random.split(jax.random.fold_in(key, i))
        w = jax.random.normal(kw, (fo, f, k, k, k), jnp.float32)
        params.append((w * np.sqrt(2.0 / (f * k**3)), bias_std * jax.random.normal(kb, (fo,))))
        f = fo
    return params


def _round(a, precision):
    """``a`` rounded to the operand format of ``precision``, in float32."""
    if precision == "bf16_products":
        return a.astype(jnp.bfloat16).astype(jnp.float32)
    if precision not in ("fp8", "int8"):
        return a
    top = jnp.max(jnp.abs(a))
    top = jnp.where(top > 0, top, 1.0)
    if precision == "fp8":
        s = top / 448.0  # e4m3's largest finite value
        return (a / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
    s = top / 127.0
    return jnp.clip(jnp.round(a / s), -127, 127) * s


def _max_filter(x, p, d):
    """Max over p taps spaced d apart, stride 1, on each spatial axis."""
    n = x.shape[-3:]
    out = tuple(m - (p - 1) * d for m in n)
    y = None
    for o in itertools.product(range(p), repeat=3):
        s = x[..., o[0] * d : o[0] * d + out[0], o[1] * d : o[1] * d + out[1],
              o[2] * d : o[2] * d + out[2]]
        y = s if y is None else jnp.maximum(y, s)
    return y


@functools.partial(jax.jit, static_argnames=("layers", "precision"))
def _forward(params, x, *, layers, precision):
    dt = jnp.bfloat16 if precision == "bf16" else jnp.float32
    # float32 operands are multiplied exactly; rounding, where a precision
    # asks for it, is done by _round beforehand
    prec = lax.Precision.DEFAULT if precision == "bf16" else lax.Precision.HIGHEST
    last = max(i for i, layer in enumerate(layers) if layer[0] == "conv")
    x = x.astype(dt)
    d = 1
    for i, layer in enumerate(layers):
        if layer[0] == "conv":
            w, b = params[i]
            lhs, rhs = _round(x, precision), _round(w.astype(dt), precision)
            x = lax.conv_general_dilated(
                lhs, rhs, (1, 1, 1), "VALID", rhs_dilation=(d, d, d),
                dimension_numbers=("NCDHW", "OIDHW", "NCDHW"), precision=prec,
                preferred_element_type=dt,
            ) + b.astype(dt).reshape(1, -1, 1, 1, 1)
            if i != last:
                x = jax.nn.relu(x)
        else:
            x = _max_filter(x, layer[1], d)
            d *= layer[1]
    return x.astype(jnp.float32)


def dense(params, net: dict, box: np.ndarray, precision: str = "highest") -> np.ndarray:
    """Dense output (f_out, X-FOV+1, ...) of one input box (f, X, Y, Z)."""
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}; known: {PRECISIONS}")
    layers = tuple(tuple(layer) for layer in net["layers"])
    with jax.default_matmul_precision("highest"):
        y = _forward(params, jnp.asarray(box)[None], layers=layers, precision=precision)
    return np.asarray(y[0])


def field_of_view(net: dict) -> int:
    fov, stride = 1, 1
    for layer in net["layers"]:
        fov += (layer[1] - 1) * stride
        if layer[0] == "pool":
            stride *= layer[1]
    return fov


def gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest |got - want| over the largest |want|."""
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got.astype(np.float64) - want)))
    if not np.all(np.isfinite(got)):
        return float("inf")
    return err / scale if scale > 0 else float("inf")
