"""Fused RMSNorm as a Pallas TPU kernel.

The jnp path materializes three [*, d] fp32 intermediates (square, mean,
rsqrt-scaled) per call — at 2 norms/layer × 126 layers this is pure HBM
traffic.  The kernel fuses the reduction and the scale into one VMEM pass
per [block_rows, d] tile: read x once, write y once.

Oracle: kernels/ref.py::rmsnorm_ref (== models/layers.py::rmsnorm).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)           # [rows, d]
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def rmsnorm(x, weight, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool = False):
    """x: [..., d]; weight: [d].  Rows are tiled into VMEM blocks."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = 1
    for s in orig_shape[:-1]:
        rows *= s
    xf = x.reshape(rows, d)
    block_rows = min(block_rows, rows)
    pad = (-rows) % block_rows
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    n = xf.shape[0] // block_rows

    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xf.shape, x.dtype),
        interpret=interpret,
    )(xf, weight)
    return out[:rows].reshape(orig_shape)
