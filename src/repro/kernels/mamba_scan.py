"""Mamba2/SSD chunked selective scan as a Pallas TPU kernel.

TPU adaptation (vs the CUDA mamba kernel):
  * the CUDA implementation leans on warp-level parallel prefix scans;
    TPUs have no warp shuffles, so we use the SSD *block decomposition*:
    the intra-chunk part becomes a decay-masked [K, K] matmul (MXU work)
    and only the chunk-boundary state is carried — the recurrence runs
    over the sequential grid dimension with the state in VMEM scratch;
  * chunk length K and head_dim are the MXU-aligned tile sides; d_state
    rides along the lane dimension.

Grid: (batch, heads, n_chunks) with n_chunks the sequential (minor) axis.
Per step the kernel computes
    y_intra = (L ∘ (C Bᵀ) ∘ dtᵀ) X        (within-chunk, matmul form)
    y_inter = exp(s) C · h                 (contribution of carried state)
    h      <- exp(s_K) h + Σ_j exp(s_K - s_j) dt_j x_j ⊗ B_j
with s the in-chunk cumulative log-decay.  Oracle: kernels/ref.py::ssd_ref.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, dtr_ref, b_ref, c_ref, a_ref, y_ref,
                hlast_ref, h_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0, 0].astype(jnp.float32)          # [K, hd]
    dt = dt_ref[0, 0].astype(jnp.float32)        # [K, 1]
    dt_row = dtr_ref[0, 0, 0].astype(jnp.float32)  # [1, K]
    bs = b_ref[0].astype(jnp.float32)            # [K, ds]
    cs = c_ref[0].astype(jnp.float32)            # [K, ds]
    a = a_ref[pl.program_id(1)]                  # scalar (negative), SMEM
    hd, ds = h_scr.shape

    iota_i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    tril = (iota_i >= iota_j).astype(jnp.float32)
    # in-chunk cumulative log-decay, as a column and as a row: prefix
    # sums are matmuls with the triangular ones matrix (MXU work, and no
    # transpose of a vector); the chunk total comes out already broadcast
    # to the shape each use needs
    dot = lambda u, w, cu, cw: jax.lax.dot_general(
        u, w, (((cu,), (cw,)), ((), ())), preferred_element_type=jnp.float32)
    s_col = dot(tril, dt * a, 1, 0)                            # [K, 1]
    s_row = dot(dt_row * a, tril, 1, 1)                        # [1, K]
    s_last_col = dot(jnp.ones((chunk, chunk), jnp.float32), dt * a, 1, 0)
    s_last_row = dot(dt_row * a, jnp.ones((chunk, ds), jnp.float32), 1, 0)
    # intra-chunk decay matrix L[i,j] = exp(s_i - s_j) * dt_j, causal
    L = jnp.where(iota_i >= iota_j, jnp.exp(s_col - s_row) * dt_row, 0.0)
    scores = dot(cs, bs, 1, 1)                                 # [K, K]
    y_intra = dot(L * scores, x, 1, 0)                         # [K, hd]
    # inter-chunk: y_i += exp(s_i) * C_i . h   (h: [hd, ds])
    h = h_scr[...]
    y_inter = jnp.exp(s_col) * dot(cs, h, 1, 1)
    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: h' = exp(s_K) h + Σ_j exp(s_K - s_j) dt_j x_j ⊗ B_j
    tail = jnp.exp(s_last_col - s_col) * dt                    # [K, 1]
    dh = dot(x * tail, bs, 0, 0)                               # [hd, ds]
    h_scr[...] = jnp.exp(s_last_row) * h + dh

    @pl.when(ci == n_chunks - 1)
    def _final():
        hlast_ref[0, 0] = h_scr[...]


def ssd_scan(xh, dt, b_s, c_s, a, *, chunk: int = 64,
             interpret: bool = False):
    """xh: [B, nh, S, hd]; dt: [B, nh, S]; b_s/c_s: [B, S, ds]; a: [nh].
    Returns (y [B, nh, S, hd], h_last [B, nh, hd, ds]).  S % chunk == 0."""
    B, nh, S, hd = xh.shape
    ds = b_s.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    kernel = functools.partial(_ssd_kernel, chunk=chunk, n_chunks=nc)

    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, chunk, 1), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, 1, 1, chunk),
                         lambda b, h, c: (b, h, c, 0, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, h, c: (b, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),       # a: whole [nh]
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, hd), lambda b, h, c: (b, h, c, 0)),
            pl.BlockSpec((1, 1, hd, ds), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, nh, S, hd), jnp.float32),
            jax.ShapeDtypeStruct((B, nh, hd, ds), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, ds), jnp.float32)],
        interpret=interpret,
    )(xh, dt[..., None], dt.reshape(B, nh, nc, 1, chunk), b_s, c_s,
      a.astype(jnp.float32))
    return y, h_last


# --------------------------------------------------------------------- #
# Mamba1: per-channel recurrence, sequential in-chunk loop
# --------------------------------------------------------------------- #

def _mamba1_kernel(x_ref, dt_ref, b_ref, c_ref, at_ref, y_ref, hlast_ref,
                   h_scr, *, chunk: int, n_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    bs = b_ref[0].astype(jnp.float32)      # [K, ds]
    cs = c_ref[0].astype(jnp.float32)      # [K, ds]
    At = at_ref[...].astype(jnp.float32)   # [ds, di]
    iota = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)

    def step(t, h):                        # h: [ds, di], d_inner on lanes
        # row t of x/dt is a dynamic sublane slice of the ref; B_t and C_t
        # come out as [ds, 1] columns from a one-hot contraction, so no
        # vector is ever transposed
        x_t = x_ref[0, pl.ds(t, 1), :].astype(jnp.float32)   # [1, di]
        dt_t = dt_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # [1, di]
        hot = (iota == t).astype(jnp.float32)                # [K, 1]
        b_t = jax.lax.dot_general(bs, hot, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        c_t = jax.lax.dot_general(cs, hot, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        h = jnp.exp(dt_t * At) * h + b_t * (dt_t * x_t)
        y_ref[0, pl.ds(t, 1), :] = jnp.sum(
            h * c_t, axis=0, keepdims=True).astype(y_ref.dtype)
        return h

    h_scr[...] = jax.lax.fori_loop(0, chunk, step, h_scr[...])

    @pl.when(ci == n_chunks - 1)
    def _final():
        hlast_ref[0] = h_scr[...]


def mamba1_scan(x, dt, b_s, c_s, A, *, chunk: int = 64,
                interpret: bool = False):
    """x/dt: [B, S, di]; b_s/c_s: [B, S, ds]; A: [di, ds] (negative).
    Returns (y [B, S, di] fp32, h_last [B, di, ds])."""
    B, S, di = x.shape
    ds = b_s.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_mamba1_kernel, chunk=chunk, n_chunks=nc)
    y, h_last = pl.pallas_call(
        kernel,
        grid=(B, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, ds), lambda b, c: (b, c, 0)),
            pl.BlockSpec((ds, di), lambda b, c: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, chunk, di), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, ds, di), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, S, di), jnp.float32),
            jax.ShapeDtypeStruct((B, ds, di), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((ds, di), jnp.float32)],
        interpret=interpret,
    )(x, dt, b_s, c_s, A.T)
    return y, h_last.transpose(0, 2, 1)
