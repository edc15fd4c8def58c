"""Int8 Pallas kernels: blocked matmul and int8-KV flash attention.

Scale scheme (docs/quantization.md): symmetric per-block absmax —
``scale = max(|x|) / 127`` over each block, ``q = round(x / scale)``
clipped to [-127, 127].  Zero blocks take scale 1.0 so the round trip
stays exact.

  * ``int8_matmul_blocked``: [M, K] x [K, N] over a (nM, nN, nK) grid
    with K as the sequential minor dimension.  Each step issues an
    int8 x int8 MXU matmul accumulated in int32
    (``preferred_element_type=jnp.int32``); because absmax scales differ
    per K block, every step dequantizes its int32 partial into the fp32
    VMEM accumulator (dequant epilogue on the last K step writes out).
  * ``flash_attention_int8kv_bhsd``: flash_attention.py's online-softmax
    kernel with int8 k/v refs plus per-token fp32 scales, dequantized
    in-kernel right before the q.k^T and p.v matmuls.  A dynamic
    key-validity input masks ring-cache slots that are not yet filled
    (decode) and padded key positions (non-causal prefill).

Oracles: kernels/ref.py (``matmul_ref``, ``attention_ref``); parity and
error bounds in tests/test_quantized.py (interpret mode on CPU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def quantize_blocks(x, block_rows: int, block_cols: int):
    """Per-2D-tile absmax int8 quantization of a [M, K] fp array (M, K
    already padded to block multiples).  Returns (q int8 [M, K],
    scale fp32 [M // block_rows, K // block_cols])."""
    M, K = x.shape
    nm, nk = M // block_rows, K // block_cols
    t = x.astype(jnp.float32).reshape(nm, block_rows, nk, block_cols)
    absmax = jnp.max(jnp.abs(t), axis=(1, 3))                  # [nm, nk]
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.round(t / scale[:, None, :, None])
    q = jnp.clip(q, -127, 127).astype(jnp.int8)
    return q.reshape(M, K), scale


def _int8_matmul_kernel(xq_ref, xs_ref, wq_ref, ws_ref, o_ref, acc_scr, *,
                        n_k_blocks: int):
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    prod = jax.lax.dot_general(
        xq_ref[...], wq_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)                  # [bm, bn] int32
    # one absmax scale per (row-block, K-block) x (K-block, col-block)
    # pair => the int32 partial dequantizes with one [1, 1] factor.
    acc_scr[...] += prod.astype(jnp.float32) * (xs_ref[0, 0] * ws_ref[0, 0])

    @pl.when(kk == n_k_blocks - 1)
    def _finalize():
        o_ref[...] = acc_scr[...]


def int8_matmul_blocked(xq, xs, wq, ws, *, block_m: int = 128,
                        block_k: int = 128, block_n: int = 128,
                        interpret: bool = False):
    """xq: [M, K] int8 with xs: [M/bm, K/bk] fp32 scales; wq: [K, N] int8
    with ws: [K/bk, N/bn].  Shapes must already be block multiples
    (ops.int8_matmul pads).  Returns fp32 [M, N]."""
    M, K = xq.shape
    N = wq.shape[1]
    nm, nn, nk = M // block_m, N // block_n, K // block_k
    assert xs.shape == (nm, nk) and ws.shape == (nk, nn), (xs.shape, ws.shape)

    kernel = functools.partial(_int8_matmul_kernel, n_k_blocks=nk)
    # each scale is its own trailing [1, 1] tile: a TPU block's last two
    # dims must be (8, 128)-aligned or span the array's, so a (1, 1)
    # block of the [nm, nk] scale matrix itself does not lower
    return pl.pallas_call(
        kernel,
        grid=(nm, nn, nk),
        in_specs=[
            pl.BlockSpec((block_m, block_k), lambda i, j, k: (i, k)),
            pl.BlockSpec((1, 1, 1, 1), lambda i, j, k: (i, k, 0, 0)),
            pl.BlockSpec((block_k, block_n), lambda i, j, k: (k, j)),
            pl.BlockSpec((1, 1, 1, 1), lambda i, j, k: (k, j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_m, block_n), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        scratch_shapes=[pltpu.VMEM((block_m, block_n), jnp.float32)],
        interpret=interpret,
    )(xq, xs[:, :, None, None], wq, ws[:, :, None, None])


def _int8kv_flash_kernel(q_ref, kq_ref, ks_ref, vq_ref, vs_ref, valid_ref,
                         o_ref, m_scr, l_scr, acc_scr, *, block_q: int,
                         block_k: int, n_kv_blocks: int, scale: float,
                         causal: bool, window: int):
    qi = pl.program_id(2)
    kj = pl.program_id(3)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32) * scale            # [bq, d]
    # dequant-in-kernel: a key's (value's) per-token scale multiplies its
    # score column (probability column), so the [1, bk] scale rows
    # broadcast over queries and are never transposed into columns
    k = kq_ref[0, 0].astype(jnp.float32)                   # [bk, d]
    v = vq_ref[0, 0].astype(jnp.float32)                   # [bk, dv]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [bq, bk]
    s = s * ks_ref[0, 0]                                   # [1, bk] scales

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = valid_ref[0] > 0                                # [1, bk] validity
    if causal:
        mask &= q_pos >= k_pos
    if window:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]                                    # [bq, 1]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    p = jnp.exp(s - m_new)
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=1, keepdims=True)
    m_scr[...] = m_new
    acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
        p * vs_ref[0, 0], v, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_int8kv_bhsd(q, kq, ks, vq, vs, valid, *,
                                causal: bool = True, window: int = 0,
                                block_q: int = 128, block_k: int = 128,
                                scale: float | None = None,
                                interpret: bool = False):
    """q: [B, H, Sq, D] fp; kq/vq: [B, KV, Sk, D*] int8 with per-token
    scales ks/vs: [B, KV, 1, Sk] fp32; valid: [B, 1, Sk] fp32 (>0 = key
    is live — carries both pad masking and the decode ring-cache fill
    state, so it may be traced).  The scales and the validity keep Sk on
    the lane axis behind a unit sublane axis, so their blocks tile.
    Returns [B, H, Sq, Dv] in q.dtype."""
    B, H, Sq, D = q.shape
    _, KV, Sk, Dv = vq.shape
    group = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, block_q, Sk, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    if scale is None:
        scale = 1.0 / (D ** 0.5)

    kernel = functools.partial(
        _int8kv_flash_kernel, block_q=block_q, block_k=block_k,
        n_kv_blocks=nk, scale=scale, causal=causal, window=window)

    return pl.pallas_call(
        kernel,
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda b, h, i, j: (b, h // group, 0, j)),
            pl.BlockSpec((1, 1, block_k, Dv),
                         lambda b, h, i, j: (b, h // group, j, 0)),
            pl.BlockSpec((1, 1, 1, block_k),
                         lambda b, h, i, j: (b, h // group, 0, j)),
            pl.BlockSpec((1, 1, block_k), lambda b, h, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, Dv),
                               lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, Dv), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),    # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),    # running denom l
            pltpu.VMEM((block_q, Dv), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(q, kq, ks, vq, vs, valid)
