"""Jit'd wrappers: layout/padding glue between model code ([B, S, H, D]
activations) and the Pallas kernels ([B, H, S, D] MXU-aligned tiles).

``interpret=None`` means "interpret on the CPU": the kernels execute (and
are tested) there through the Pallas interpreter, and every other backend
gets the compiled kernels.  ``_default_interpret`` is the one place that
makes this choice.
"""
from __future__ import annotations

import contextlib
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu import flash_attention as _tpu_fa

from repro.kernels import mamba_scan as _scan
from repro.kernels import flash_attention as _fa
from repro.kernels import quantized as _q


def _default_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _pad_axis(x, mult: int, axis: int):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ------------------------------------------------------------------ #
# fused causal flash attention with a backward pass: jax's Pallas TPU
# flash attention kernels (jax.experimental.pallas.ops.tpu), forward,
# dk/dv and dq, with bf16 operands, fp32 softmax statistics and
# accumulation, scores in VMEM, and blocks wholly above the diagonal
# skipped.  Block sizes are from a sweep on a TPU v5e at S 1024, D 64.
# ------------------------------------------------------------------ #

def fused_block(seq: int) -> int:
    """The block along q and k of the forward and the dq kernel."""
    return min(seq, 512)


def _dkv_block(seq: int) -> int:
    """The block of the dk/dv kernel: the whole sequence up to 1024."""
    return seq if seq <= 1024 else fused_block(seq)


def fused_attention_takes(q_shape, k_shape, v_shape, *, causal: bool,
                          window: int) -> bool:
    """Whether the fused kernel computes attention of these shapes
    (model layout, q: [B, Sq, H, Dk]; k/v: [B, Sk, KV, D*]): plain
    causal self-attention, one kv head per query head, Dk == Dv, and a
    sequence its block divides into lane-aligned tiles."""
    _, Sq, H, Dk = q_shape
    _, Sk, KV, _ = k_shape
    Dv = v_shape[-1]
    blk = fused_block(Sq)
    return (causal and not window and H == KV and Dk == Dv and Sq == Sk
            and (Dk <= 128 or Dk % 128 == 0)
            and blk % 128 == 0 and Sq % blk == 0)


def _interpreting(interpret: bool):
    return pltpu.force_tpu_interpret_mode() if interpret \
        else contextlib.nullcontext()


def _fused_forward(q, k, v, save_residuals: bool):
    """o, or with ``save_residuals`` (o, l, m): the softmax's row sums
    and maxima, [B, H, S] fp32."""
    b = fused_block(q.shape[2])
    return _tpu_fa._flash_attention_impl(
        q, k, v, None, None, save_residuals, True, q.shape[3] ** -0.5,
        1, b, b, b, False)


def _fused_dq(q, k, v, l, m, do, di):
    """jax's dq kernel over blocks of ``fused_block``, fed dO.O and the
    softmax statistics at the one lane width it reads of them.  jax's own
    wrapper broadcasts dO.O across the whole k block in HBM, four times
    the bytes at a block of 512, and the compiler then moves an operand
    of the MLP's weight gradient out of VMEM."""
    B, H, S, D = q.shape
    b, lanes = fused_block(S), _tpu_fa.MIN_BLOCK_SIZE
    l, m, di = (jnp.broadcast_to(x[..., None], (*x.shape, lanes))
                for x in (l, m, di))

    def q_map(bi, hi, qi, ki):
        return bi, hi, qi, 0

    def kv_map(bi, hi, qi, ki):
        # a block above the diagonal is skipped: fetch block 0 instead
        return bi, hi, jax.lax.select(
            _tpu_fa.below_or_on_diag(qi, b, ki, b), ki, 0), 0

    q_spec = pl.BlockSpec((1, 1, b, D), q_map)
    kv_spec = pl.BlockSpec((1, 1, b, D), kv_map)
    stat_spec = pl.BlockSpec((1, 1, b, lanes), q_map)
    kernel = partial(_tpu_fa._flash_attention_dq_kernel, sm_scale=D ** -0.5,
                     causal=True, mask_value=_tpu_fa.DEFAULT_MASK_VALUE,
                     block_k=b, kv_seq_len=S)
    with jax.named_scope("flash_mha_bwd_dq"):
        dq, _ = pl.pallas_call(
            kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=0, grid=(B, H, S // b, S // b),
                in_specs=[q_spec, kv_spec, kv_spec, None, None, None,
                          stat_spec, stat_spec, q_spec, stat_spec],
                out_specs=[q_spec, None],
                scratch_shapes=[pltpu.VMEM((b, D), jnp.float32)]),
            out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), None],
            compiler_params=pltpu.CompilerParams(dimension_semantics=(
                "parallel", "parallel", "parallel", "arbitrary")),
        )(q, k, v, None, None, None, l, m, do, di)
    return dq


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _fused_causal(q, k, v, interpret: bool):
    """[B, H, S, D] causal attention through jax's fused flash kernel."""
    with _interpreting(interpret):
        return _fused_forward(q, k, v, False)


def _fused_causal_fwd(q, k, v, interpret):
    with _interpreting(interpret):
        o, l, m = _fused_forward(q, k, v, True)
    return o, (q, k, v, o, l, m)


def _fused_causal_bwd(interpret, res, do):
    q, k, v, o, l, m = res
    b = _dkv_block(q.shape[2])
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    with _interpreting(interpret):
        dk, dv = _tpu_fa._flash_attention_bwd_dkv(
            q, k, v, None, None, l, m, do, di, block_q_major=b, block_q=b,
            block_k_major=b, block_k=b, sm_scale=q.shape[3] ** -0.5,
            causal=True, mask_value=_tpu_fa.DEFAULT_MASK_VALUE)
        return _fused_dq(q, k, v, l, m, do, di), dk, dv


_fused_causal.defvjp(_fused_causal_fwd, _fused_causal_bwd)


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None):
    """Model-layout flash attention.  q: [B, Sq, H, Dk]; k/v: [B, Sk, KV, D*].

    Where ``fused_attention_takes`` the shapes, the fused causal kernels
    run, and the call is differentiable.  Otherwise the forward-only kernel of
    kernels/flash_attention.py runs over ``block_q`` x ``block_k`` tiles:
    seq padded to block multiples and head_dim to a lane multiple (128),
    in [B, H, S, D] layout, unpadded after."""
    if interpret is None:
        interpret = _default_interpret()
    if fused_attention_takes(q.shape, k.shape, v.shape, causal=causal,
                             window=window):
        o = _fused_causal(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                          v.transpose(0, 2, 1, 3), interpret)
        return o.transpose(0, 2, 1, 3)
    B, Sq, H, Dk = q.shape
    Sk, Dv = k.shape[1], v.shape[-1]
    qT = _pad_axis(_pad_axis(q.transpose(0, 2, 1, 3), block_q, 2), 128, 3)
    kT = _pad_axis(_pad_axis(k.transpose(0, 2, 1, 3), block_k, 2), 128, 3)
    vT = _pad_axis(_pad_axis(v.transpose(0, 2, 1, 3), block_k, 2), 128, 3)
    # padded kv positions past Sk are masked in-kernel via the static
    # kv_len key-validity mask — causality alone only hides them for
    # causal inputs, so the non-causal path needs it too.
    o = _fa.flash_attention_bhsd(qT, kT, vT, causal=causal, window=window,
                                 block_q=min(block_q, qT.shape[2]),
                                 block_k=min(block_k, kT.shape[2]),
                                 scale=1.0 / (Dk ** 0.5), kv_len=Sk,
                                 interpret=interpret)
    o = o.transpose(0, 2, 1, 3)[:, :Sq, :, :Dv]
    return o.astype(q.dtype)


# ------------------------------------------------------------------ #
# int8 quantization (kernels/quantized.py; docs/quantization.md)
# ------------------------------------------------------------------ #

@partial(jax.jit, static_argnames=("block", "axis"))
def quantize(x, *, block: int = 128, axis: int = -1):
    """Symmetric per-block absmax int8 quantization along ``axis``:
    scale = absmax/127 per block of ``block`` elements (all-zero blocks
    take scale 1.0).  Returns (q int8, x.shape) and (scale fp32, with
    the ``axis`` dim shrunk to ceil(n/block))."""
    axis = axis % x.ndim
    n = x.shape[axis]
    xm = jnp.moveaxis(x, axis, -1).astype(jnp.float32)
    pad = (-n) % block
    if pad:
        xm = jnp.pad(xm, [(0, 0)] * (xm.ndim - 1) + [(0, pad)])
    nb = xm.shape[-1] // block
    t = xm.reshape(xm.shape[:-1] + (nb, block))
    absmax = jnp.max(jnp.abs(t), axis=-1)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(t / scale[..., None]), -127, 127)
    q = q.astype(jnp.int8).reshape(xm.shape)[..., :n]
    return jnp.moveaxis(q, -1, axis), jnp.moveaxis(scale, -1, axis)


@partial(jax.jit, static_argnames=("block", "axis"))
def dequantize(q, scale, *, block: int = 128, axis: int = -1):
    """Inverse of ``quantize``: q int8 * per-block scale -> fp32."""
    axis = axis % q.ndim
    n = q.shape[axis]
    qm = jnp.moveaxis(q, axis, -1).astype(jnp.float32)
    sm = jnp.repeat(jnp.moveaxis(scale, axis, -1), block, axis=-1)[..., :n]
    return jnp.moveaxis(qm * sm, -1, axis)


@partial(jax.jit, static_argnames=("block_m", "block_k", "block_n",
                                   "interpret"))
def int8_matmul(x, w, *, block_m: int = 128, block_k: int = 128,
                block_n: int = 128, interpret: bool | None = None):
    """Quantize fp x [M, K] and w [K, N] into per-tile int8 and multiply
    with the Pallas kernel (int32 MXU accumulate, fp32 dequant epilogue).
    Pads to block multiples (zero pads quantize to 0 and contribute
    nothing), unpads.  Returns fp32 [M, N]."""
    if interpret is None:
        interpret = _default_interpret()
    M, K = x.shape
    N = w.shape[1]
    xp = _pad_axis(_pad_axis(x, block_m, 0), block_k, 1)
    wp = _pad_axis(_pad_axis(w, block_k, 0), block_n, 1)
    xq, xs = _q.quantize_blocks(xp, block_m, block_k)
    wq, ws = _q.quantize_blocks(wp, block_k, block_n)
    out = _q.int8_matmul_blocked(xq, xs, wq, ws, block_m=block_m,
                                 block_k=block_k, block_n=block_n,
                                 interpret=interpret)
    return out[:M, :N]


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                   "interpret"))
def flash_attention_int8kv(q, k_q, k_scale, v_q, v_scale, *, valid=None,
                           causal: bool = True, window: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """Model-layout attention over int8-quantized keys/values.
    q: [B, Sq, H, Dk] fp; k_q/v_q: [B, Sk, KV, D*] int8 with per-token
    absmax scales k_scale/v_scale: [B, Sk, KV] fp32 (``quantize`` over
    the head dim, one block); valid: optional [B, Sk], >0 = key live —
    traced, so the decode ring-cache fill state can flow through it.
    Pads seq/head_dim, dequantizes in-kernel, unpads."""
    if interpret is None:
        interpret = _default_interpret()
    B, Sq, H, Dk = q.shape
    Sk, Dv = k_q.shape[1], v_q.shape[-1]
    if valid is None:
        valid = jnp.ones((B, Sk), jnp.float32)
    qT = _pad_axis(_pad_axis(q.transpose(0, 2, 1, 3), block_q, 2), 128, 3)
    kT = _pad_axis(_pad_axis(k_q.transpose(0, 2, 1, 3), block_k, 2), 128, 3)
    vT = _pad_axis(_pad_axis(v_q.transpose(0, 2, 1, 3), block_k, 2), 128, 3)
    ksT = _pad_axis(k_scale.transpose(0, 2, 1)[:, :, None], block_k, 3)
    vsT = _pad_axis(v_scale.transpose(0, 2, 1)[:, :, None], block_k, 3)
    validp = _pad_axis(valid.astype(jnp.float32)[:, None], block_k,
                       2)                                 # pad => dead
    o = _q.flash_attention_int8kv_bhsd(
        qT, kT, ksT, vT, vsT, validp, causal=causal, window=window,
        block_q=min(block_q, qT.shape[2]), block_k=min(block_k, kT.shape[2]),
        scale=1.0 / (Dk ** 0.5), interpret=interpret)
    o = o.transpose(0, 2, 1, 3)[:, :Sq, :, :Dv]
    return o.astype(q.dtype)


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(xh, dt, b_s, c_s, a, *, chunk: int = 64,
             interpret: bool | None = None):
    """Model-layout SSD.  xh: [B, S, nh, hd]; dt: [B, S, nh];
    b_s/c_s: [B, S, ds]; a: [nh].  Returns (y [B,S,nh,hd] fp32, h_last)."""
    if interpret is None:
        interpret = _default_interpret()
    B, S, nh, hd = xh.shape
    x_t = _pad_axis(xh.transpose(0, 2, 1, 3), chunk, 2)       # [B,nh,S,hd]
    dt_t = _pad_axis(dt.transpose(0, 2, 1), chunk, 2)         # [B,nh,S]
    b_p = _pad_axis(b_s, chunk, 1)
    c_p = _pad_axis(c_s, chunk, 1)
    y, h_last = _scan.ssd_scan(x_t, dt_t, b_p, c_p, a, chunk=chunk,
                               interpret=interpret)
    return y[:, :, :S].transpose(0, 2, 1, 3), h_last


@partial(jax.jit, static_argnames=("chunk", "interpret"))
def mamba1_scan(x, dt, b_s, c_s, A, *, chunk: int = 64,
                interpret: bool | None = None):
    """x/dt: [B, S, di]; b_s/c_s: [B, S, ds]; A: [di, ds]."""
    if interpret is None:
        interpret = _default_interpret()
    S = x.shape[1]
    y, h_last = _scan.mamba1_scan(
        _pad_axis(x, chunk, 1), _pad_axis(dt, chunk, 1),
        _pad_axis(b_s, chunk, 1), _pad_axis(c_s, chunk, 1), A,
        chunk=chunk, interpret=interpret)
    return y[:, :S], h_last


# ------------------------------------------------------------------ #
# model-facing adapters (called from repro.models.* when use_pallas=True)
# ------------------------------------------------------------------ #

def ssd_scan_op(xh, delta, B_s, C_s, A, h0, *, chunk: int):
    """Adapter matching models.ssm._ssd_chunk_scan's signature.
    h0 is assumed zero at train time (kernel owns the carry)."""
    y, h_last = ssd_scan(xh, delta, B_s, C_s, A, chunk=chunk)
    return y, h_last


def mamba1_scan_op(x_conv, z, params, cfg, h0, *, chunk: int):
    """Adapter matching models.ssm._mamba1_inner: projects dt/B/C itself and
    applies D skip + gate, mirroring the jnp path."""
    dt_x = x_conv.dtype
    dt_rank = params["dt_proj"].shape[0]
    ds = cfg.ssm.d_state
    proj = jnp.einsum("bsc,cr->bsr", x_conv, params["x_proj"].astype(dt_x))
    dt_raw, B_s, C_s = jnp.split(proj, [dt_rank, dt_rank + ds], axis=-1)
    delta = jax.nn.softplus(
        jnp.einsum("bsr,rc->bsc", dt_raw, params["dt_proj"].astype(dt_x))
        .astype(jnp.float32) + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))
    y, h_last = mamba1_scan(x_conv.astype(jnp.float32), delta,
                            B_s.astype(jnp.float32), C_s.astype(jnp.float32),
                            A, chunk=chunk)
    y = y + params["D"].astype(jnp.float32) * x_conv.astype(jnp.float32)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    return y.astype(dt_x), h_last


@partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def rmsnorm(x, weight, *, eps: float = 1e-5, block_rows: int = 256,
            interpret: bool | None = None):
    """Fused RMSNorm (kernels/rmsnorm.py)."""
    from repro.kernels import rmsnorm as _rn
    if interpret is None:
        interpret = _default_interpret()
    return _rn.rmsnorm(x, weight, eps=eps, block_rows=block_rows,
                       interpret=interpret)
