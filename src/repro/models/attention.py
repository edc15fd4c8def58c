"""Attention: chunked flash-style causal GQA, sliding windows, decode over
KV caches, and Multi-head Latent Attention (MLA) with an absorbed-matmul
latent-cache decode path.

The chunked implementation is the memory-bounded pure-jnp path and the
oracle for the Pallas kernels.  On a TPU, plain causal self-attention
whose shapes the fused kernel takes (``kernels.ops.fused_attention_takes``)
runs through that kernel, forward and backward, wherever the program is
built for one device; ``use_pallas`` in the model call swaps in
kernels/flash_attention.py for the rest.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import AxisType

from repro import tracing
from repro.configs.base import MLAConfig, ModelConfig
from repro.models.layers import apply_rope, dense_init

NEG_INF = -1e30


# --------------------------------------------------------------------- #
# chunked (flash-style) attention over full sequences
# --------------------------------------------------------------------- #

def _pad_to_multiple(x, mult: int, axis: int):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x, size
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), size


def _one_device_program() -> bool:
    """Whether the program being traced runs on one device, or this is
    the per-device body of a ``shard_map``.  Under GSPMD's partitioning
    a kernel's custom call would gather its operands."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty:
        return jax.device_count() == 1
    return all(n == 1 or t == AxisType.Manual
               for n, t in zip(mesh.axis_sizes, mesh.axis_types))


def _fused_applies(q, k, v, *, causal: bool, window: int, q_positions,
                   kv_positions) -> bool:
    """Whether the fused kernel computes this call: on a TPU, for shapes
    it takes, with the plain causal mask (no positions of the caller's
    own), in a program built for one device."""
    from repro.kernels import ops as kernel_ops
    return (q_positions is None and kv_positions is None
            and jax.default_backend() == "tpu"
            and kernel_ops.fused_attention_takes(
                q.shape, k.shape, v.shape, causal=causal, window=window)
            and _one_device_program())


def chunked_attention(
    q, k, v, *,
    causal: bool = True,
    window: int = 0,
    q_positions=None,
    kv_positions=None,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    use_pallas: bool = False,
):
    """Memory-bounded attention.

    q: [B, Sq, H, Dk]; k: [B, Sk, KV, Dk]; v: [B, Sk, KV, Dv]; H % KV == 0.
    Positions of None are the plain ``arange`` of every row.  Where
    ``_fused_applies``, the fused flash kernel computes it; otherwise
    softmax accumulates in fp32 with the online max/denominator
    recurrence over q and kv chunks.  Returns [B, Sq, H, Dv].
    """
    fused = _fused_applies(q, k, v, causal=causal, window=window,
                           q_positions=q_positions, kv_positions=kv_positions)
    tracing.note(tracing.FUSED_ATTENTION if fused
                 else tracing.CHUNKED_ATTENTION)
    if fused or use_pallas:
        from repro.kernels import ops as kernel_ops
        return kernel_ops.flash_attention(
            q, k, v, causal=causal, window=window)

    B, Sq, H, Dk = q.shape
    _, Sk, KV, Dv = v.shape
    group = H // KV
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dk, jnp.float32))

    if q_positions is None:
        q_positions = jnp.arange(Sq, dtype=jnp.int32)[None, :]
    if kv_positions is None:
        kv_positions = jnp.arange(Sk, dtype=jnp.int32)[None, :]
    q_positions = jnp.broadcast_to(q_positions, (B, Sq))
    kv_positions = jnp.broadcast_to(kv_positions, (B, Sk))

    q_chunk = min(q_chunk, Sq)
    k_chunk = min(k_chunk, Sk)
    q, _ = _pad_to_multiple(q, q_chunk, 1)
    qpos, _ = _pad_to_multiple(q_positions, q_chunk, 1)
    k, _ = _pad_to_multiple(k, k_chunk, 1)
    v, _ = _pad_to_multiple(v, k_chunk, 1)
    # padded kv slots get position +inf-ish so the causal mask kills them
    kpos = jnp.pad(kv_positions, ((0, 0), (0, (-Sk) % k_chunk)),
                   constant_values=jnp.iinfo(jnp.int32).max)
    nq, nk = q.shape[1] // q_chunk, k.shape[1] // k_chunk

    qc = q.reshape(B, nq, q_chunk, H, Dk).transpose(1, 0, 2, 3, 4)
    qp = qpos.reshape(B, nq, q_chunk).transpose(1, 0, 2)
    kc = k.reshape(B, nk, k_chunk, KV, Dk).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nk, k_chunk, KV, Dv).transpose(1, 0, 2, 3, 4)
    kp = kpos.reshape(B, nk, k_chunk).transpose(1, 0, 2)

    def q_block(carry, q_in):
        qi, qpi = q_in  # [B, Cq, H, Dk], [B, Cq]
        qi32 = (qi.astype(jnp.float32) * scale).reshape(
            B, q_chunk, KV, group, Dk)

        @jax.checkpoint
        def kv_block(acc, kv_in):
            m, l, o = acc
            kj, vj, kpj = kv_in
            s = jnp.einsum("bqkgd,bjkd->bkgqj", qi32, kj.astype(jnp.float32))
            if causal:  # mask: [B, Cq, Cj]
                mask = qpi[:, :, None] >= kpj[:, None, :]
                if window:
                    mask &= (qpi[:, :, None] - kpj[:, None, :]) < window
            else:  # only mask padded kv slots
                mask = jnp.broadcast_to(
                    (kpj < jnp.iinfo(jnp.int32).max)[:, None, :],
                    (B, q_chunk, kpj.shape[1]))
            s = jnp.where(mask[:, None, None, :, :], s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            o_new = o * corr[..., None] + jnp.einsum(
                "bkgqj,bjkd->bkgqd", p, vj.astype(jnp.float32))
            return (m_new, l_new, o_new), None

        acc0 = (
            jnp.full((B, KV, group, q_chunk), NEG_INF, jnp.float32),
            jnp.zeros((B, KV, group, q_chunk), jnp.float32),
            jnp.zeros((B, KV, group, q_chunk, Dv), jnp.float32),
        )
        (m, l, o), _ = jax.lax.scan(kv_block, acc0, (kc, vc, kp))
        out = o / jnp.maximum(l, 1e-30)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, q_chunk, H, Dv)
        return carry, out.astype(q.dtype)

    # remat on both scan levels: without it AD saves the fp32 [Cq, Ck]
    # probability chunks for every (q, kv) block pair — the O(S²) memory
    # that flash attention exists to avoid (the Pallas kernel does this
    # structurally; this is the jnp path's equivalent).
    q_block = jax.checkpoint(q_block)
    _, out = jax.lax.scan(q_block, None, (qc, qp))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, nq * q_chunk, H, Dv)
    return out[:, :Sq]


# --------------------------------------------------------------------- #
# decode attention over a (possibly ring-buffered) KV cache
# --------------------------------------------------------------------- #

def _ring_valid(index, batch: int, capacity: int):
    """Filled-slot mask [batch, capacity] for a ring index that is either
    a scalar (one write position shared by the whole batch — the fixed
    -batch engine) or per-slot ``[batch]`` (continuous batching, where
    every slot tracks its own fill; serve/engine.ContinuousEngine)."""
    slots = jnp.arange(capacity, dtype=jnp.int32)
    filled = jnp.minimum(index, capacity)
    if index.ndim == 0:
        return jnp.broadcast_to(slots[None, :] < filled, (batch, capacity))
    return slots[None, :] < filled[:, None]


def _append_token(buf, new, slot):
    """Write one token's row (``new``: [B, 1, ...]) into ``buf``
    ([B, S, ...]) at ring position ``slot`` — a scalar (shared index) or
    per-slot ``[B]`` vector (each batch row writes its own position)."""
    new = new.astype(buf.dtype)
    if slot.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(buf, new, slot, 1)
    return jax.vmap(
        lambda b, n, s: jax.lax.dynamic_update_slice_in_dim(b, n, s, 0)
    )(buf, new, slot)


def _decode_positions(index):
    """RoPE positions [*, 1] of the token being decoded: the cache index
    broadcast ([1, 1]) for a scalar index, per-slot [B, 1] otherwise."""
    return index[None, None] if index.ndim == 0 else index[:, None]


def decode_attention(q, k_cache, v_cache, valid_mask):
    """One-token attention. q: [B, 1, H, Dk]; caches [B, S, KV, D*];
    valid_mask: [B, S] bool marking filled slots."""
    B, _, H, Dk = q.shape
    KV = k_cache.shape[2]
    group = H // KV
    scale = 1.0 / jnp.sqrt(jnp.asarray(Dk, jnp.float32))
    qf = (q.astype(jnp.float32) * scale).reshape(B, KV, group, Dk)
    s = jnp.einsum("bkgd,bskd->bkgs", qf, k_cache.astype(jnp.float32))
    s = jnp.where(valid_mask[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, -1).astype(q.dtype)


class KVCache(NamedTuple):
    """Ring-buffered KV cache (window=0 => plain cache of full length)."""
    k: jax.Array          # [B, S, KV, Dk]
    v: jax.Array          # [B, S, KV, Dv]
    index: jax.Array      # int32 next write position (total tokens):
                          # scalar (shared) or [B] (per-slot, continuous
                          # batching)

    @property
    def capacity(self) -> int:
        return self.k.shape[1]

    def slot_positions(self):
        """Absolute position stored in each slot; -1 for empty slots."""
        S = self.capacity
        slots = jnp.arange(S, dtype=jnp.int32)
        n = self.index
        # slot s holds position: the largest p < n with p % S == s
        last = n - 1 - (n - 1 - slots) % S
        return jnp.where(slots < jnp.minimum(n, S), jnp.where(
            last >= 0, last, -1), jnp.where(last >= n - S, last, -1))

    def valid(self, batch: int):
        return _ring_valid(self.index, batch, self.capacity)


def init_kv_cache(batch: int, capacity: int, kv_heads: int, dk: int, dv: int,
                  dtype) -> KVCache:
    return KVCache(
        k=jnp.zeros((batch, capacity, kv_heads, dk), dtype),
        v=jnp.zeros((batch, capacity, kv_heads, dv), dtype),
        index=jnp.zeros((), jnp.int32),
    )


def cache_append(cache: KVCache, k_new, v_new) -> KVCache:
    """Append one token (k_new/v_new: [B, 1, KV, D]) at the ring position."""
    slot = jnp.mod(cache.index, cache.capacity)
    k = _append_token(cache.k, k_new, slot)
    v = _append_token(cache.v, v_new, slot)
    return KVCache(k=k, v=v, index=cache.index + 1)


# --------------------------------------------------------------------- #
# int8-quantized KV cache (docs/quantization.md): per-(token, head)
# absmax scales over head_dim; decode attends through the int8-KV Pallas
# kernel (kernels/quantized.py) with the ring fill state as its dynamic
# key-validity mask
# --------------------------------------------------------------------- #

class QuantKVCache(NamedTuple):
    """Ring-buffered int8 KV cache — 4x smaller than the fp32 ``KVCache``
    at the cost of one absmax scale per (token, kv-head)."""
    k_q: jax.Array        # [B, S, KV, Dk] int8
    k_scale: jax.Array    # [B, S, KV] fp32
    v_q: jax.Array        # [B, S, KV, Dv] int8
    v_scale: jax.Array    # [B, S, KV] fp32
    index: jax.Array      # int32 next write position: scalar or [B]

    @property
    def capacity(self) -> int:
        return self.k_q.shape[1]

    def valid(self, batch: int):
        return _ring_valid(self.index, batch, self.capacity)


def init_quant_kv_cache(batch: int, capacity: int, kv_heads: int, dk: int,
                        dv: int) -> QuantKVCache:
    return QuantKVCache(
        k_q=jnp.zeros((batch, capacity, kv_heads, dk), jnp.int8),
        k_scale=jnp.ones((batch, capacity, kv_heads), jnp.float32),
        v_q=jnp.zeros((batch, capacity, kv_heads, dv), jnp.int8),
        v_scale=jnp.ones((batch, capacity, kv_heads), jnp.float32),
        index=jnp.zeros((), jnp.int32),
    )


def _quant_kv(x):
    """[B, S, KV, D] fp -> (int8 payload, [B, S, KV] fp32 scales): one
    absmax block spanning the whole head_dim per (token, kv-head)."""
    from repro.kernels import ops as kernel_ops
    q, s = kernel_ops.quantize(x, block=x.shape[-1], axis=-1)
    return q, s[..., 0]


def quant_cache_append(cache: QuantKVCache, k_new, v_new) -> QuantKVCache:
    """Quantize + append one token (k_new/v_new: [B, 1, KV, D])."""
    slot = jnp.mod(cache.index, cache.capacity)
    kq, ks = _quant_kv(k_new)
    vq, vs = _quant_kv(v_new)
    return QuantKVCache(
        k_q=_append_token(cache.k_q, kq, slot),
        k_scale=_append_token(cache.k_scale, ks, slot),
        v_q=_append_token(cache.v_q, vq, slot),
        v_scale=_append_token(cache.v_scale, vs, slot),
        index=cache.index + 1)


def _ring_fill(buf, new, S: int):
    """Prefill a ring buffer leaf: keep the most recent ``capacity``
    entries of ``new`` [B, S, ...] in slot = pos % capacity layout."""
    cap = buf.shape[1]
    if S >= cap:
        roll = -((S - cap) % cap) if cap else 0
        return jnp.roll(new[:, S - cap:], roll, axis=1).astype(buf.dtype)
    return jax.lax.dynamic_update_slice_in_dim(
        buf, new.astype(buf.dtype), 0, 1)


def quant_cache_prefill(cache: QuantKVCache, k, v, S: int) -> QuantKVCache:
    """Fill the quantized cache from full-sequence k/v [B, S, KV, D]."""
    kq, ks = _quant_kv(k)
    vq, vs = _quant_kv(v)
    return QuantKVCache(
        k_q=_ring_fill(cache.k_q, kq, S),
        k_scale=_ring_fill(cache.k_scale, ks, S),
        v_q=_ring_fill(cache.v_q, vq, S),
        v_scale=_ring_fill(cache.v_scale, vs, S),
        index=jnp.asarray(S, jnp.int32))


def quant_decode_attention(q, cache: QuantKVCache):
    """One-token attention over the int8 cache via the Pallas int8-KV
    kernel; the traced ring fill state rides the kernel's dynamic
    key-validity input.  Every cached token is in the past, so the mask
    alone (causal=False) reproduces ``decode_attention``'s semantics."""
    from repro.kernels import ops as kernel_ops
    B = q.shape[0]
    return kernel_ops.flash_attention_int8kv(
        q, cache.k_q, cache.k_scale, cache.v_q, cache.v_scale,
        valid=cache.valid(B).astype(jnp.float32), causal=False, block_q=8)


# --------------------------------------------------------------------- #
# standard GQA attention parameters
# --------------------------------------------------------------------- #

def init_attention(rng, cfg: ModelConfig):
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    r = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(r[0], (d, H, hd), d),
        "wk": dense_init(r[1], (d, KV, hd), d),
        "wv": dense_init(r[2], (d, KV, hd), d),
        "wo": dense_init(r[3], (H, hd, d), H * hd),
    }
    if cfg.norm == "layernorm":  # gpt2/whisper-style attention biases
        p["bq"] = jnp.zeros((H, hd))
        p["bk"] = jnp.zeros((KV, hd))
        p["bv"] = jnp.zeros((KV, hd))
        p["bo"] = jnp.zeros((d,))
    return p


def _qkv(x, params, cfg: ModelConfig):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, params["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, params["wv"].astype(dt))
    if "bq" in params:
        q = q + params["bq"].astype(dt)
        k = k + params["bk"].astype(dt)
        v = v + params["bv"].astype(dt)
    return q, k, v


def _out(o, params):
    dt = o.dtype
    y = jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))
    if "bo" in params:
        y = y + params["bo"].astype(dt)
    return y


def _plain_positions(positions, x):
    """``positions``, or the plain [1, S] ``arange`` where it is None."""
    if positions is None:
        return jnp.arange(x.shape[1], dtype=jnp.int32)[None]
    return positions


def attention_forward(x, params, cfg: ModelConfig, *, positions,
                      causal: bool = True, window: int = 0,
                      use_pallas: bool = False):
    """Full-sequence attention (train / prefill / encoder).  ``positions``
    None: every row is at the plain ``arange``, so the mask is plain."""
    with jax.named_scope(tracing.ATTENTION):
        q, k, v = _qkv(x, params, cfg)
        if cfg.rope_theta:
            pos = _plain_positions(positions, x)
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              q_positions=positions, kv_positions=positions,
                              use_pallas=use_pallas)
        return _out(o, params)


def attention_prefill(x, params, cfg: ModelConfig, *, positions,
                      cache: KVCache, window: int = 0):
    """Prefill: run full attention AND fill the cache with k/v."""
    q, k, v = _qkv(x, params, cfg)
    if cfg.rope_theta:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    o = chunked_attention(q, k, v, causal=True, window=window,
                          q_positions=positions, kv_positions=positions)
    S = x.shape[1]
    if isinstance(cache, QuantKVCache):
        return _out(o, params), quant_cache_prefill(cache, k, v, S)
    cap = cache.capacity
    if S >= cap:  # keep the most recent `cap` tokens
        k_keep, v_keep = k[:, S - cap:], v[:, S - cap:]
        # ring layout: slot = pos % cap
        roll = -((S - cap) % cap) if cap else 0
        k_keep = jnp.roll(k_keep, roll, axis=1)
        v_keep = jnp.roll(v_keep, roll, axis=1)
        new = KVCache(k=k_keep.astype(cache.k.dtype),
                      v=v_keep.astype(cache.v.dtype),
                      index=jnp.asarray(S, jnp.int32))
    else:
        k_full = jax.lax.dynamic_update_slice_in_dim(
            cache.k, k.astype(cache.k.dtype), 0, 1)
        v_full = jax.lax.dynamic_update_slice_in_dim(
            cache.v, v.astype(cache.v.dtype), 0, 1)
        new = KVCache(k=k_full, v=v_full, index=jnp.asarray(S, jnp.int32))
    return _out(o, params), new


def attention_decode(x, params, cfg: ModelConfig, *, cache: KVCache,
                     window: int = 0):
    """One-token decode: x [B, 1, d]."""
    B = x.shape[0]
    q, k, v = _qkv(x, params, cfg)
    pos = _decode_positions(cache.index)
    if cfg.rope_theta:
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)
    if isinstance(cache, QuantKVCache):
        cache = quant_cache_append(cache, k, v)
        o = quant_decode_attention(q, cache)
    else:
        cache = cache_append(cache, k, v)
        o = decode_attention(q, cache.k, cache.v, cache.valid(B))
    return _out(o, params), cache


# --------------------------------------------------------------------- #
# Multi-head Latent Attention (MiniCPM3 / DeepSeek-V2)
# --------------------------------------------------------------------- #

class MLACache(NamedTuple):
    c_kv: jax.Array      # [B, S, R] latent cache
    k_rope: jax.Array    # [B, S, rope_dim]
    index: jax.Array

    @property
    def capacity(self) -> int:
        return self.c_kv.shape[1]

    def valid(self, batch: int):
        return _ring_valid(self.index, batch, self.capacity)


def init_mla_cache(batch: int, capacity: int, mla: MLAConfig, dtype) -> MLACache:
    return MLACache(
        c_kv=jnp.zeros((batch, capacity, mla.kv_lora_rank), dtype),
        k_rope=jnp.zeros((batch, capacity, mla.rope_head_dim), dtype),
        index=jnp.zeros((), jnp.int32),
    )


def init_mla(rng, cfg: ModelConfig):
    m, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    r = jax.random.split(rng, 8)
    p = {}
    q_in = d
    if m.q_lora_rank:
        p["w_dq"] = dense_init(r[0], (d, m.q_lora_rank), d)
        p["q_norm"] = jnp.ones((m.q_lora_rank,))
        q_in = m.q_lora_rank
    p["w_uq"] = dense_init(r[1], (q_in, H, m.nope_head_dim + m.rope_head_dim), q_in)
    p["w_dkv"] = dense_init(r[2], (d, m.kv_lora_rank), d)
    p["kv_norm"] = jnp.ones((m.kv_lora_rank,))
    p["w_kr"] = dense_init(r[3], (d, m.rope_head_dim), d)
    p["w_uk"] = dense_init(r[4], (H, m.kv_lora_rank, m.nope_head_dim), m.kv_lora_rank)
    p["w_uv"] = dense_init(r[5], (H, m.kv_lora_rank, m.v_head_dim), m.kv_lora_rank)
    p["wo"] = dense_init(r[6], (H, m.v_head_dim, d), H * m.v_head_dim)
    return p


def _mla_q(x, params, cfg: ModelConfig, positions):
    from repro.models.layers import rmsnorm
    m, dt = cfg.mla, x.dtype
    if "w_dq" in params:
        cq = jnp.einsum("bsd,dr->bsr", x, params["w_dq"].astype(dt))
        cq = rmsnorm(cq, params["q_norm"], cfg.norm_eps)
    else:
        cq = x
    q = jnp.einsum("bsr,rhk->bshk", cq, params["w_uq"].astype(dt))
    q_nope = q[..., : m.nope_head_dim]
    q_rope = apply_rope(q[..., m.nope_head_dim:], positions, cfg.rope_theta)
    return q_nope, q_rope


def _mla_latent(x, params, cfg: ModelConfig, positions):
    from repro.models.layers import rmsnorm
    dt = x.dtype
    c_kv = jnp.einsum("bsd,dr->bsr", x, params["w_dkv"].astype(dt))
    c_kv = rmsnorm(c_kv, params["kv_norm"], cfg.norm_eps)
    k_rope = jnp.einsum("bsd,dk->bsk", x, params["w_kr"].astype(dt))
    k_rope = apply_rope(k_rope[:, :, None, :], positions,
                        cfg.rope_theta)[:, :, 0, :]
    return c_kv, k_rope


def mla_forward(x, params, cfg: ModelConfig, *, positions, window: int = 0,
                use_pallas: bool = False):
    """Full-sequence MLA: decompress K/V per head and run chunked attention."""
    with jax.named_scope(tracing.ATTENTION):
        m, dt = cfg.mla, x.dtype
        pos = _plain_positions(positions, x)
        q_nope, q_rope = _mla_q(x, params, cfg, pos)
        c_kv, k_rope = _mla_latent(x, params, cfg, pos)
        k_nope = jnp.einsum("bsr,hrk->bshk", c_kv, params["w_uk"].astype(dt))
        v = jnp.einsum("bsr,hrk->bshk", c_kv, params["w_uv"].astype(dt))
        H = cfg.n_heads
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (*k_rope.shape[:2], H,
                                       m.rope_head_dim))],
            axis=-1)
        o = chunked_attention(q, k, v, causal=True, window=window,
                              q_positions=positions, kv_positions=positions,
                              use_pallas=use_pallas)
        return jnp.einsum("bshk,hkd->bsd", o, params["wo"].astype(dt))


def mla_prefill(x, params, cfg: ModelConfig, *, positions, cache: MLACache,
                window: int = 0):
    out = mla_forward(x, params, cfg, positions=positions, window=window)
    c_kv, k_rope = _mla_latent(x, params, cfg, positions)
    S, cap = x.shape[1], cache.capacity
    if S >= cap:
        roll = -((S - cap) % cap) if cap else 0
        c_keep = jnp.roll(c_kv[:, S - cap:], roll, axis=1)
        r_keep = jnp.roll(k_rope[:, S - cap:], roll, axis=1)
        new = MLACache(c_kv=c_keep.astype(cache.c_kv.dtype),
                       k_rope=r_keep.astype(cache.k_rope.dtype),
                       index=jnp.asarray(S, jnp.int32))
    else:
        new = MLACache(
            c_kv=jax.lax.dynamic_update_slice_in_dim(
                cache.c_kv, c_kv.astype(cache.c_kv.dtype), 0, 1),
            k_rope=jax.lax.dynamic_update_slice_in_dim(
                cache.k_rope, k_rope.astype(cache.k_rope.dtype), 0, 1),
            index=jnp.asarray(S, jnp.int32))
    return out, new


def mla_decode(x, params, cfg: ModelConfig, *, cache: MLACache,
               window: int = 0):
    """Absorbed-matmul decode: scores computed directly in latent space, so
    the cache stays [B, S, kv_lora + rope] — MLA's memory win."""
    m, dt = cfg.mla, x.dtype
    B = x.shape[0]
    pos = _decode_positions(cache.index)
    q_nope, q_rope = _mla_q(x, params, cfg, pos)          # [B,1,H,*]
    c_new, r_new = _mla_latent(x, params, cfg, pos)       # [B,1,R], [B,1,rope]
    slot = jnp.mod(cache.index, cache.capacity)
    cache = MLACache(
        c_kv=_append_token(cache.c_kv, c_new, slot),
        k_rope=_append_token(cache.k_rope, r_new, slot),
        index=cache.index + 1)
    # absorb W_uk into q: q_lat[h] = q_nope[h] @ W_uk[h]
    q_lat = jnp.einsum("bqhk,hrk->bqhr", q_nope, params["w_uk"].astype(dt))
    scale = 1.0 / jnp.sqrt(jnp.asarray(m.nope_head_dim + m.rope_head_dim,
                                       jnp.float32))
    s = jnp.einsum("bqhr,bsr->bhqs", q_lat.astype(jnp.float32),
                   cache.c_kv.astype(jnp.float32))
    s += jnp.einsum("bqhk,bsk->bhqs", q_rope.astype(jnp.float32),
                    cache.k_rope.astype(jnp.float32))
    s = s * scale
    s = jnp.where(cache.valid(B)[:, None, None, :], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1)
    ctx_lat = jnp.einsum("bhqs,bsr->bqhr", w, cache.c_kv.astype(jnp.float32))
    o = jnp.einsum("bqhr,hrk->bqhk", ctx_lat.astype(dt),
                   params["w_uv"].astype(dt))
    return jnp.einsum("bqhk,hkd->bqd", o, params["wo"].astype(dt)), cache
