"""Int8 quantization: kernels/quantized.py vs the fp32 oracles in
kernels/ref.py (interpret mode on CPU), the absmax round-trip error
contract, and the quantized KV-cache serving path (docs/quantization.md)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prophelpers import given, settings, st

from repro.kernels import ops, ref


def _mk(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape).astype(np.float32), dtype)


# ------------------------------------------------------------------ #
# absmax quantize / dequantize round trip
# ------------------------------------------------------------------ #

def test_quantize_shapes_and_blocking():
    rng = np.random.default_rng(0)
    x = _mk(rng, (6, 70))
    q, s = ops.quantize(x, block=32, axis=-1)
    assert q.dtype == jnp.int8 and q.shape == x.shape
    assert s.dtype == jnp.float32 and s.shape == (6, 3)   # ceil(70/32)
    back = ops.dequantize(q, s, block=32, axis=-1)
    # per-element error <= its block's scale / 2 (round-to-nearest)
    scale_full = np.asarray(ops.dequantize(
        jnp.ones_like(q), s, block=32, axis=-1))
    err = np.abs(np.asarray(back) - np.asarray(x))
    assert np.all(err <= scale_full * 0.5 + 1e-7)


def test_quantize_zero_block_is_exact():
    x = jnp.zeros((4, 64))
    q, s = ops.quantize(x, block=32)
    assert np.all(np.asarray(s) == 1.0)          # zero blocks: scale 1.0
    assert np.all(np.asarray(ops.dequantize(q, s, block=32)) == 0.0)


def test_quantize_non_last_axis():
    rng = np.random.default_rng(1)
    x = _mk(rng, (40, 3, 5))
    q, s = ops.quantize(x, block=16, axis=0)
    assert q.shape == x.shape and s.shape == (3, 3, 5)
    back = ops.dequantize(q, s, block=16, axis=0)
    bound = float(jnp.max(jnp.abs(x))) / 254.0 + 1e-7
    assert float(jnp.max(jnp.abs(back - x))) <= bound


@settings(max_examples=20, deadline=None)
@given(rows=st.integers(1, 40), cols=st.integers(1, 90),
       block=st.sampled_from([8, 16, 32, 128]),
       scale_pow=st.integers(-3, 3),
       seed=st.integers(0, 2**31 - 1))
def test_quantize_roundtrip_property(rows, cols, block, scale_pow, seed):
    """Property: |x - deq(quant(x))| <= absmax / 254 globally, at any
    magnitude (the per-block bound is tighter; this one always holds
    because block absmax <= global absmax)."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, cols)).astype(np.float32)
                    * (10.0 ** scale_pow))
    q, s = ops.quantize(x, block=block)
    back = ops.dequantize(q, s, block=block)
    bound = float(jnp.max(jnp.abs(x))) / 254.0 * (1 + 1e-6) + 1e-12
    assert float(jnp.max(jnp.abs(back - x))) <= bound


# ------------------------------------------------------------------ #
# int8 blocked matmul vs the fp32 oracle
# ------------------------------------------------------------------ #

MM_CASES = [
    # (M, K, N, block)
    (64, 64, 64, 32),
    (128, 128, 128, 128),      # single tile per grid cell
    (100, 70, 52, 32),         # every dim pads
    (30, 20, 10, 16),          # tiny, all-pad path
]


@pytest.mark.parametrize("M,K,N,blk", MM_CASES)
def test_int8_matmul_error_bound(M, K, N, blk):
    rng = np.random.default_rng(2)
    x = _mk(rng, (M, K))
    w = _mk(rng, (K, N))
    out = np.asarray(ops.int8_matmul(x, w, block_m=blk, block_k=blk,
                                     block_n=blk, interpret=True))
    want = np.asarray(ref.matmul_ref(x, w))
    rel = np.linalg.norm(out - want) / np.linalg.norm(want)
    assert rel < 0.02, rel


def test_int8_matmul_matches_explicit_dequant():
    """The kernel must equal the same quantized operands multiplied in
    fp32 after dequantization — the scales are applied per K block, not
    once at the end."""
    from repro.kernels.quantized import quantize_blocks
    rng = np.random.default_rng(3)
    x = _mk(rng, (64, 96))
    w = _mk(rng, (96, 64))
    out = np.asarray(ops.int8_matmul(x, w, block_m=32, block_k=32,
                                     block_n=32, interpret=True))
    xq, xs = quantize_blocks(x, 32, 32)
    wq, ws = quantize_blocks(w, 32, 32)
    xd = np.asarray(xq, np.float32).reshape(2, 32, 3, 32) \
        * np.asarray(xs)[:, None, :, None]
    wd = np.asarray(wq, np.float32).reshape(3, 32, 2, 32) \
        * np.asarray(ws)[:, None, :, None]
    want = xd.reshape(64, 96) @ wd.reshape(96, 64)
    np.testing.assert_allclose(out, want, atol=1e-4, rtol=1e-5)


# ------------------------------------------------------------------ #
# int8-KV flash attention
# ------------------------------------------------------------------ #

def _quant_tokens(x):
    """[B, S, KV, D] -> (int8, scales [B, S, KV]) per-token over head dim."""
    q, s = ops.quantize(x, block=x.shape[-1], axis=-1)
    return q, s


KV_CASES = [
    # (B, S, H, KV, D, causal, window)
    (2, 64, 4, 2, 32, True, 0),
    (1, 40, 2, 2, 16, True, 0),       # Sk % block_k != 0 => pad path
    (2, 96, 8, 2, 48, True, 32),      # GQA + window
    (1, 40, 2, 1, 32, False, 0),      # non-causal + pad: the mask matters
]


@pytest.mark.parametrize("B,S,H,KV,D,causal,window", KV_CASES)
def test_int8kv_attention_vs_dequant_ref(B, S, H, KV, D, causal, window):
    """Near-exact vs attention_ref over the dequantized k/v — isolates
    the kernel from the quantization error."""
    rng = np.random.default_rng(4)
    q = _mk(rng, (B, S, H, D))
    k = _mk(rng, (B, S, KV, D))
    v = _mk(rng, (B, S, KV, D))
    kq, ks = _quant_tokens(k)
    vq, vs = _quant_tokens(v)
    out = ops.flash_attention_int8kv(
        q, kq, ks[..., 0], vq, vs[..., 0], causal=causal, window=window,
        block_q=32, block_k=32, interpret=True)
    kd = ops.dequantize(kq, ks, block=D, axis=-1)
    vd = ops.dequantize(vq, vs, block=D, axis=-1)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3), kd.transpose(0, 2, 1, 3),
        vd.transpose(0, 2, 1, 3), causal=causal,
        window=window).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_int8kv_attention_cosine_gate():
    """End-to-end quantization error: outputs stay within cosine 0.999
    of the pure-fp32 attention."""
    rng = np.random.default_rng(5)
    B, S, H, D = 2, 64, 4, 32
    q = _mk(rng, (B, S, H, D))
    k = _mk(rng, (B, S, H, D))
    v = _mk(rng, (B, S, H, D))
    kq, ks = _quant_tokens(k)
    vq, vs = _quant_tokens(v)
    out = np.asarray(ops.flash_attention_int8kv(
        q, kq, ks[..., 0], vq, vs[..., 0], causal=True,
        block_q=32, block_k=32, interpret=True)).reshape(-1)
    pure = np.asarray(ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3),
        causal=True).transpose(0, 2, 1, 3)).reshape(-1)
    cos = np.dot(out, pure) / (np.linalg.norm(out) * np.linalg.norm(pure))
    assert cos > 0.999, cos


def test_int8kv_valid_mask_truncates_keys():
    """The dynamic validity input must reproduce attention over the
    truncated key set — the decode ring-cache contract (non-causal, a
    traced number of live slots)."""
    rng = np.random.default_rng(6)
    B, S, H, D, live = 1, 48, 2, 16, 33
    q = _mk(rng, (B, S, H, D))
    k = _mk(rng, (B, S, H, D))
    v = _mk(rng, (B, S, H, D))
    kq, ks = _quant_tokens(k)
    vq, vs = _quant_tokens(v)
    valid = jnp.asarray(
        (np.arange(S) < live)[None].astype(np.float32))
    out = ops.flash_attention_int8kv(
        q, kq, ks[..., 0], vq, vs[..., 0], valid=valid, causal=False,
        block_q=16, block_k=16, interpret=True)
    kd = ops.dequantize(kq, ks, block=D, axis=-1)
    vd = ops.dequantize(vq, vs, block=D, axis=-1)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3), kd[:, :live].transpose(0, 2, 1, 3),
        vd[:, :live].transpose(0, 2, 1, 3),
        causal=False).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_attention_noncausal_pad_regression():
    """Regression (ISSUE 6 satellite): ops.flash_attention with
    causal=False and Sk % block_k != 0 must mask the padded keys — the
    causal mask no longer hides them."""
    rng = np.random.default_rng(7)
    B, S, H, D = 1, 40, 2, 16           # 40 % 32 != 0
    q = _mk(rng, (B, S, H, D))
    k = _mk(rng, (B, S, H, D))
    v = _mk(rng, (B, S, H, D))
    out = ops.flash_attention(q, k, v, causal=False, block_q=32,
                              block_k=32, interpret=True)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=False).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ------------------------------------------------------------------ #
# the quantized KV-cache serving path (models/attention.py + serve)
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def tiny_model():
    from repro.configs import get_config
    from repro.models import Model
    cfg = dataclasses.replace(get_config("llama3.2-3b").reduced(),
                              vocab_size=512)
    model = Model(cfg)
    params = model.init(jax.random.key(0))
    return model, params


def test_quant_cache_ring_append():
    from repro.models.attention import (init_quant_kv_cache,
                                        quant_cache_append)
    cache = init_quant_kv_cache(1, 4, 1, 8, 8)
    assert cache.capacity == 4
    for t in range(6):
        k = jnp.full((1, 1, 1, 8), float(t + 1))
        cache = quant_cache_append(cache, k, k)
    assert int(cache.index) == 6
    # ring layout: slot s holds the latest token with pos % 4 == s
    deq = np.asarray(ops.dequantize(
        cache.k_q, cache.k_scale[..., None], block=8, axis=-1))
    np.testing.assert_allclose(deq[0, :, 0, 0], [5.0, 6.0, 3.0, 4.0],
                               rtol=1e-6)
    assert bool(np.all(np.asarray(cache.valid(1))))


def test_quant_cache_decode_matches_fp(tiny_model):
    """The int8-KV decode guard: greedy tokens must match the fp cache
    path exactly and per-step logits stay within a small delta (the
    serving-quality gate; docs/quantization.md)."""
    model, params = tiny_model
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(4, 400, (2, 12)), jnp.int32)
    c_fp = model.init_cache(2, 48)
    c_q = model.init_cache(2, 48, kv_dtype="int8")
    lg_fp, c_fp = model.prefill(params, {"tokens": toks}, c_fp)
    lg_q, c_q = model.prefill(params, {"tokens": toks}, c_q)
    # prefill logits come from full attention, identical by construction
    np.testing.assert_array_equal(np.asarray(lg_fp), np.asarray(lg_q))
    tok = jnp.argmax(lg_fp, -1)[:, None].astype(jnp.int32)
    for _ in range(4):
        lf, c_fp = model.decode_step(params, c_fp, tok)
        lq, c_q = model.decode_step(params, c_q, tok)
        assert float(jnp.max(jnp.abs(lf - lq))) < 0.25
        nf = jnp.argmax(lf, -1)
        nq = jnp.argmax(lq, -1)
        np.testing.assert_array_equal(np.asarray(nf), np.asarray(nq))
        tok = nf[:, None].astype(jnp.int32)


def test_init_cache_kv_dtype_gates(tiny_model):
    from repro.configs import get_config
    from repro.models import Model
    model, _ = tiny_model
    with pytest.raises(ValueError, match="unknown kv_dtype"):
        model.init_cache(1, 8, kv_dtype="int4")
    for arch in ("falcon-mamba-7b", "minicpm3-4b"):
        m = Model(get_config(arch).reduced())
        with pytest.raises(ValueError, match="plain-GQA"):
            m.init_cache(1, 8, kv_dtype="int8")


def test_engine_int8_kv(tiny_model):
    """End-to-end: the Engine carries the quantized cache through the
    compiled prefill/serve steps and generates the same greedy tokens."""
    from repro.core.plans import get_plan
    from repro.launch.mesh import make_mesh
    from repro.serve import Engine
    model, params = tiny_model
    mesh = make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(0)
    prompts = np.asarray(rng.integers(4, 400, (2, 12)), np.int32)
    out_fp = Engine(model, get_plan("data"), mesh, batch_size=2,
                    max_len=48).generate(
                        params, {"tokens": prompts}, n_tokens=5)
    out_q = Engine(model, get_plan("data"), mesh, batch_size=2,
                   max_len=48, kv_dtype="int8").generate(
                       params, {"tokens": prompts}, n_tokens=5)
    np.testing.assert_array_equal(out_fp["tokens"], out_q["tokens"])
