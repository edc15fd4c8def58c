"""Pallas kernels vs pure-jnp oracles (interpret mode on CPU), swept over
shapes and dtypes per the assignment, plus hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from prophelpers import given, settings, st

from repro.kernels import ops, ref


def _mk(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


# ------------------------------------------------------------------ #
# flash attention
# ------------------------------------------------------------------ #

FLASH_CASES = [
    # (B, S, H, KV, D, window, dtype)
    (2, 64, 4, 2, 32, 0, jnp.float32),
    (1, 128, 4, 4, 64, 0, jnp.float32),
    (2, 96, 8, 2, 48, 32, jnp.float32),    # GQA + window + padding
    (1, 64, 2, 1, 128, 0, jnp.float32),    # MQA
    (2, 64, 4, 2, 32, 0, jnp.bfloat16),
    (1, 256, 2, 2, 64, 64, jnp.bfloat16),
]


@pytest.mark.parametrize("B,S,H,KV,D,window,dtype", FLASH_CASES)
def test_flash_attention_vs_ref(B, S, H, KV, D, window, dtype):
    rng = np.random.default_rng(0)
    q = _mk(rng, (B, S, H, D), dtype)
    k = _mk(rng, (B, S, KV, D), dtype)
    v = _mk(rng, (B, S, KV, D), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=32, block_k=32, interpret=True)
    want = ref.attention_ref(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), causal=True,
        window=window).transpose(0, 2, 1, 3)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want, np.float32),
        atol=tol, rtol=tol)


@settings(max_examples=10, deadline=None)
@given(
    s=st.sampled_from([32, 48, 64]),
    h=st.sampled_from([1, 2, 4]),
    d=st.sampled_from([16, 32]),
    seed=st.integers(0, 2**31 - 1),
)
def test_flash_attention_property(s, h, d, seed):
    """Property: rows of the attention output are convex combinations of V
    rows => output is bounded by V's min/max per feature (plus eps)."""
    rng = np.random.default_rng(seed)
    q = _mk(rng, (1, s, h, d), jnp.float32)
    k = _mk(rng, (1, s, h, d), jnp.float32)
    v = _mk(rng, (1, s, h, d), jnp.float32)
    out = np.asarray(ops.flash_attention(q, k, v, causal=True, block_q=16,
                                         block_k=16, interpret=True))
    vmin, vmax = np.min(np.asarray(v)), np.max(np.asarray(v))
    assert out.min() >= vmin - 1e-4 and out.max() <= vmax + 1e-4
    # first position attends only to itself
    np.testing.assert_allclose(out[0, 0], np.asarray(v)[0, 0], atol=1e-5)


# ------------------------------------------------------------------ #
# fused causal flash attention with its backward (jax's Pallas TPU
# kernel, behind ops.flash_attention): parity with the jnp scan, and
# which calls take it
# ------------------------------------------------------------------ #

FUSED_CASES = [
    # (S, dtype): one q/kv block per sequence (S <= 512), then two, of
    # which the kernels skip the one above the diagonal
    (256, jnp.bfloat16),
    (256, jnp.float32),
    (512, jnp.bfloat16),
    (512, jnp.float32),
    (1024, jnp.bfloat16),
    (1024, jnp.float32),
]


def _rel_err(got, want):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    return np.max(np.abs(got - want)) / np.max(np.abs(want))


@pytest.mark.parametrize("S,dtype", FUSED_CASES)
def test_fused_attention_and_its_gradients_match_the_scan(S, dtype):
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(S)
    q, k, v, g = (_mk(rng, (1, S, 2, 64), dtype) for _ in range(4))
    assert ops.fused_attention_takes(q.shape, k.shape, v.shape,
                                     causal=True, window=0)

    def fused(q, k, v):
        return ops.flash_attention(q, k, v, interpret=True)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                       * g.astype(jnp.float32))

    got = [fused(q, k, v)] + list(
        jax.grad(loss(fused), argnums=(0, 1, 2))(q, k, v))
    want = [chunked_attention(q, k, v)] + list(
        jax.grad(loss(chunked_attention), argnums=(0, 1, 2))(q, k, v))
    # bf16: q/k/v and P of the PV product round to 8 bits; f32: exact
    # but for the order of the sums
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype, name
        assert _rel_err(a, b) < tol, (name, _rel_err(a, b))


def _dispatch_call(case):
    """Arguments of one ``chunked_attention`` call: the plain causal
    self-attention of 2 heads of 64 over 256 positions, changed as
    ``case`` says."""
    S, H, KV, Dk, Dv = 256, 2, 2, 64, 64
    kw = dict(causal=True, window=0, q_positions=None, kv_positions=None)
    if case == "window":
        kw["window"] = 128
    elif case == "gqa":
        KV = 1
    elif case == "dk_ne_dv":
        Dv = 32
    elif case == "not_causal":
        kw["causal"] = False
    elif case == "s_not_divided":
        S = 640               # blocks of 512 leave a remainder of 128
    elif case == "positions":
        kw["q_positions"] = kw["kv_positions"] = jnp.arange(S)[None] + 3
    q = jnp.zeros((1, S, H, Dk), jnp.bfloat16)
    k = jnp.zeros((1, S, KV, Dk), jnp.bfloat16)
    v = jnp.zeros((1, S, KV, Dv), jnp.bfloat16)
    return (q, k, v), kw


@pytest.mark.parametrize("case,takes", [
    ("plain", True), ("window", False), ("gqa", False), ("dk_ne_dv", False),
    ("not_causal", False), ("s_not_divided", False), ("positions", False),
    ("cpu", False), ("mesh", False), ("manual_mesh", True)])
def test_which_attention_calls_take_the_fused_kernel(monkeypatch, case,
                                                     takes):
    from jax.sharding import AbstractMesh, AxisType

    from repro import tracing
    from repro.models import attention
    if case != "cpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    args, kw = _dispatch_call(case)
    axis = {"mesh": AxisType.Auto, "manual_mesh": AxisType.Manual}.get(case)
    mesh = AbstractMesh((2,), ("data",), axis_types=(axis,)) if axis \
        else jax.sharding.get_abstract_mesh()
    with jax.sharding.use_abstract_mesh(mesh), tracing.lowering() as noted:
        assert attention._fused_applies(*args, **kw) == takes
        jax.eval_shape(lambda *a: attention.chunked_attention(*a, **kw),
                       *args)
    assert noted == {tracing.FUSED_ATTENTION if takes
                     else tracing.CHUNKED_ATTENTION: 1}


@pytest.mark.parametrize("positions,path", [
    (False, "attention.fused"), (True, "attention.chunked")])
def test_the_model_takes_the_fused_kernel_only_with_plain_positions(
        monkeypatch, positions, path):
    """gpt2 on a TPU: a batch without positions of its own trains
    through the fused kernel, forward and backward; one that brings its
    own keeps the scan.  Traced, not run."""
    from repro import tracing
    from repro.configs import get_config
    from repro.models import Model
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    model = Model(get_config("gpt2m").reduced())
    params = jax.eval_shape(model.init, jax.random.key(0))
    tok = jnp.zeros((2, 256), jnp.int32)
    batch = {"tokens": tok, "labels": tok}
    if positions:
        batch["positions"] = jnp.broadcast_to(jnp.arange(256), (2, 256))
    with tracing.lowering() as noted:
        jax.eval_shape(jax.grad(lambda p: model.loss(p, batch)[0]), params)
    assert set(noted) == {path}


# ------------------------------------------------------------------ #
# SSD (mamba2) scan
# ------------------------------------------------------------------ #

SSD_CASES = [
    # (B, S, nh, hd, ds, chunk)
    (2, 128, 3, 16, 8, 32),
    (1, 64, 2, 32, 16, 16),
    (2, 96, 1, 8, 4, 32),      # padding (96 % 32 == 0 but odd sizes)
    (1, 80, 4, 16, 8, 32),     # S not multiple of chunk => pad path
]


@pytest.mark.parametrize("B,S,nh,hd,ds,chunk", SSD_CASES)
def test_ssd_scan_vs_ref(B, S, nh, hd, ds, chunk):
    rng = np.random.default_rng(1)
    xh = _mk(rng, (B, S, nh, hd), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, nh)), jnp.float32)
    bs = _mk(rng, (B, S, ds), jnp.float32)
    cs = _mk(rng, (B, S, ds), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (nh,)), jnp.float32)
    y, h = ops.ssd_scan(xh, dt, bs, cs, a, chunk=chunk, interpret=True)
    yr, hr = ref.ssd_ref(xh.transpose(0, 2, 1, 3), dt.transpose(0, 2, 1),
                         bs, cs, a)
    np.testing.assert_allclose(np.asarray(y),
                               np.asarray(yr.transpose(0, 2, 1, 3)),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)


def test_ssd_model_path_matches_jnp():
    """models.ssm._ssd_chunk_scan (jnp) vs the kernel, through mamba2."""
    from repro.models.ssm import _ssd_chunk_scan
    rng = np.random.default_rng(2)
    B, S, nh, hd, ds = 2, 64, 2, 16, 8
    xh = _mk(rng, (B, S, nh, hd), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, nh)), jnp.float32)
    bs = _mk(rng, (B, S, ds), jnp.float32)
    cs = _mk(rng, (B, S, ds), jnp.float32)
    a = jnp.asarray(-rng.uniform(0.5, 2.0, (nh,)), jnp.float32)
    h0 = jnp.zeros((B, nh, hd, ds), jnp.float32)
    y_jnp, h_jnp = _ssd_chunk_scan(xh, dt, bs, cs, a, h0, chunk=16)
    y_k, h_k = ops.ssd_scan(xh, dt, bs, cs, a, chunk=16, interpret=True)
    np.testing.assert_allclose(np.asarray(y_jnp), np.asarray(y_k),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h_jnp), np.asarray(h_k),
                               atol=1e-4, rtol=1e-4)


# ------------------------------------------------------------------ #
# mamba1 scan
# ------------------------------------------------------------------ #

M1_CASES = [
    (2, 64, 24, 8, 16),
    (1, 128, 16, 4, 32),
    (2, 48, 8, 8, 16),     # S pads to chunk multiple
]


@pytest.mark.parametrize("B,S,di,ds,chunk", M1_CASES)
def test_mamba1_scan_vs_ref(B, S, di, ds, chunk):
    rng = np.random.default_rng(3)
    x = _mk(rng, (B, S, di), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, S, di)), jnp.float32)
    bs = _mk(rng, (B, S, ds), jnp.float32)
    cs = _mk(rng, (B, S, ds), jnp.float32)
    A = jnp.asarray(-rng.uniform(0.5, 2.0, (di, ds)), jnp.float32)
    y, h = ops.mamba1_scan(x, dt, bs, cs, A, chunk=chunk, interpret=True)
    yr, hr = ref.mamba1_ref(x, dt, bs, cs, A)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               atol=1e-4, rtol=1e-4)


@settings(max_examples=8, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       s=st.sampled_from([16, 32, 64]))
def test_ssd_state_decay_property(seed, s):
    """Property: with negative A, a zero-input suffix decays the state
    monotonically (|h| after extra zero steps <= before)."""
    rng = np.random.default_rng(seed)
    B, nh, hd, ds = 1, 2, 8, 4
    xh = np.zeros((B, 2 * s, nh, hd), np.float32)
    xh[:, :s] = rng.standard_normal((B, s, nh, hd))
    dt = np.full((B, 2 * s, nh), 0.1, np.float32)
    bs = rng.standard_normal((B, 2 * s, ds)).astype(np.float32)
    cs = rng.standard_normal((B, 2 * s, ds)).astype(np.float32)
    a = -np.abs(rng.standard_normal(nh)).astype(np.float32) - 0.1
    _, h_half = ops.ssd_scan(jnp.asarray(xh[:, :s]), jnp.asarray(dt[:, :s]),
                             jnp.asarray(bs[:, :s]), jnp.asarray(cs[:, :s]),
                             jnp.asarray(a), chunk=16, interpret=True)
    xh2 = xh.copy()
    xh2[:, s:] = 0.0
    _, h_full = ops.ssd_scan(jnp.asarray(xh2), jnp.asarray(dt),
                             jnp.asarray(bs), jnp.asarray(cs),
                             jnp.asarray(a), chunk=16, interpret=True)
    assert float(jnp.max(jnp.abs(h_full))) <= \
        float(jnp.max(jnp.abs(h_half))) + 1e-5


def test_model_level_pallas_parity():
    """use_pallas=True end-to-end forward equals the jnp path."""
    from repro.configs import get_config
    from repro.models import Model
    from repro.models.registry import input_specs
    from repro.configs.base import ShapeConfig
    rng = np.random.default_rng(0)
    shape = ShapeConfig("t", 64, 1, "train")
    for arch in ("llama3.2-3b", "falcon-mamba-7b", "zamba2-2.7b"):
        cfg = get_config(arch).reduced()
        m0, m1 = Model(cfg), Model(cfg, use_pallas=True)
        params = m0.init(jax.random.key(0))
        batch = input_specs(cfg, shape, abstract=False, rng=rng)
        l0, _ = m0.forward(params, batch, remat=False)
        l1, _ = m1.forward(params, batch, remat=False)
        np.testing.assert_allclose(
            np.asarray(l0, np.float32), np.asarray(l1, np.float32),
            atol=2e-2, rtol=2e-2)


# ------------------------------------------------------------------ #
# fused RMSNorm
# ------------------------------------------------------------------ #

RMS_CASES = [
    ((4, 32, 64), jnp.float32, 16),
    ((2, 100, 128), jnp.bfloat16, 32),   # rows pad to block multiple
    ((7, 96), jnp.float32, 4),
]


@pytest.mark.parametrize("shape,dtype,block", RMS_CASES)
def test_rmsnorm_vs_ref(shape, dtype, block):
    rng = np.random.default_rng(4)
    x = _mk(rng, shape, dtype)
    w = _mk(rng, shape[-1:], dtype) + 1.0
    out = ops.rmsnorm(x, w, block_rows=block, interpret=True)
    want = ref.rmsnorm_ref(x, w)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)


def test_rmsnorm_matches_model_layer():
    from repro.models.layers import rmsnorm as model_rmsnorm
    rng = np.random.default_rng(5)
    x = _mk(rng, (3, 17, 64), jnp.float32)
    w = _mk(rng, (64,), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(ops.rmsnorm(x, w, interpret=True)),
        np.asarray(model_rmsnorm(x, w)), atol=1e-5, rtol=1e-5)


@settings(max_examples=10, deadline=None)
@given(rows=st.integers(1, 50), d=st.sampled_from([8, 32, 128]),
       seed=st.integers(0, 2**31 - 1))
def test_rmsnorm_unit_norm_property(rows, d, seed):
    """Property: with unit weight, output rows have RMS ~= 1."""
    rng = np.random.default_rng(seed)
    x = _mk(rng, (rows, d), jnp.float32) * 5.0
    out = np.asarray(ops.rmsnorm(x, jnp.ones((d,)), block_rows=16,
                                 interpret=True))
    rms = np.sqrt(np.mean(out ** 2, axis=-1))
    np.testing.assert_allclose(rms, 1.0, atol=1e-3)
