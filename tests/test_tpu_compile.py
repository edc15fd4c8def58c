"""Ahead-of-time compiles for a described TPU v5e (no chip attached).

The TPU compiler is installed with jax, and it compiles for a topology
that is only described.  What it refuses here (a block that is not
(8, 128)-aligned, a primitive Mosaic cannot lower, a program that does
not fit the chip's 16 GB) it would refuse on the chip.  Nothing runs, so
these tests say nothing about results or times; the interpret-mode
tests in test_kernels.py / test_quantized.py check the numbers.

The topology is described inside a module-scoped fixture, never at
import time: only one process may load the TPU library, and every
test worker imports every test file.
"""
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh, SingleDeviceSharding

from repro import tracing
from repro.kernels import ops
from repro.models.attention import chunked_attention

# gpt2m widths: batch 8 x context 1024, 16 heads of 64
B, S, H, D = 8, 1024, 16, 64
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _compiled_kernel_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Pallas kernel, compiled
    return text


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (B, S, H, D), jnp.bfloat16)
    _compiled_kernel_text(
        lambda q, k, v: ops.flash_attention(q, k, v, interpret=False),
        q, q, q)


def _attention_grad(one_chip, attend):
    """One attention call under the model's scope, and the gradient of a
    sum of its output for q, k and v, compiled for the described chip."""
    qkv = [_spec(one_chip, (B, S, H, D), jnp.bfloat16)] * 3

    def loss(q, k, v):
        with jax.named_scope(tracing.ATTENTION):
            o = attend(q, k, v)
        return jnp.sum(o.astype(jnp.float32))

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(*qkv).compile()


def test_fused_attention_gradient_compiles_with_its_backward(one_chip):
    """The training path's attention at gpt2m widths: the fused kernel
    runs forward and backward as custom calls, each in the attention
    scope, in less temporary memory than the scan it replaces."""
    fused = _attention_grad(
        one_chip, lambda q, k, v: ops.flash_attention(q, k, v,
                                                      interpret=False))
    text = fused.as_text()
    calls = re.findall(r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*tpu_custom_call'
                       r'.*op_name="([^"]*)"', text, re.M)
    backward = [name for name, op in calls if "transpose(" in op]
    assert len(backward) >= 2                     # dq and dk/dv kernels
    assert len(calls) > len(backward)             # and the forward's
    table = tracing.scopes_of(text)
    assert {table.get(name) for name, _ in calls} == {tracing.ATTENTION}

    # here the backend is the CPU, so chunked_attention keeps its scan
    chunked = _attention_grad(one_chip, chunked_attention)
    assert "tpu_custom_call" not in chunked.as_text()
    assert fused.memory_analysis().temp_size_in_bytes \
        < chunked.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("batch,sq,sk,block_q", [
    (4, 1, 256, 8),          # decode: 4 serving slots over a 256 cache
    (B, S, S, 128),          # full-length attention over the int8 cache
])
def test_flash_attention_int8kv_compiles(one_chip, batch, sq, sk, block_q):
    _compiled_kernel_text(
        lambda q, kq, ks, vq, vs, valid: ops.flash_attention_int8kv(
            q, kq, ks, vq, vs, valid=valid, causal=sq > 1,
            block_q=block_q, interpret=False),
        _spec(one_chip, (batch, sq, H, D), jnp.bfloat16),
        _spec(one_chip, (batch, sk, H, D), jnp.int8),
        _spec(one_chip, (batch, sk, H), jnp.float32),
        _spec(one_chip, (batch, sk, H, D), jnp.int8),
        _spec(one_chip, (batch, sk, H), jnp.float32),
        _spec(one_chip, (batch, sk), jnp.float32))


def test_rmsnorm_compiles(one_chip):
    _compiled_kernel_text(
        lambda x, w: ops.rmsnorm(x, w, interpret=False),
        _spec(one_chip, (B, S, 1024), jnp.bfloat16),
        _spec(one_chip, (1024,), jnp.float32))


def test_int8_matmul_compiles(one_chip):
    _compiled_kernel_text(
        lambda x, w: ops.int8_matmul(x, w, interpret=False),
        _spec(one_chip, (8192, 1024), jnp.bfloat16),
        _spec(one_chip, (1024, 4096), jnp.bfloat16))


def test_ssd_scan_compiles(one_chip):
    """Mamba-2 SSD at zamba2-2.7b widths: 80 heads of 64, d_state 64."""
    nh, hd, ds = 80, 64, 64
    f32 = jnp.float32
    _compiled_kernel_text(
        lambda x, dt, b, c, a: ops.ssd_scan(x, dt, b, c, a, chunk=64,
                                            interpret=False),
        _spec(one_chip, (1, S, nh, hd), f32),
        _spec(one_chip, (1, S, nh), f32),
        _spec(one_chip, (1, S, ds), f32),
        _spec(one_chip, (1, S, ds), f32),
        _spec(one_chip, (nh,), f32))


def test_mamba1_scan_compiles(one_chip):
    """Mamba-1 at falcon-mamba-7b widths: d_inner 8192, d_state 16."""
    di, ds = 8192, 16
    f32 = jnp.float32
    _compiled_kernel_text(
        lambda x, dt, b, c, A: ops.mamba1_scan(x, dt, b, c, A, chunk=64,
                                               interpret=False),
        _spec(one_chip, (1, S, di), f32),
        _spec(one_chip, (1, S, di), f32),
        _spec(one_chip, (1, S, ds), f32),
        _spec(one_chip, (1, S, ds), f32),
        _spec(one_chip, (di, ds), f32))


def test_gpt2m_data_train_step_fits_one_chip(topo):
    """The training main path of the one-chip smoke: gpt2m at published
    widths, plan data, batch 8 x 1024, on one described chip."""
    from repro.configs import get_config
    from repro.configs.base import TrainConfig
    from repro.core.plans import get_plan
    from repro.core.steps import build_train_step
    from repro.models import Model
    from repro.optim import init_adamw

    cfg = get_config("gpt2m")
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (24, 1024, 50257)
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    model = Model(cfg)
    p_shapes = jax.eval_shape(model.init, jax.random.key(0))
    tok = jax.ShapeDtypeStruct((B, S), jnp.int32)
    b_shapes = {"tokens": tok, "labels": tok}
    with jax.set_mesh(mesh):
        step, _ = build_train_step(model, get_plan("data"), mesh,
                                   TrainConfig(), params_shapes=p_shapes,
                                   batch_shapes=b_shapes)
        compiled = step.lower(p_shapes, jax.eval_shape(init_adamw, p_shapes),
                              b_shapes).compile()
    m = compiled.memory_analysis()
    total = (m.temp_size_in_bytes + m.argument_size_in_bytes
             + m.output_size_in_bytes - m.alias_size_in_bytes)
    assert total < V5E_HBM, total
