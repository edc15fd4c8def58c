"""Analytic roofline-cost sanity tests + pipeline mesh construction."""
import numpy as np
import pytest

from repro.configs import get_config, get_shape
from repro.configs.base import ShapeConfig
from repro.launch.analytic import analytic_cost
from repro.launch.hlo_parse import _groups_cross_pod


def test_flops_scale_with_tokens():
    cfg = get_config("llama3.2-3b")
    a = analytic_cost(cfg, ShapeConfig("a", 1024, 8, "train"), n_devices=16)
    b = analytic_cost(cfg, ShapeConfig("b", 1024, 16, "train"), n_devices=16)
    assert 1.8 < b.flops_total / a.flops_total < 2.4  # ~2x (+attn S² const)


def test_train_flops_include_remat_overhead():
    cfg = get_config("llama3.2-3b")
    s = ShapeConfig("t", 4096, 256, "train")
    with_remat = analytic_cost(cfg, s, n_devices=256, remat=True)
    without = analytic_cost(cfg, s, n_devices=256, remat=False)
    assert with_remat.flops_total > without.flops_total
    assert with_remat.model_flops == without.model_flops
    # useful fraction below 1 by construction
    assert with_remat.model_flops < with_remat.flops_total


def test_decode_flops_tiny_vs_prefill():
    cfg = get_config("llama3.2-3b")
    pre = analytic_cost(cfg, get_shape("prefill_32k"), n_devices=256)
    dec = analytic_cost(cfg, get_shape("decode_32k"), n_devices=256)
    assert dec.flops_total < pre.flops_total / 1000


def test_moe_uses_active_params():
    cfg = get_config("phi3.5-moe-42b-a6.6b")
    s = ShapeConfig("t", 1024, 8, "train")
    a = analytic_cost(cfg, s, n_devices=16)
    # 6*N_active*D, not 6*N_total*D
    assert a.model_flops == 6.0 * cfg.active_param_count() * 1024 * 8


def test_window_bounds_decode_cache_traffic():
    cfg = get_config("llama3.2-3b")
    s = get_shape("long_500k")
    full = analytic_cost(cfg, s, n_devices=256, window=0)
    windowed = analytic_cost(cfg, s, n_devices=256, window=8192)
    assert windowed.hbm_bytes_per_device < full.hbm_bytes_per_device


def test_tp_reduces_param_traffic():
    cfg = get_config("llama3.2-3b")
    s = ShapeConfig("t", 1024, 16, "train")
    tp1 = analytic_cost(cfg, s, n_devices=16, dp=16, tp=1)
    tp16 = analytic_cost(cfg, s, n_devices=256, dp=16, tp=16)
    assert tp16.hbm_bytes_per_device < tp1.hbm_bytes_per_device


# ------------------------------------------------------------------ #
# pod-crossing classification
# ------------------------------------------------------------------ #

def test_iota_groups_within_pod():
    # [32,16]<=[512]: consecutive groups of 16 — never cross a 256 boundary
    line = "x = f32[4] all-reduce(%y), replica_groups=[32,16]<=[512]"
    assert not _groups_cross_pod(line, pod_size=256)


def test_iota_groups_crossing_pod():
    # [256,2]<=[2,16,16]T(2,1,0): pairs (i, i+256) — always cross
    line = ("x = f32[4] all-reduce(%y), "
            "replica_groups=[256,2]<=[2,16,16]T(2,1,0)")
    assert _groups_cross_pod(line, pod_size=256)


def test_explicit_groups_and_pairs():
    assert not _groups_cross_pod("replica_groups={{0,1},{2,3}}", pod_size=2)
    assert _groups_cross_pod("replica_groups={{0,2}}", pod_size=2)
    assert not _groups_cross_pod("replica_groups={{0,1}}", pod_size=2)
    assert _groups_cross_pod("source_target_pairs={{0,3},{3,0}}", pod_size=2)
    assert not _groups_cross_pod("source_target_pairs={{0,1},{1,0}}",
                                 pod_size=2)


def test_pipeline_mesh_construction():
    import jax
    from repro.core.pipeline import pipeline_mesh, validate_stages
    from repro.launch.mesh import make_mesh
    base = make_mesh((1, 1), ("data", "model"))
    m = pipeline_mesh(base, 1)
    assert m.shape["stage"] == 1
    # stage must divide the stack length
    class FakeCfg:
        name = "x"
    leaf = jax.ShapeDtypeStruct((9, 4), np.float32)
    with pytest.raises(ValueError):
        validate_stages(FakeCfg(), {"w": leaf}, 2)
    validate_stages(FakeCfg(), {"w": leaf}, 3)
