"""TFLOP-weighted pipeline stage balancing (ROADMAP "heterogeneous stage
balancing"): the layer allocator's invariants, its effect on the cost
model, and the Placement → pipeline_mesh threading."""
import pytest

from prophelpers import given, settings, st

from repro.configs import get_config
from repro.core.costmodel import (balanced_stage_layers, paper_workload,
                                  stage_compute_tflops,
                                  technique_step_cost)
from repro.core.pipeline import pipeline_mesh, validate_stages
from repro.core.plans import Placement
from repro.core.search import PlanSearch
from repro.core.topology import Link, Site, ring

WL_M = paper_workload(get_config("gpt2m"))


def mixed_ring(gpu_types, lat_ms=20.0):
    sites = [Site((g, g), name=f"S{i}") for i, g in enumerate(gpu_types)]
    return ring("mixed", sites, [Link(lat_ms * 1e-3, 3.0)] * len(sites))


# ------------------------------------------------------------------ #
# the allocator
# ------------------------------------------------------------------ #

def test_balanced_split_sums_and_floors():
    split = balanced_stage_layers(24, [50.0, 50.0, 20.0])
    assert split == (10, 10, 4)
    assert sum(split) == 24
    # even a near-zero stage keeps its one mandatory layer
    assert balanced_stage_layers(24, [100.0, 0.001])[1] == 1


def test_balanced_split_homogeneous_is_even():
    assert balanced_stage_layers(24, [25.0] * 3) == (8, 8, 8)
    assert balanced_stage_layers(30, [50.0] * 2) == (15, 15)
    # non-divisible: off-by-one even split, earlier stages first
    assert balanced_stage_layers(30, [25.0] * 4) == (8, 8, 7, 7)


def test_balanced_split_monotone_in_tflops():
    split = balanced_stage_layers(24, [50.0, 20.0, 40.0])
    assert split[0] >= split[2] >= split[1]


def test_balanced_split_validates():
    with pytest.raises(ValueError):
        balanced_stage_layers(2, [1.0, 1.0, 1.0])   # fewer layers than stages
    with pytest.raises(ValueError):
        balanced_stage_layers(8, [1.0, 0.0])        # non-positive tflops
    with pytest.raises(ValueError):
        balanced_stage_layers(8, [])


@settings(max_examples=50, deadline=None)
@given(n_layers=st.integers(4, 96),
       tf=st.lists(st.floats(0.5, 200.0), min_size=1, max_size=6))
def test_balanced_split_properties(n_layers, tf):
    """Sum, floor, and monotonicity hold for any stage-TFLOP/s vector."""
    if n_layers < len(tf):
        n_layers = len(tf)
    split = balanced_stage_layers(n_layers, tf)
    assert sum(split) == n_layers
    assert all(l >= 1 for l in split)
    for i in range(len(tf)):
        for j in range(len(tf)):
            # strict enough that the proportional quotas can't collide
            # to the same float (ties are broken by stage index)
            if tf[i] > tf[j] * (1 + 1e-9):
                assert split[i] >= split[j], (tf, split)


# ------------------------------------------------------------------ #
# cost model: a T4 site gets fewer layers than an A30 site
# ------------------------------------------------------------------ #

def test_t4_site_gets_strictly_fewer_layers_in_mixed_ring():
    topo = mixed_ring(["A30", "A30", "T4"])
    tf = stage_compute_tflops(topo, (0, 1, 2))
    split = balanced_stage_layers(WL_M.cfg.n_layers, tf)
    assert tf == [50.0, 50.0, 20.0]
    assert split[2] < split[0] and split[2] < split[1]


def test_weighted_balance_speeds_up_heterogeneous_pipeshard():
    """On a mixed ring the TFLOP-weighted split strictly beats the even
    split (the T4 stage stops pacing every tick); on a homogeneous ring
    the two are identical."""
    het = mixed_ring(["A30", "A30", "T4"])
    even = technique_step_cost("pipeshard", WL_M, het,
                               stage_balance="even")
    bal = technique_step_cost("pipeshard", WL_M, het,
                              stage_balance="tflops")
    assert bal.compute_s < even.compute_s
    hom = mixed_ring(["A30", "A30", "A30"])
    e = technique_step_cost("pipeshard", WL_M, hom, stage_balance="even")
    b = technique_step_cost("pipeshard", WL_M, hom,
                            stage_balance="tflops")
    assert b.total_s == pytest.approx(e.total_s)


def test_explicit_stage_layers_override_and_validate():
    topo = mixed_ring(["A30", "T4", "A30"])
    c = technique_step_cost("pipeshard", WL_M, topo,
                            stage_layers=[10, 4, 10])
    assert c.compute_s > 0
    with pytest.raises(ValueError, match="partition"):
        technique_step_cost("pipeshard", WL_M, topo,
                            stage_layers=[10, 10, 10])
    with pytest.raises(ValueError, match="stage_balance"):
        technique_step_cost("pipeshard", WL_M, topo,
                            stage_balance="nonsense")


def test_plansearch_placement_attaches_balanced_layers():
    topo = mixed_ring(["A30", "A30", "T4"])
    search = PlanSearch(WL_M, topo, stage_balance="tflops")
    cand = next(c for c in search.candidates()
                if c.technique == "pipeshard" and c.sites == (0, 1, 2))
    p = search.placement(cand)
    assert p.stage_layers == (10, 10, 4)
    # even-balance searches keep the legacy bare placement
    bare = PlanSearch(WL_M, topo).placement(cand)
    assert bare.stage_layers is None


# ------------------------------------------------------------------ #
# Placement / mesh threading
# ------------------------------------------------------------------ #

def test_placement_validates_stage_layers():
    p = Placement(sites=(0, 1, 2), stage_order=(2, 0, 1),
                  stage_layers=(4, 10, 10))
    assert p.n_stages == 3
    with pytest.raises(ValueError, match="entries"):
        Placement(sites=(0, 1), stage_layers=(8, 8, 8))
    with pytest.raises(ValueError, match=">= 1"):
        Placement(sites=(0, 1), stage_layers=(24, 0))


def test_pipeline_mesh_accepts_weighted_splits():
    from repro.launch.mesh import make_mesh
    base = make_mesh((1, 1), ("data", "model"))
    mesh = pipeline_mesh(base, 1, stage_layers=(24,))
    assert mesh.shape["stage"] == 1
    with pytest.raises(ValueError, match="entries"):
        pipeline_mesh(base, 1, stage_layers=(16, 8))
    with pytest.raises(ValueError, match=">= 1"):
        pipeline_mesh(base, 1, stage_layers=(0,))


def test_validate_stages_accepts_uneven_and_rejects_bad_splits():
    import numpy as np
    cfg = get_config("gpt2m")
    stack = {"w": np.zeros((24, 4))}
    assert validate_stages(cfg, stack, 2, stage_layers=(12, 12)) == (12, 12)
    # uneven splits are realized at runtime now (pad-and-mask)
    assert validate_stages(cfg, stack, 2, stage_layers=(16, 8)) == (16, 8)
    assert validate_stages(cfg, stack, 3, stage_layers=(10, 10, 4)) \
        == (10, 10, 4)
    assert validate_stages(cfg, stack, 2) is None
    with pytest.raises(ValueError, match="partition"):
        validate_stages(cfg, stack, 2, stage_layers=(12, 14))
    with pytest.raises(ValueError, match="partition"):
        validate_stages(cfg, stack, 2, stage_layers=(24, 0))
    # no explicit split: the stack must divide evenly across stages
    with pytest.raises(ValueError, match="divisible"):
        validate_stages(cfg, stack, 5)
