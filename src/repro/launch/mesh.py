"""Production meshes, and topology→mesh mapping.

Single pod: 16x16 = 256 chips, axes ("data", "model").
Multi-pod:  2x16x16 = 512 chips, axes ("pod", "data", "model") — the "pod"
axis is the slow (DCN) dimension, the TPU analogue of the paper's
site-to-site WAN links.

``make_topology_mesh`` maps an N-site ``core.topology.Topology`` selection
onto the same axis vocabulary: one pod block per selected site, intra-site
GPUs split over (data, model).  Pipeshard's ``pipeline_mesh`` then absorbs
the pod axis into stages, so a ``core.search`` stage→site assignment lands
each stage on its site's devices (DESIGN.md §5).

``make_production_mesh`` is a FUNCTION (not a module constant) so importing
this module never touches jax device state; only launch/dryrun.py forces
the 512-device host platform.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh

from repro.core.topology import Topology


def make_mesh(shape: Sequence[int], axes: Sequence[str], *,
              devices=None) -> Mesh:
    """The one mesh constructor: every axis is Auto, so the plans'
    sharding rules steer GSPMD.  (``jax.make_mesh`` defaults to Explicit
    axes, under which the embedding gather raises ``ShardingTypeError``.)
    ``devices`` defaults to all local devices."""
    return jax.make_mesh(tuple(shape), tuple(axes), devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


# --------------------------------------------------------------------- #
# topology sites -> mesh axes
# --------------------------------------------------------------------- #

def topology_mesh_spec(topo: Topology,
                       sites: Optional[Sequence[int]] = None, *,
                       model: int = 1
                       ) -> Tuple[Tuple[int, int, int],
                                  Tuple[str, str, str]]:
    """(shape, axes) of the mesh realizing a site selection: pod = one
    block per site (the slow inter-site dimension), each site's GPUs split
    into (data, model).  Pure function of the topology — unit-testable
    without devices; ``make_topology_mesh`` materializes it."""
    sel = topo.select(sites)
    if not sel:
        raise ValueError("empty site selection")
    per = {len(topo.sites[i].gpus) for i in sel}
    if len(per) != 1:
        raise ValueError(
            f"sites {sel} have unequal GPU counts {sorted(per)}; meshes "
            f"are rectangular — select equal-sized sites per mesh")
    n_per = per.pop()
    if n_per % model != 0:
        raise ValueError(f"model={model} does not divide the {n_per} GPUs "
                         f"per site")
    return (len(sel), n_per // model, model), ("pod", "data", "model")


def make_topology_mesh(topo: Topology,
                       sites: Optional[Sequence[int]] = None, *,
                       model: int = 1, devices=None) -> Mesh:
    """Mesh over `devices` (default: all local) shaped after a topology
    site selection; device blocks follow the order of `sites`."""
    shape, axes = topology_mesh_spec(topo, sites, model=model)
    n = shape[0] * shape[1] * shape[2]
    devs = list(jax.devices()) if devices is None else list(devices)
    if len(devs) < n:
        raise ValueError(f"topology selection needs {n} devices, "
                         f"have {len(devs)}")
    return make_mesh(shape, axes, devices=devs[:n])


def placement_pipeline_mesh(topo: Topology, placement, *,
                            model: int = 1, devices=None) -> Mesh:
    """Realize a searched pipeline ``core.plans.Placement`` as a staged
    mesh: one pod block per placed site, pod blocks permuted into the
    placement's stage order, and the TFLOP-weighted ``stage_layers``
    (when present) shape-checked against the stage count — the full
    Placement → ``make_topology_mesh`` → ``pipeline_mesh`` wiring of
    DESIGN.md §5 in one call.  Pass the same ``placement.stage_layers``
    to ``core.steps.build_train_step`` / ``core.pipeline
    .make_pipeline_loss`` so the split executes (uneven splits run
    pad-and-masked).

    Args:
        topo: the N-site topology the placement was searched on.
        placement: a ``core.plans.Placement`` (site subset, stage order,
            optional per-stage layer counts).
        model: tensor-parallel degree inside each site.
        devices: explicit device list (default: all local devices).

    Returns:
        A ``(stage, data, model)`` mesh with stage k on the devices of
        the site the search assigned to stage k.
    """
    from repro.core.pipeline import pipeline_mesh
    base = make_topology_mesh(topo, placement.sites, model=model,
                              devices=devices)
    return pipeline_mesh(base, placement.n_stages,
                         stage_order=placement.pod_permutation(),
                         stage_layers=placement.stage_layers,
                         schedule=placement.schedule)


def placement_mesh(topo: Topology, plan, placement, *,
                   model: int = 1, devices=None) -> Mesh:
    """Realize any searched ``core.plans.Placement`` for a plan: the
    one-call Placement → mesh wiring the extended technique pool needs
    (docs/cost-model.md).  Pipeline plans build the staged mesh
    (``placement_pipeline_mesh``); flat plans — data/zero2/shard/
    shard_zero/fsdp winners — get the plain topology mesh over the
    placement's site subset.

    Args:
        topo: the N-site topology the placement was searched on.
        plan: the ``core.plans.Plan`` being launched.
        placement: the searched ``core.plans.Placement``.
        model: tensor-parallel degree inside each site.
        devices: explicit device list (default: all local devices).

    Returns:
        A mesh the plan's shardings apply to directly.
    """
    if plan.pipeline:
        return placement_pipeline_mesh(topo, placement, model=model,
                                       devices=devices)
    return make_topology_mesh(topo, placement.sites, model=model,
                              devices=devices)


# TPU v5e roofline constants (per chip) — see EXPERIMENTS.md §Roofline.
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # bytes/s
ICI_BW_PER_LINK = 50e9        # bytes/s/link
DCN_BW_PER_HOST = 6.25e9      # bytes/s (50 Gbit) — inter-pod "WAN"
