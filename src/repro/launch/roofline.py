"""Roofline-term extraction from a compiled dry-run artifact.

Three terms per (arch × shape × mesh), in seconds:

    compute    = executed_FLOPs / (chips × peak_FLOP/s)
    memory     = HBM_bytes_per_device / HBM_bw
    collective = collective_bytes_per_device / link_bw
                 (ICI; multi-pod runs price at DCN)

Sources:
  * collective bytes — parsed from the optimized HLO with while-loop trip
    counts applied (launch/hlo_parse.py); raw ``cost_analysis`` counts loop
    bodies once, which would silently drop ~n_layers× of the traffic;
  * executed FLOPs / HBM bytes — closed-form per-step estimates
    (launch/analytic.py) for the same reason, cross-checked against the raw
    ``cost_analysis()`` numbers which are also recorded;
  * per-device memory footprint — ``compiled.memory_analysis()``
    (argument + output + temp), the "does it fit 16 GB HBM" check.

MODEL_FLOPS = 6·N_active·D; useful_flops_fraction = MODEL_FLOPS /
executed_FLOPs exposes remat + full-block-attention waste.
"""
from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import Dict, Optional

from repro.launch.hlo_parse import collective_bytes_with_trips
from repro.launch.mesh import (DCN_BW_PER_HOST, HBM_BW, ICI_BW_PER_LINK,
                               PEAK_FLOPS_BF16)


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    plan: str
    flops_total: float                  # executed, all chips (analytic)
    hbm_bytes_per_device: float         # analytic stream estimate
    collective_bytes_per_device: float  # HLO-parsed, trip-aware (intra-pod)
    collective_breakdown: Dict[str, float]
    dcn_bytes_per_device: float         # pod-crossing collective bytes
    model_flops: float
    n_devices: int
    memory_per_device_bytes: float      # compiled.memory_analysis footprint
    hlo_flops_raw: float                # cost_analysis (loop bodies once)
    hlo_bytes_raw: float
    crosses_pod: bool = False
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    ici_bw: float = ICI_BW_PER_LINK

    @property
    def compute_s(self) -> float:
        return self.flops_total / (self.n_devices * self.peak_flops)

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes_per_device / self.hbm_bw

    @property
    def collective_s(self) -> float:
        """Intra-pod bytes at ICI bandwidth + pod-crossing bytes at DCN."""
        return self.collective_bytes_per_device / self.ici_bw \
            + self.dcn_bytes_per_device / DCN_BW_PER_HOST

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_fraction(self) -> float:
        return self.model_flops / self.flops_total if self.flops_total else 0.0

    @property
    def fits_hbm(self) -> bool:
        return self.memory_per_device_bytes <= 16e9   # v5e: 16 GB

    def to_dict(self) -> Dict:
        d = asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 collective_s=self.collective_s, dominant=self.dominant,
                 useful_flops_fraction=self.useful_flops_fraction,
                 fits_hbm=self.fits_hbm)
        return d


def from_compiled(compiled, *, arch: str, shape: str, mesh_name: str,
                  plan: str, analytic, n_devices: int,
                  crosses_pod: bool = False,
                  hlo_text: Optional[str] = None) -> Roofline:
    cost = compiled.cost_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    pod_size = n_devices // 2 if crosses_pod else 0
    coll = collective_bytes_with_trips(text, pod_size=pod_size)
    breakdown = {k: v for k, v in coll.items() if not k.startswith("_")}
    dcn = sum(coll.get("_crossing", {}).values())  # type: ignore[arg-type]
    mem = compiled.memory_analysis()
    mem_bytes = 0.0
    for attr in ("argument_size_in_bytes", "temp_size_in_bytes",
                 "output_size_in_bytes"):
        mem_bytes += float(getattr(mem, attr, 0) or 0)
    return Roofline(
        arch=arch, shape=shape, mesh=mesh_name, plan=plan,
        flops_total=analytic.flops_total,
        hbm_bytes_per_device=analytic.hbm_bytes_per_device,
        collective_bytes_per_device=float(sum(breakdown.values())),
        collective_breakdown=dict(
            breakdown, crossing=coll.get("_crossing", {})),
        dcn_bytes_per_device=float(dcn),
        model_flops=analytic.model_flops,
        n_devices=n_devices,
        memory_per_device_bytes=mem_bytes,
        hlo_flops_raw=float(cost.get("flops", 0.0)),
        hlo_bytes_raw=float(cost.get("bytes accessed", 0.0)),
        crosses_pod=crosses_pod,
    )
