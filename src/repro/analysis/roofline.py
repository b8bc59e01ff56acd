"""Three-term roofline model (TPU v5e defaults, overridable peaks).

    compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
    memory term     = HLO_bytes / HBM_bw                 (per chip)
    collective term = Σ_kind wire_bytes(kind) / link_bw  (per chip)

Peaks default to the TPU v5e constants below (the paper-model target);
pass ``peaks=`` (anything with ``peak_flops`` / ``hbm_bw`` / ``link_bw``
attributes, e.g. a :class:`repro.telemetry.perf.MachineProfile`) to
evaluate the same model against the *detected* host — that is how the
performance observatory turns a measured wall time into a meaningful
roofline-efficiency % on a CPU CI runner.

Sources: FLOPs / traffic / collective payloads come from the while-aware
HLO parser (``repro.analysis.hlo``) applied to the compiled executable;
``compiled.cost_analysis()`` is recorded as a cross-check only (it counts
scan bodies once — see hlo.py docstring).

Wire factors (bidirectional ring on the ICI torus; n = group size):
    all-reduce       2·(n−1)/n · payload
    all-gather       (n−1)/n · payload      (payload = gathered result)
    reduce-scatter   (n−1)/n · payload      (payload = pre-scatter operand)
    all-to-all       (n−1)/n · payload
    collective-permute  1 · payload

``model_flops_global`` is the algorithm's own FLOP count (LU: 2/3·n³) —
the ratio model FLOPs / HLO_FLOPs exposes redundant work (masked
whole-matrix updates, padding).
"""
from __future__ import annotations

import dataclasses

from repro.analysis.hlo import HloCost

# ---- TPU v5e hardware constants (per chip) --------------------------------
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link (≈ one direction)

_WIRE_FACTORS = {
    "all-reduce": lambda n: 2.0 * (n - 1) / n,
    "all-gather": lambda n: (n - 1) / n,
    "reduce-scatter": lambda n: (n - 1) / n,
    "all-to-all": lambda n: (n - 1) / n,
    "collective-permute": lambda n: 1.0,
}


@dataclasses.dataclass
class RooflineReport:
    name: str
    chips: int
    # per-chip, per-step
    hlo_flops: float
    hlo_bytes: float
    wire_bytes: float
    model_flops_global: float            # the algorithm's FLOP count
    xla_flops: float = 0.0               # cost_analysis cross-check
    xla_bytes: float = 0.0
    collective_breakdown: dict = dataclasses.field(default_factory=dict)
    # hardware peaks the three terms divide by — v5e unless overridden
    peak_flops: float = PEAK_FLOPS_BF16
    hbm_bw: float = HBM_BW
    link_bw: float = ICI_BW

    @property
    def t_compute(self) -> float:
        return self.hlo_flops / self.peak_flops

    @property
    def t_memory(self) -> float:
        return self.hlo_bytes / self.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.wire_bytes / self.link_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def model_flops_per_chip(self) -> float:
        return self.model_flops_global / self.chips

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (per chip)."""
        return (self.model_flops_per_chip / self.hlo_flops
                if self.hlo_flops else 0.0)


def wire_bytes(cost: HloCost) -> tuple[float, dict]:
    total, detail = 0.0, {}
    for kind, payload in cost.collective_bytes.items():
        n = max(int(cost.group_sizes.get(kind, 2)), 2)
        factor = _WIRE_FACTORS.get(kind, lambda n: 1.0)(n)
        w = payload * factor
        detail[kind] = {"payload": payload, "group": n, "wire": w,
                        "count": cost.collective_counts.get(kind, 0)}
        total += w
    return total, detail


def roofline(name: str, cost: HloCost, *, chips: int,
             model_flops_global: float, xla_flops: float = 0.0,
             xla_bytes: float = 0.0, peaks=None) -> RooflineReport:
    """Build a :class:`RooflineReport`.  ``peaks`` overrides the v5e
    hardware constants (duck-typed: ``peak_flops`` / ``hbm_bw`` /
    ``link_bw`` attributes)."""
    wb, detail = wire_bytes(cost)
    hw = {} if peaks is None else {
        "peak_flops": float(peaks.peak_flops),
        "hbm_bw": float(peaks.hbm_bw),
        "link_bw": float(peaks.link_bw)}
    return RooflineReport(
        name=name, chips=chips, hlo_flops=cost.flops,
        hlo_bytes=cost.traffic_bytes, wire_bytes=wb,
        model_flops_global=model_flops_global,
        xla_flops=xla_flops, xla_bytes=xla_bytes,
        collective_breakdown=detail, **hw)
