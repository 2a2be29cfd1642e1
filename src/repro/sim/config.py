"""Simulator configuration (paper Table 1, Maxwell-class).

`n_apps` is arbitrary (1 <= n_apps <= n_cores): cores are split between
apps by the oracle partition of §6 (app a owns a contiguous core range),
and the per-app core/warp counts exposed here are the single source of
truth for the scheduler, token distribution, and stats attribution.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Optional, Tuple

from repro.core.design import Design, as_design, get_design

if TYPE_CHECKING:   # sim.faults imports this module; annotation only
    from repro.sim.faults import FaultPlan


@dataclasses.dataclass(frozen=True)
class SimConfig:
    n_cores: int = 30
    warps_per_core: int = 32
    n_apps: int = 2
    # L2 data cache: 2MB, 16-way, 128B lines -> 1024 sets
    l2_sets: int = 1024
    l2_ways: int = 16
    # page-walk cache (Fig. 2a design): 16-way, 1024 entries (§3 fn. 2)
    pwc_entries: int = 1024
    pwc_ways: int = 16
    # DRAM: 8 channels x 8 banks
    n_channels: int = 8
    n_banks: int = 8
    # latencies (cycles)
    lat_l1_tlb: int = 1
    lat_l2_tlb: int = 10
    lat_l2_cache: int = 10
    lat_l1_data: int = 1
    sim_cycles: int = 60_000
    # a repro.core.design.Design; a registered name is coerced
    design: Design = dataclasses.field(
        default_factory=lambda: get_design("gpu-mmu"))
    # deterministic chaos schedule for `runner.run_trace` (sim.faults).
    # Hashable and part of the config identity, but stripped by the
    # runner's compile-cache canonicalization: fault operands are data,
    # so every plan shares the no-fault trace.
    fault_plan: Optional["FaultPlan"] = None

    def __post_init__(self):
        if not 1 <= self.n_apps <= self.n_cores:
            raise ValueError(
                f"n_apps must be in [1, n_cores={self.n_cores}], "
                f"got {self.n_apps}")
        if not isinstance(self.design, Design):
            object.__setattr__(self, "design", as_design(self.design))

    @property
    def total_warps(self) -> int:
        return self.n_cores * self.warps_per_core

    @property
    def app_of_core(self) -> Tuple[int, ...]:
        """(n_cores,) oracle core split (§6): contiguous, near-equal ranges."""
        return tuple((c * self.n_apps) // self.n_cores
                     for c in range(self.n_cores))

    @property
    def cores_per_app(self) -> Tuple[int, ...]:
        """(n_apps,) core counts under the oracle split."""
        counts = [0] * self.n_apps
        for a in self.app_of_core:
            counts[a] += 1
        return tuple(counts)

    @property
    def warps_per_app(self) -> Tuple[int, ...]:
        """(n_apps,) warp counts — token budgets and IPC denominators."""
        return tuple(c * self.warps_per_core for c in self.cores_per_app)
