"""MASK core: the paper's contribution as composable pure-JAX policy modules.

  asid        — address spaces / protection domains (§5.1)
  page_table  — multi-level radix walks, PTE line addressing (§3)
  tlb         — set-associative ASID-tagged TLB state (L1/L2/bypass cache)
  tokens      — TLB-Fill Tokens epoch controller (§5.2)
  bypass      — TLB-request-aware L2 data-cache bypass (§5.3)
  dram_sched  — golden/silver/normal scheduler with Eq. (1) quotas (§5.4)
  design      — composable design points: per-layer policy specs +
                registry (register_design / get_design / list_designs)
"""
from repro.core.design import (PAPER_DESIGNS, BypassSpec,  # noqa: F401
                               Design, DramSpec, PartitionSpec, TokenSpec,
                               TranslationSpec, get_design, list_designs,
                               register_design)
