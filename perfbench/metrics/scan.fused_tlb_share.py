"""Share of the scan's leaf device time in the fused TLB probe+fill rounds,
nested in the PWC round and the L2$: ops under `mem.fused_tlb`, mean
over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.fused_tlb")
