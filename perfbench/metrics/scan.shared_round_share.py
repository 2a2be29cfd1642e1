"""Share of the scan's leaf device time in the shared L2$ + DRAM round
(stage 3), with its fused-TLB and DRAM parts: ops under
`mem.shared_round`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.shared_round")
