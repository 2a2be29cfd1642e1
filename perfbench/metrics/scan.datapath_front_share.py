"""Share of the scan's leaf device time in the L1D draw and data line
addresses (stage 2b): ops under `mem.datapath_front`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.datapath_front")
