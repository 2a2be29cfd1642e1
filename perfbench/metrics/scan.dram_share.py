"""Share of the scan's leaf device time in the DRAM scheduler round, nested
in the shared round: ops under `mem.dram`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.dram")
