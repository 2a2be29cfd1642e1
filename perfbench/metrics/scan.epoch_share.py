"""Share of the scan's leaf device time in the epoch's token hill-climb,
DRAM pressure and bypass latch: ops under `mem.epoch`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.epoch")
