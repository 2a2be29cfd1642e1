"""Share of the scan's leaf device time in warp scheduling (stage 1): ops
under `mem.warp_sched`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.warp_sched")
