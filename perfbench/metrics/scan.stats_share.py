"""Share of the scan's leaf device time in the per-app statistics planes:
ops under `mem.stats`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.stats")
