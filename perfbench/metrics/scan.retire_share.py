"""Share of the scan's leaf device time in the data latencies, warp retire
and token record: ops under `mem.retire`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.retire")
