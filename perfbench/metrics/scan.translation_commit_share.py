"""Share of the scan's leaf device time in walk latencies and walk-table
installs (stage 4): ops under `mem.translation_commit`, mean over chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.translation_commit")
