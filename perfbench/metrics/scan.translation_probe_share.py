"""Share of the scan's leaf device time in the TLB probes, PWC round and
walk set-up (stage 2a): ops under `mem.translation_probe`, mean over
chips."""
from perfbench.metrics._memsys import share


def read(run):
    return share(run, "mem.translation_probe")
