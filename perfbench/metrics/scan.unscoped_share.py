"""Share of the leaf device time under none of the eight memsys stage
scopes: the scan's loop control and whatever runs outside the scan
(eager helpers, copies), mean over chips. With the eight stage shares it
sums to 1."""
from perfbench.metrics._memsys import UNSCOPED, share


def read(run):
    return share(run, UNSCOPED)
