"""Share of the traced slice in which no operation ran on the device
(mean over the cell's chips): 1 - busy / window."""


def read(run):
    red = run.trace["reduced"] if run.trace else None
    return red["idle_share"] if red else None
