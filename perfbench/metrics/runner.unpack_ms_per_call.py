"""Host time per entry call in the runner's `runner.unpack` spans (per-row
slicing, `_stats` and assembling the answers): each span inside a call
that lies wholly in the traced slice, less the device-busy time inside
it, in milliseconds."""
from perfbench import scopes


def read(run):
    t = run.trace
    return scopes.phase_ms_per_call(t["events"], t["lo"], t["hi"],
                                    "runner.unpack") if t else None
