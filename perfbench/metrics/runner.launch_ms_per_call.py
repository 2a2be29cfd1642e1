"""Host time per entry call in the runner's `runner.launch` spans (building
and placing the stacked rows and enqueueing the program): each span
inside a call that lies wholly in the traced slice, less the device-busy
time inside it, in milliseconds."""
from perfbench import scopes


def read(run):
    t = run.trace
    return scopes.phase_ms_per_call(t["events"], t["lo"], t["hi"],
                                    "runner.launch") if t else None
