"""Host time per segment boundary of `run_trace`: each `runner.boundary`
span (segment k's fetch and unpack, then segment k+1's launch) that lies
wholly inside the traced slice, less the device-busy time inside it (mean
over chips), in milliseconds, averaged over those spans."""
from perfbench import trace


def read(run):
    t = run.trace
    return trace.host_ms_per_call(t["events"], t["lo"], t["hi"],
                                  "runner.boundary") if t else None
