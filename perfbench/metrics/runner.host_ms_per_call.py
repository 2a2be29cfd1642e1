"""Host time per entry call: each call span that lies wholly inside the
traced slice, less the device-busy time inside it, in milliseconds."""
from perfbench import trace


def read(run):
    t = run.trace
    return trace.host_ms_per_call(t["events"], t["lo"], t["hi"]) if t \
        else None
