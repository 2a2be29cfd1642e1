"""Reduce a profiler trace to device busy time, idle share, host spans and
the `breakdown` of the result line.

`load` reads the `.xplane.pb` that `jax.profiler` writes into plain event
lists; everything after that is arithmetic on (name, start_ns, end_ns)
tuples, so it is tested on a recorded trace.
"""
from __future__ import annotations

import collections
import gzip
from typing import Dict, List, Tuple

Event = Tuple[str, float, float]           # name, start_ns, end_ns

DEVICE_LINE = "XLA Ops"
SLICE = "perfbench.slice"        # the traced slice, on the tracing thread


def load(path: str) -> dict:
    """{"device": {plane: [Event]}, "host": [Event]} from an xplane file
    (gzipped if its name ends in .gz). Device events are the operations of
    each accelerator plane's "XLA Ops" line; host events are the events of
    the host planes, less the tracing thread's own: of the thread that
    holds the "perfbench.slice" span only that span is kept."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    device: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [ev for line in plane.lines if line.name == DEVICE_LINE
                   for ev in line.events]
            if ops:
                # an op's event is named by its whole HLO instruction;
                # keep the instruction's name ("%fusion.411")
                device[plane.name] = [(e.name.split(" = ")[0], e.start_ns,
                                       e.end_ns) for e in ops]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                ev = [(e.name, e.start_ns, e.end_ns) for e in line.events]
                mine = [e for e in ev if e[0] == SLICE]
                host.extend(mine or ev)
    return {"device": device, "host": host}


def union(intervals) -> List[Tuple[float, float]]:
    """Merged, sorted (start, end) intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    """Time in [lo, hi) during which some operation ran."""
    return sum(e - s for s, e in clip(union((s, e) for _, s, e in events),
                                      lo, hi))


def spans(trace: dict, name: str) -> List[Tuple[float, float]]:
    return sorted((s, e) for n, s, e in trace["host"] if n == name)


def reduce(trace: dict, lo: float, hi: float, top: int = 10) -> dict:
    """Busy seconds (mean over devices), window seconds, idle share, the
    device operations that took most time and the longest idle gaps, each
    gap named by the innermost host event around its middle."""
    window = hi - lo
    planes = trace["device"]
    if not planes or window <= 0:
        return {}
    busy = [busy_ns(ev, lo, hi) for ev in planes.values()]
    op_time: Dict[str, float] = collections.Counter()
    gaps = []
    for ev in planes.values():
        for name, s, e in ev:
            for cs, ce in clip([(s, e)], lo, hi):
                op_time[name] += ce - cs
        merged = clip(union((s, e) for _, s, e in ev), lo, hi)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    gaps.sort(key=lambda g: g[0] - g[1])
    host = trace["host"]

    def what(g):
        mid = (g[0] + g[1]) / 2
        around = [(e - s, n) for n, s, e in host
                  if s <= mid < e and n != SLICE]
        return min(around)[1] if around else "no host event"

    busy_s = sum(busy) / len(busy) / 1e9
    return {
        "busy_s": busy_s,
        "window_s": window / 1e9,
        "idle_share": 1.0 - busy_s / (window / 1e9),
        "device_ops": [[n, t / 1e9] for n, t in
                       sorted(op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[what(g), (g[1] - g[0]) / 1e9] for g in gaps[:top]],
    }


def host_ms_per_call(trace: dict, lo: float, hi: float,
                     name: str = "perfbench.call"):
    """Mean host time of the calls wholly inside [lo, hi): each call's span
    less the device-busy time inside it (mean over devices). None when no
    call lies wholly inside."""
    calls = [(s, e) for s, e in spans(trace, name) if s >= lo and e <= hi]
    planes = trace["device"]
    if not calls or not planes:
        return None
    per = [(e - s) - sum(busy_ns(ev, s, e) for ev in planes.values())
           / len(planes) for s, e in calls]
    return sum(per) / len(per) / 1e6
