"""Device time by the program's named scopes and host time by its spans.

A device op's scope is the HLO `op_name` of its instruction, a path such as
`jit(run)/vmap()/while/body/mem.shared_round/mem.dram/scatter:` that
`jax.named_scope` writes into the op's metadata. `trace.load` keeps each op
by its instruction name alone ("%fusion.411"), so the op names come from the
compiled programs:

- `live_op_names` reads them from the executables alive in this process,
  which are the programs a traced window has just run;
- `file_op_names` reads them from a saved trace, where the profiler keeps
  them as the "tf_op" stat of each event's metadata, which
  `jax.profiler.ProfileData` does not expose: a protobuf wire walk
  (`tf_ops`) reads them.

Both give, for each device plane of a loaded trace, a list of op names
aligned with `trace["device"][plane]` ("" where an op has none).
"""
from __future__ import annotations

import bisect
import collections
import gzip
import re
from typing import Dict, List, Optional, Sequence, Tuple

from perfbench import trace as tr

OpNames = Dict[str, List[str]]     # plane -> op name of each device event

_INSTR = re.compile(r"^\s*(?:ROOT )?%?(\S+) = (.*)$", re.M)
_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')


def hlo_op_names(text: str) -> Dict[str, str]:
    """{instruction name: its metadata op_name, or ""} of an HLO module's
    text, every computation's instructions included."""
    out = {}
    for name, rest in _INSTR.findall(text):
        m = _OP_NAME.search(rest)
        out[name] = m.group(1) if m else ""
    return out


def assign(names: Sequence[str], modules: Sequence[Dict[str, str]]
           ) -> List[str]:
    """Op name of each event of one device line, given in time order by
    instruction name, from the modules that may have run them.

    Instruction names are unique within a module but not across modules,
    and a module runs its ops as one unbroken stretch of the line. So the
    line is cut into the fewest stretches that one module each explains:
    a stretch goes on while some module holds every name in it. An event
    whose name no module holds gets ""."""
    holders: Dict[str, frozenset] = {}
    for name in set(names):
        holders[name] = frozenset(
            k for k, m in enumerate(modules) if name in m)
    out = [""] * len(names)
    stretch: List[int] = []
    live: frozenset = frozenset()

    def close():
        if stretch:
            m = modules[min(live)]
            for i in stretch:
                out[i] = m[names[i]]

    for i, name in enumerate(names):
        has = holders[name]
        if not has:
            continue
        if stretch and live & has:
            live &= has
        else:
            close()
            stretch, live = [], has
        stretch.append(i)
    close()
    return out


def live_modules() -> List[Dict[str, str]]:
    """`hlo_op_names` of every HLO module of the executables alive on the
    default backend."""
    import jax
    client = jax.local_devices()[0].client
    return [hlo_op_names(m.to_string())
            for exe in client.live_executables() for m in exe.hlo_modules()]


def live_op_names(events: dict) -> OpNames:
    """Op names of a loaded trace's device events, from the programs this
    process has compiled and still holds."""
    modules = live_modules()
    out: OpNames = {}
    for plane, ev in events["device"].items():
        order = sorted(range(len(ev)), key=lambda i: ev[i][1])
        got = assign([ev[i][0].lstrip("%") for i in order], modules)
        names = [""] * len(ev)
        for i, op in zip(order, got):
            names[i] = op
        out[plane] = names
    return out


# --- a protobuf wire walk over the XSpace, for the stats of each event's
# metadata. Field numbers are tensorflow/tsl's xplane.proto: XSpace.planes
# 1; XPlane.name 2, .event_metadata 4, .stat_metadata 5 (maps: key 1,
# value 2); XEventMetadata.name 2, .stats 5; XStatMetadata.name 2;
# XStat.metadata_id 1, .str_value 5.

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        shift += 7
        if byte < 0x80:
            return value, i


def _fields(buf: bytes, lo: int, hi: int):
    """(field number, value) of one message in buf[lo:hi]; a
    length-delimited value is its (start, end) in buf, which is skipped
    over unread, so planes' event lines cost nothing."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 1:
            value, i = None, i + 8
        elif kind == 2:
            n, i = _varint(buf, i)
            value, i = (i, i + n), i + n
        elif kind == 5:
            value, i = None, i + 4
        else:
            raise ValueError(f"xplane: wire type {kind} at byte {i}")
        yield key >> 3, value


def _map_entries(buf: bytes, fields, number: int):
    for f, span in fields:
        if f == number:
            entry = dict(_fields(buf, *span))
            yield entry.get(1), entry.get(2)


def tf_ops(raw: bytes) -> Dict[str, Dict[str, str]]:
    """{plane name: {event metadata name: its "tf_op" stat, or ""}} from
    a serialized XSpace, for the planes whose stats include "tf_op". An
    event's metadata name is its whole HLO text, which is also the name
    `jax.profiler.ProfileData` gives the event."""
    def text(span):
        return raw[span[0]:span[1]].decode("utf-8", "replace")

    out: Dict[str, Dict[str, str]] = {}
    for f, plane_span in _fields(raw, 0, len(raw)):
        if f != 1:
            continue
        plane = list(_fields(raw, *plane_span))
        tf_op = {key for key, meta in _map_entries(raw, plane, 5)
                 for g, v in _fields(raw, *meta)
                 if g == 2 and text(v) == "tf_op"}
        if not tf_op:
            continue
        names: Dict[str, str] = {}
        for _, meta in _map_entries(raw, plane, 4):
            name, op = None, None
            for g, v in _fields(raw, *meta):
                if g == 2:
                    name = text(v)
                elif g == 5:
                    stat = dict(_fields(raw, *v))
                    if stat.get(1) in tf_op and 5 in stat:
                        op = text(stat[5])
            if name is not None and (op or name not in names):
                names[name] = op or ""
        plane_name = next((text(v) for g, v in plane if g == 2), "")
        out[plane_name] = names
    return out


def file_op_names(path: str) -> OpNames:
    """Op names of the device events that `trace.load(path)` gives, from
    the saved trace's own event metadata."""
    from jax.profiler import ProfileData
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        raw = f.read()
    walked = tf_ops(raw)
    out: OpNames = {}
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/device:") and "CPU" not in plane.name:
            ops = [e for line in plane.lines if line.name == tr.DEVICE_LINE
                   for e in line.events]
            if ops:
                names = walked.get(plane.name, {})
                out[plane.name] = [names.get(e.name, "") for e in ops]
    return out


def _leaves(events: List[tr.Event]) -> List[int]:
    """Indices of the events that enclose no other event of their line
    (a `while` or `conditional` encloses the ops of its body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    out = []
    for k, i in enumerate(order):
        _, s, e = events[i]
        if k + 1 < len(order):
            _, s2, e2 = events[order[k + 1]]
            if s2 < e and e2 <= e:
                continue
        out.append(i)
    return out


def scope_shares(events: dict, op_names: OpNames, lo: float, hi: float,
                 scopes: Sequence[str], nested: Sequence[str] = ()
                 ) -> Optional[Dict[str, float]]:
    """Device time of the leaf ops in [lo, hi) by the program's named
    scopes, as shares of all leaf time there, mean over devices.

    An op counts toward the first segment of its op name that is one of
    `scopes`, or toward "" when none is; each of `nested` gets the share of
    ops with that segment anywhere in their op name. None when no op in
    the slice carries any of `scopes`, as in a program that names none of
    them."""
    wanted = set(scopes)
    per_plane, named = [], False
    for plane, ev in events["device"].items():
        paths = op_names.get(plane, [""] * len(ev))
        time: Dict[str, float] = collections.Counter()
        total = 0.0
        for i in _leaves(ev):
            _, s, e = ev[i]
            dt = min(e, hi) - max(s, lo)
            if dt <= 0:
                continue
            segs = paths[i].split("/")
            top = next((g for g in segs if g in wanted), "")
            time[top] += dt
            for g in nested:
                if g in segs:
                    time[g] += dt
            total += dt
        named |= any(time[g] for g in scopes)
        if total:
            per_plane.append({k: time[k] / total
                              for k in (*scopes, "", *nested)})
    if not named or not per_plane:
        return None
    return {k: sum(p[k] for p in per_plane) / len(per_plane)
            for k in per_plane[0]}


def phase_ms_per_call(events: dict, lo: float, hi: float,
                      phase: str) -> Optional[float]:
    """Mean host time per call of the program's `phase` spans: for each
    "perfbench.call" span wholly inside [lo, hi), the `phase` spans inside
    it, each less the device-busy time inside it (mean over devices),
    summed. None when no such call holds a `phase` span."""
    calls = [(s, e) for s, e in tr.spans(events, "perfbench.call")
             if s >= lo and e <= hi]
    phases = tr.spans(events, phase)
    planes = events["device"]
    if not calls or not planes or not phases:
        return None
    merged = [tr.union((s, e) for _, s, e in ev) for ev in planes.values()]
    starts = [[s for s, _ in m] for m in merged]

    def busy(s, e):
        total = 0.0
        for m, st in zip(merged, starts):
            k = max(bisect.bisect_right(st, s) - 1, 0)
            while k < len(m) and m[k][0] < e:
                total += max(min(m[k][1], e) - max(m[k][0], s), 0)
                k += 1
        return total / len(merged)

    per, seen = [], False
    for cs, ce in calls:
        inside = [(s, e) for s, e in phases if s >= cs and e <= ce]
        seen |= bool(inside)
        per.append(sum((e - s) - busy(s, e) for s, e in inside))
    return sum(per) / len(per) / 1e6 if seen else None
