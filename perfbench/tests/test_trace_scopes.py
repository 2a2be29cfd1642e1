"""Device time by the program's named scopes and host time by its spans:
on hand-made events; on programs compiled in this process, whose op names
the readers take from the live executables; on the trace recorded before
the program named anything (the old reduction gives exactly what it gave,
and the new readers read nothing, as on a parent without the names); and
on a trace recorded on one TPU v5e chip with the names in place (16-row
oracle calls of 40 cycles through the harness's traced window, each in a
`perfbench.call` span), whose op names come from its own metadata."""
import collections
import gzip
import hashlib
import json
import os
import types

import pytest

from perfbench import scopes, trace
from perfbench.metrics import _memsys

HERE = os.path.dirname(os.path.abspath(__file__))
UNNAMED = os.path.join(HERE, "fixtures", "oracle_call.xplane.pb.gz")
NAMED = os.path.join(HERE, "fixtures", "oracle_call_scoped.xplane.pb.gz")
METRICS = os.path.join(os.path.dirname(HERE), "metrics")
SCAN = [f"scan.{s}_share" for s in (
    "warp_sched", "translation_probe", "datapath_front", "shared_round",
    "translation_commit", "retire", "stats", "epoch", "unscoped", "dram",
    "fused_tlb")]
PHASES = [f"runner.{p}_ms_per_call" for p in ("launch", "fetch", "unpack")]


def read_metric(name, run):
    from perfbench.run import load_module
    return load_module(os.path.join(METRICS, name + ".py"),
                       "perfbench_metric_" + name).read(run)


def traced_run(path):
    """The harness's trace context for a saved trace, with the op names
    that the trace itself holds (the programs that ran it are not live
    here)."""
    t = trace.load(path)
    (lo, hi), = trace.spans(t, trace.SLICE)
    return types.SimpleNamespace(trace=dict(
        events=t, lo=lo, hi=hi, op_names=scopes.file_op_names(path)))


@pytest.fixture(scope="module")
def unnamed():
    return traced_run(UNNAMED)


@pytest.fixture(scope="module")
def named():
    return traced_run(NAMED)


def test_scope_shares_hand_made():
    stages = ("mem.a", "mem.b")
    ev = [("while", 0, 100),               # a parent: its body is below
          ("f1", 0, 40), ("f2", 40, 70), ("f3", 70, 90),
          ("copy", 150, 160)]              # outside the slice
    paths = ["jit(run)/while",
             "jit(run)/while/body/mem.a/mem.x/scatter:",
             "jit(run)/while/body/mem.b/gather:;mem.a/gather:",
             "jit(run)/while/body/add:", "jit(run)/copy:"]
    t = {"device": {"/device:TPU:0": ev, "/device:TPU:1": ev[1:2]},
         "host": []}
    names = {"/device:TPU:0": paths, "/device:TPU:1": paths[1:2]}
    got = scopes.scope_shares(t, names, 0, 100, stages, nested=("mem.x",))
    # chip 0: leaves f1 40 (a, x), f2 30 (b), f3 20 (none) of 90;
    # chip 1: f1 alone; mean over the two chips
    assert got == pytest.approx({"mem.a": (40 / 90 + 1) / 2,
                                 "mem.b": 30 / 90 / 2, "": 20 / 90 / 2,
                                 "mem.x": (40 / 90 + 1) / 2})
    assert sum(got[s] for s in (*stages, "")) == pytest.approx(1)
    # clipped to the slice: only f2's last 10 ns and f3 lie in [60, 100),
    # and chip 1, with no op there, has no share to count
    half = scopes.scope_shares(t, names, 60, 100, stages)
    assert half["mem.b"] == pytest.approx(10 / 30)
    # a program that names no stage reads nothing
    blank = {p: [""] * len(e) for p, e in t["device"].items()}
    assert scopes.scope_shares(t, blank, 0, 100, stages) is None


def test_phase_ms_per_call_hand_made():
    t = {"device": {"/device:TPU:0": [("a", 10, 20), ("b", 30, 60)]},
         "host": [("perfbench.slice", 0, 200), ("perfbench.call", 5, 100),
                  ("runner.launch", 5, 15), ("runner.fetch", 15, 70),
                  ("runner.unpack", 70, 95), ("perfbench.call", 150, 250),
                  ("runner.fetch", 160, 170)]}
    # one call wholly inside: launch 10 ns less 5 busy, fetch 55 less 35
    assert scopes.phase_ms_per_call(t, 0, 200, "runner.launch") == \
        pytest.approx(5e-6)
    assert scopes.phase_ms_per_call(t, 0, 200, "runner.fetch") == \
        pytest.approx(20e-6)
    assert scopes.phase_ms_per_call(t, 0, 200, "runner.unpack") == \
        pytest.approx(25e-6)
    assert scopes.phase_ms_per_call(t, 0, 200, "runner.none") is None
    assert scopes.phase_ms_per_call(t, 10, 200, "runner.fetch") is None


def _staged_program():
    """A small program compiled here, two of its parts under stage scopes
    and one under a nested scope: (its compiled executable, its module's
    op names)."""
    import jax
    import jax.numpy as jnp
    first, shared = _memsys.STAGES[0], _memsys.STAGES[3]

    @jax.jit
    def f(x):
        with jax.named_scope(first):
            y = jnp.sin(x) * 2
        with jax.named_scope(shared), jax.named_scope(_memsys.NESTED[0]):
            return jnp.cumsum(y) + x[::-1]

    exe = f.lower(jnp.ones(1000)).compile()
    return exe, scopes.hlo_op_names(exe.as_text())


def test_hlo_op_names_reads_every_instruction():
    exe, ops = _staged_program()
    text = exe.as_text()
    lines = [ln for ln in text.splitlines() if " = " in ln
             and not ln.startswith("HloModule")]
    assert len(ops) == len(lines)
    named = {op for op in ops.values() if op}
    assert any(f"/{_memsys.STAGES[0]}/" in op for op in named)
    assert any(f"/{_memsys.STAGES[3]}/{_memsys.NESTED[0]}/" in op
               for op in named)
    assert "" in ops.values()      # parameters' and copies' names


def test_assign_cuts_a_line_by_module():
    a = {"x": "A/x", "y": "A/y", "z": "A/z"}
    b = {"x": "B/x", "y": "B/y", "w": "B/w"}
    # x and y are in both; z only in a, w only in b; q in neither
    got = scopes.assign(["x", "z", "y", "x", "w", "y", "q", "y"], [a, b])
    assert got == ["A/x", "A/z", "A/y", "A/x", "B/w", "B/y", "", "B/y"]
    assert scopes.assign(["y", "x"], [b, a]) == ["B/y", "B/x"]
    assert scopes.assign(["q"], []) == [""]


def test_live_modules_hold_the_programs_of_this_process():
    exe, ops = _staged_program()
    assert ops in scopes.live_modules()


def test_readers_take_op_names_from_the_live_programs(monkeypatch):
    """Without op names of its own, the trace context is read by the
    programs this process holds: device events named by a live program's
    instructions read that program's stage shares."""
    exe, ops = _staged_program()
    decoy = {name: "jit(other)/mem.epoch/x" for name in ops}
    monkeypatch.setattr(scopes, "live_modules", lambda: [ops, decoy])
    entry = [name for name, op in ops.items() if op]
    ev = [("%" + name, 10 * k, 10 * k + 10) for k, name in enumerate(entry)]
    n = len(ev) + 1
    ev.append(("%not.an.instruction", 10 * len(ev), 10 * n))
    t = {"device": {"/device:TPU:0": ev}, "host": []}
    run = types.SimpleNamespace(trace=dict(events=t, lo=0, hi=10 * n))
    got = {n: read_metric(n, run) for n in SCAN}
    assert sum(got[n] for n in SCAN[:9]) == pytest.approx(1, abs=1e-6)
    want = collections.Counter(
        next((g for g in ops[n].split("/") if g in _memsys.STAGES), "")
        for n in entry)
    assert want[_memsys.STAGES[0]] and want[_memsys.STAGES[3]]
    assert got["scan.warp_sched_share"] == pytest.approx(
        want[_memsys.STAGES[0]] / n)
    assert got["scan.unscoped_share"] == pytest.approx((want[""] + 1) / n)
    assert got["scan.epoch_share"] == 0


def test_scope_walk_maps_every_device_event():
    with gzip.open(UNNAMED, "rb") as f:
        walked = scopes.tf_ops(f.read())
    from jax.profiler import ProfileData
    with gzip.open(UNNAMED, "rb") as f:
        data = ProfileData.from_serialized_xspace(f.read())
    t = trace.load(UNNAMED)
    names = scopes.file_op_names(UNNAMED)
    for plane in data.planes:
        if plane.name not in t["device"]:
            continue
        ops = [e for line in plane.lines if line.name == trace.DEVICE_LINE
               for e in line.events]
        assert all(e.name in walked[plane.name] for e in ops)
        assert len(names[plane.name]) == len(t["device"][plane.name])
        assert names[plane.name] == [walked[plane.name][e.name]
                                     for e in ops]
    assert set(names) == set(t["device"])
    paths = [p for ps in names.values() for p in ps]
    assert sum(bool(p) for p in paths) > 0.9 * len(paths)
    assert all(p.startswith("jit(") for p in paths if p)


def test_old_reduction_unchanged(unnamed):
    """`reduce` and `host_ms_per_call` give exactly what they gave before
    the program named its parts (the digest of the full result, from
    then)."""
    t = unnamed.trace
    red = trace.reduce(t["events"], t["lo"], t["hi"])
    host = trace.host_ms_per_call(t["events"], t["lo"], t["hi"])
    assert red["busy_s"] == 0.177920728 and red["window_s"] == 0.30015182
    assert red["idle_share"] == 0.40723088735560564
    assert host == 31.27904733333333
    digest = hashlib.sha256(json.dumps([red, host]).encode()).hexdigest()
    assert digest == ("05912040e337d906cdd977f9a75b7b647048715c65a9b7f3"
                      "9b7b2201a093e8f0")


@pytest.mark.parametrize("name", SCAN + PHASES)
def test_unnamed_program_reads_nothing(unnamed, name):
    unnamed.trace.pop("memsys_shares", None)
    assert read_metric(name, unnamed) is None


@pytest.mark.parametrize("name", SCAN + PHASES)
def test_no_trace_reads_nothing(name):
    assert read_metric(name, types.SimpleNamespace(trace=None)) is None


def test_named_stage_shares_sum_to_one(named):
    t = named.trace
    shares = scopes.scope_shares(t["events"], t["op_names"], t["lo"],
                                 t["hi"], _memsys.STAGES, _memsys.NESTED)
    stages = [shares[s] for s in _memsys.STAGES]
    assert all(x > 0 for x in stages)
    assert sum(stages) + shares[""] == pytest.approx(1, abs=1e-6)
    # the nested rounds lie inside the stages that call them
    assert 0 < shares["mem.dram"] < shares["mem.shared_round"]
    assert 0 < shares["mem.fused_tlb"] < (shares["mem.shared_round"]
                                          + shares["mem.translation_probe"])


def test_named_readers_read_every_metric(named):
    named.trace.pop("memsys_shares", None)
    got = {n: read_metric(n, named) for n in SCAN + PHASES}
    assert all(v is not None and v >= 0 for v in got.values()), got
    assert sum(got[n] for n in SCAN[:9]) == pytest.approx(1, abs=1e-6)
    host = trace.host_ms_per_call(named.trace["events"], named.trace["lo"],
                                  named.trace["hi"])
    assert 0 < sum(got[n] for n in PHASES) <= host


def test_fetch_spans_share_the_device_clock(named):
    """Every `runner.fetch` span waits for the scan, so device-busy time
    lies inside it: the program's spans and the device ops share one
    clock."""
    t, lo, hi = (named.trace[k] for k in ("events", "lo", "hi"))
    fetches = [(s, e) for s, e in trace.spans(t, "runner.fetch")
               if lo <= s and e <= hi]
    assert fetches
    for s, e in fetches:
        assert all(trace.busy_ns(ev, s, e) > 0 for ev in t["device"].values())
    # and each sits inside a harness call, after that call's launch
    calls = trace.spans(t, "perfbench.call")
    launches = trace.spans(t, "runner.launch")
    for s, e in fetches:
        (cs, ce), = [(a, b) for a, b in calls if a <= s and e <= b]
        assert any(cs <= a and b <= s for a, b in launches)
