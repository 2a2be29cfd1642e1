"""The program's own trace names: one `jax.named_scope` per memsys stage
in the scan's HLO metadata, and the runner's host spans in a profiler
trace, nested under the entry point that caused them."""
import glob

import jax
import jax.numpy as jnp
import pytest

from repro.core.design import design_params, get_design
from repro.sim import runner
from repro.sim.config import SimConfig

STAGES = ("mem.warp_sched", "mem.translation_probe", "mem.datapath_front",
          "mem.shared_round", "mem.translation_commit", "mem.retire",
          "mem.stats", "mem.epoch")
NESTED = ("mem.fused_tlb", "mem.dram")


@pytest.mark.parametrize("name", ["mask", "ideal"])
def test_every_stage_scope_is_in_the_op_metadata(name):
    cfg = runner._canonical(SimConfig(n_cores=4, warps_per_core=4, n_apps=2,
                                      sim_cycles=4, design=get_design(name)))
    dp = design_params(get_design(name))
    pm = jnp.asarray(runner._mix_matrix(["3DS", "BLK"]))
    text = jax.jit(runner._run_fn(cfg)).lower(dp, pm).as_text(
        debug_info=True)
    segments = {seg for loc in text.split('loc("')[1:]
                for seg in loc.split('"')[0].split("/")}
    # "ideal" compiles translation out: no PWC round, no walk lanes, but
    # its data lanes still take the fused L2$ round and DRAM
    assert set(STAGES) | set(NESTED) <= segments


def _host_spans(logdir):
    """(name, start_ns, end_ns) of every host event in the trace."""
    (path,) = glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True)
    with open(path, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    return [(e.name, e.start_ns, e.end_ns) for p in data.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events]


def test_runner_spans_nest_under_their_entry(tmp_path):
    mixes = [("3DS", "BLK"), ("MUM",)]
    runner.predict_mixes("mask", mixes, cycles=4, slots=2)   # compile
    with jax.profiler.trace(str(tmp_path)):
        runner.predict_mixes("mask", mixes, cycles=4, slots=2)
    events = _host_spans(tmp_path)
    by = {n: [(s, e) for m, s, e in events if m == n] for n in (
        "runner.predict_mixes", "runner.run_grid", "runner.launch",
        "runner.fetch", "runner.unpack")}
    (outer,) = by["runner.predict_mixes"]
    (grid,) = by["runner.run_grid"]
    within = lambda a, b: b[0] <= a[0] and a[1] <= b[1]  # noqa: E731
    assert within(grid, outer)
    # one chunk: launch twice (the workload matrices, then the chunk's
    # rows) and fetch once inside run_grid; unpack twice, the per-row
    # stats in run_grid and the prediction assembly after it
    assert len(by["runner.launch"]) == 2 and len(by["runner.fetch"]) == 1
    assert all(within(x, grid) for x in by["runner.launch"]
               + by["runner.fetch"])
    assert len(by["runner.unpack"]) == 2
    assert sum(within(x, grid) for x in by["runner.unpack"]) == 1
    assert all(within(x, outer) for x in by["runner.unpack"])
    fetch = by["runner.fetch"][0]
    assert all(launch[1] <= fetch[0] for launch in by["runner.launch"])


def test_run_mix_and_sweep_spans(tmp_path):
    runner.run_mix("mask", ["3DS", None], cycles=4)   # compile
    runner.sweep(["mask"], [("3DS", "BLK")], cycles=4)
    with jax.profiler.trace(str(tmp_path)):
        runner.run_mix("mask", ["3DS", None], cycles=4)
        runner.sweep(["mask"], [("3DS", "BLK")], cycles=4)
    names = [n for n, _, _ in _host_spans(tmp_path)]
    for name in ("runner.run_mix", "runner.sweep", "runner.run_grid"):
        assert names.count(name) == 1, name
    # run_mix: one of each phase; sweep: the workload matrices, one chunk
    # and the assembly
    assert names.count("runner.launch") == 3
    assert names.count("runner.fetch") == 2
    assert names.count("runner.unpack") == 3


def test_membership_scope_is_in_the_segment_program():
    from repro.sim import faults
    from repro.sim.memsys import init_state
    cfg = runner._canonical(SimConfig(n_cores=4, warps_per_core=4, n_apps=2,
                                      sim_cycles=4, design=get_design("mask")))
    dp = design_params(get_design("mask"))
    pm = jnp.asarray(runner._mix_matrix(["3DS", "BLK"]))
    fops = jax.tree_util.tree_map(lambda x: x[0],
                                  faults.empty_operands(cfg, 1))
    text = runner._compiled_seg_run(cfg).lower(
        dp, pm, init_state(cfg, dp), jnp.zeros(2, bool), fops).as_text(
        debug_info=True)
    segments = {seg for loc in text.split('loc("')[1:]
                for seg in loc.split('"')[0].split("/")}
    assert "mem.membership" in segments and set(STAGES) <= segments


def test_run_trace_spans_one_boundary_per_boundary(tmp_path):
    schedule = [("3DS", "BLK"), ("3DS", None), ("MUM", None)]
    runner.run_trace("mask", schedule, seg_cycles=4)   # compile
    with jax.profiler.trace(str(tmp_path)):
        runner.run_trace("mask", schedule, seg_cycles=4)
    events = _host_spans(tmp_path)
    by = {n: sorted((s, e) for m, s, e in events if m == n) for n in (
        "runner.run_trace", "runner.boundary", "runner.launch",
        "runner.fetch", "runner.unpack")}
    (outer,) = by["runner.run_trace"]
    within = lambda a, b: b[0] <= a[0] and a[1] <= b[1]  # noqa: E731
    # a launch, fetch and unpack per segment; between two segments one
    # boundary: segment k's fetch and unpack, then segment k+1's launch
    assert len(by["runner.boundary"]) == len(schedule) - 1
    for name in ("runner.launch", "runner.fetch", "runner.unpack"):
        assert len(by[name]) == len(schedule), name
        assert all(within(x, outer) for x in by[name])
    for k, b in enumerate(by["runner.boundary"]):
        assert within(b, outer)
        assert within(by["runner.fetch"][k], b)
        assert within(by["runner.unpack"][k], b)
        assert within(by["runner.launch"][k + 1], b)
        assert by["runner.fetch"][k][1] <= by["runner.launch"][k + 1][0]
    assert not within(by["runner.launch"][0], by["runner.boundary"][0])
