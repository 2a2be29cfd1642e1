"""Design-vectorized sweep coverage (static/traced design split).

Three layers:

  * `static_signature` / `canonical_design` / `design_params` contracts —
    the signature is hashable, stable under dynamic-knob changes, and
    sensitive to every shape/structure knob; the paper's 8 designs group
    into exactly TWO signatures (ideal + everything else).
  * grid == loop, bit-for-bit — `run_grid` / grid `sweep` reproduce the
    per-design `run_mix` / `Experiment` path exactly (float-hex, all 8
    designs x n_apps in {1, 2}), which chains through the pinned goldens
    in test_memsys_stages.py.
  * compile accounting — a full 8-design sweep traces exactly one
    program per signature group (TRACE_COUNT), and repeating it traces
    nothing new.
"""
import numpy as np
import pytest

from repro.core.design import (PAPER_DESIGNS, DesignParams, canonical_design,
                               design_params, get_design, static_signature)
from repro.sim import runner
from repro.sim.runner import Experiment, run_grid, run_mix, sweep

CYCLES = 1_200       # matches the float-hex goldens' executable


# ------------------------------------------------------ signature contracts

def test_builtin_designs_group_into_two_signatures():
    sigs = {name: static_signature(get_design(name)) for name in PAPER_DESIGNS}
    groups = {}
    for name, sig in sigs.items():
        groups.setdefault(sig, []).append(name)
    assert len(groups) == 2
    assert groups[sigs["ideal"]] == ["ideal"]
    assert sorted(groups[sigs["mask"]]) == sorted(
        n for n in PAPER_DESIGNS if n != "ideal")


def test_signature_stable_under_dynamic_knobs():
    """Dynamic (traced) knobs — policy selectors, token fracs, DRAM quota,
    partitioning, the name — must NOT change the compile key."""
    mask = get_design("mask")
    sig = static_signature(mask)
    for variant in (
            mask.with_(name="x"),
            mask.with_(tokens=dict(enabled=False, initial_frac=0.9,
                                   step_frac=0.1)),
            mask.with_(bypass=dict(enabled=False)),
            mask.with_(dram=dict(kind="fr_fcfs", thres_max=77)),
            mask.with_(partition=dict(kind="static")),
            mask.with_(translation=dict(kind="pwc")),   # non-ideal org
    ):
        assert static_signature(variant) == sig, variant
        assert hash(static_signature(variant)) == hash(sig)
        assert canonical_design(static_signature(variant)) == \
            canonical_design(sig)


def test_signature_sensitive_to_static_knobs():
    """Shape/structure knobs each produce a distinct signature."""
    mask = get_design("mask")
    base = static_signature(mask)
    variants = [
        mask.with_(translation=dict(kind="ideal")),
        mask.with_(translation=dict(l1_entries=32)),
        mask.with_(translation=dict(l2_entries=1024)),
        mask.with_(translation=dict(l2_ways=8)),
        mask.with_(translation=dict(walk_levels=3)),
        mask.with_(translation=dict(max_concurrent_walks=32)),
        mask.with_(tokens=dict(bypass_cache_entries=64)),
        mask.with_(epoch_cycles=4_000),
    ]
    sigs = [static_signature(v) for v in variants]
    assert all(s != base for s in sigs)
    assert len(set(sigs)) == len(sigs)


def test_design_params_values_and_dtypes():
    dp = design_params(get_design("mask"))
    assert isinstance(dp, DesignParams)
    assert bool(dp.use_l2_tlb) and not bool(dp.use_pwc)
    assert bool(dp.tokens_on) and bool(dp.bypass_on) and bool(dp.dram_on)
    assert not bool(dp.static_part)
    assert float(dp.initial_frac) == pytest.approx(0.25)
    assert int(dp.thres_max) == 500
    for leaf in dp:
        assert leaf.shape == ()
    dp_pwc = design_params(get_design("pwc"))
    assert bool(dp_pwc.use_pwc) and not bool(dp_pwc.use_l2_tlb)
    assert not bool(dp_pwc.tokens_on)
    assert bool(design_params(get_design("static")).static_part)


# ------------------------------------------------------- grid == loop exact

def _hexed(s):
    return {k: [x.hex() for x in
                np.asarray(v, np.float64).ravel().tolist()] for k, v in
            s.items()}


@pytest.mark.parametrize("n_apps,mix", [(1, ("3DS",)), (2, ("3DS", "BLK"))])
def test_grid_matches_loop_bitforbit(n_apps, mix):
    """run_grid over all 8 designs == per-design run_mix, float-hex exact
    (so the grid path inherits the GOLDEN pins of test_memsys_stages)."""
    grid = run_grid(list(PAPER_DESIGNS), [mix], cycles=CYCLES)
    for i, name in enumerate(PAPER_DESIGNS):
        loop = _hexed(run_mix(name, list(mix), cycles=CYCLES))
        got = _hexed(grid[i][0])
        assert got == loop, f"{name} n_apps={n_apps} drifted from loop"


def test_sweep_grid_matches_experiment_loop():
    """Grid-path sweep == per-design Experiment loop: same raw stats
    (float-hex), same derived metrics, same solo-baseline bookkeeping."""
    designs = ["ideal", "gpu-mmu", "mask"]
    mixes = [("3DS", "BLK"), ("MUM", "RED")]
    g = sweep(designs, mixes, cycles=CYCLES, grid=True)
    for name in designs:
        ell = Experiment(name, mixes, cycles=CYCLES).run()
        assert set(g) == set(designs)
        gres = g[name]
        assert gres.solo_ipc == ell.solo_ipc
        assert len(gres) == len(ell)
        for rg, rl in zip(gres, ell):
            assert rg.benches == rl.benches
            assert _hexed(rg.raw) == _hexed(rl.raw)
            assert rg.weighted_speedup() == rl.weighted_speedup()
            assert rg.unfairness() == rl.unfairness()


# --------------------------------------------------------- compile counting

def test_full_sweep_traces_one_program_per_signature_group():
    """The 8-design x 2-mix sweep (solo baselines included) compiles
    exactly len(signature groups) == 2 programs; re-running it compiles
    nothing."""
    mixes = [("3DS", "BLK"), ("MUM", "RED")]
    cycles = 977          # unique -> cannot reuse another test's programs
    before = runner.TRACE_COUNT
    res = sweep(list(PAPER_DESIGNS), mixes, cycles=cycles)
    assert runner.TRACE_COUNT - before == 2, \
        "expected ONE traced program per signature group"
    assert set(res) == set(PAPER_DESIGNS)
    again = sweep(list(PAPER_DESIGNS), mixes, cycles=cycles)
    assert runner.TRACE_COUNT - before == 2, "re-sweep must not retrace"
    for name in PAPER_DESIGNS:
        for a, b in zip(res[name], again[name]):
            assert _hexed(a.raw) == _hexed(b.raw)
