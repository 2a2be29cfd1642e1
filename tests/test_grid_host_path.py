"""The grid call's host path: a chunk's rows stacked with numpy on the
host and placed in one transfer, only the leaves `_stats` reads fetched,
and one `_stats` pass over all of the chunk's rows.

Every row `run_grid` returns must equal what the per-row path gave: the
rows stacked with eager device ops, the whole final state fetched, and the
stats arithmetic run on each row's slice alone (`_per_row_grid` and
`_per_row_stats` below keep that path as it was), float-hex, with the same
keys, types, dtypes and shapes.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.design import (PAPER_DESIGNS, canonical_design,
                               design_params, get_design, host_design_params,
                               static_signature)
from repro.sim import audit, runner
from repro.sim.config import SimConfig
from repro.sim.workloads import BENCHES, app_matrix, app_row, make_app

CYCLES = 60
MIXES = {
    1: [("3DS",), ("BLK",), ("MUM",)],
    2: [("3DS", "BLK"), ("MUM", None), ("RED", "3DS")],
    4: [("3DS", "BLK", "MUM", "RED"), ("BLK", None, None, None),
        ("RED", "MUM", None, None)],
}
STATS_ROW_BYTES = 8 * 1024


def _per_row_stats(cfg, st):
    """The stats of one fully fetched state, as the per-row path
    computed them."""
    na = cfg.n_apps
    warp_app = np.repeat(np.asarray(cfg.app_of_core), cfg.warps_per_core)
    t = float(st.t)
    ipc = np.bincount(warp_app, weights=st.instr, minlength=na) / t
    s = st.stats
    g = lambda x: np.asarray(x, np.float64)  # noqa: E731
    l1p = g(s.s_l1_hit) + g(s.s_l1_miss)
    l2p = g(s.s_l2_hit) + g(s.s_l2_miss)
    return {
        "ipc": ipc,
        "l1_hit_rate": g(s.s_l1_hit) / np.maximum(l1p, 1),
        "l1_miss_rate": g(s.s_l1_miss) / np.maximum(l1p, 1),
        "l2_hit_rate": g(s.s_l2_hit) / np.maximum(l2p, 1),
        "l2_miss_rate": g(s.s_l2_miss) / np.maximum(l2p, 1),
        "byp_hit_rate": g(s.s_byp_hit) / np.maximum(g(s.s_byp_probe), 1),
        "walk_lat": g(s.s_walk_lat) / np.maximum(g(s.s_walks), 1),
        "walks": g(s.s_walks),
        "stalls_per_miss": g(s.s_stall_per_miss) / np.maximum(g(s.s_walks), 1),
        "dram_tlb_lat": g(s.s_dram_tlb_lat) / np.maximum(g(s.s_dram_tlb_n), 1),
        "dram_data_lat": g(s.s_dram_data_lat)
        / np.maximum(g(s.s_dram_data_n), 1),
        "dram_tlb_n": g(s.s_dram_tlb_n),
        "dram_data_n": g(s.s_dram_data_n),
        "l2c_tlb_hit_rate": (g(s.s_l2c_tlb_hit)
                             / np.maximum(g(s.s_l2c_tlb_probe), 1)),
        "l2c_data_hit_rate": (g(s.s_l2c_data_hit)
                              / np.maximum(g(s.s_l2c_data_probe), 1)),
        "tokens": np.asarray(st.tokens.tokens),
        "cycles": float(st.t),
    }


def _eager_rows(designs, mixes):
    """A group's (DesignParams, params_mat) rows stacked with eager device
    ops, design-major."""
    M = len(mixes)
    dp = jax.tree_util.tree_map(
        lambda *leaves: jnp.repeat(jnp.stack(leaves), M, axis=0),
        *[design_params(d) for d in designs])
    pms = np.stack([app_matrix(list(m)) for m in mixes])
    return dp, jnp.asarray(np.tile(pms, (len(designs), 1, 1)))


def _per_row_grid(designs, mixes, cycles):
    """`run_grid` (one chunk per signature group) as the per-row path ran
    it: eager stacking, the whole final state fetched, then a slice of
    every leaf and one stats pass per row."""
    ds = [get_design(d) for d in designs]
    out = [[None] * len(mixes) for _ in ds]
    groups = {}
    for i, d in enumerate(ds):
        groups.setdefault(static_signature(d), []).append(i)
    for sig, idxs in groups.items():
        ccfg = SimConfig(n_apps=len(mixes[0]), sim_cycles=cycles,
                         design=canonical_design(sig))
        final = jax.device_get(runner._compiled_grid_run(ccfg)(
            *_eager_rows([ds[i] for i in idxs], mixes)))
        for g, di in enumerate(idxs):
            for m in range(len(mixes)):
                row = jax.tree_util.tree_map(
                    lambda x, r=g * len(mixes) + m: x[r], final)
                out[di][m] = _per_row_stats(ccfg, row)
    return out


def _described(s):
    """Each value's type, dtype, shape and float-hex digits."""
    return {k: (type(v), np.asarray(v).dtype, np.shape(v),
                [x.hex() for x in np.asarray(v, np.float64).ravel().tolist()])
            for k, v in s.items()}


@pytest.mark.parametrize("pad_rows", [0, 4])
@pytest.mark.parametrize("n_apps", [1, 2, 4])
def test_grid_rows_equal_per_row_stats_of_whole_state(n_apps, pad_rows):
    mixes = list(MIXES[n_apps])
    if pad_rows:        # as `predict_mixes` pads: repeat the last row
        mixes += [mixes[-1]] * ((-len(mixes)) % pad_rows)
    grid = runner.run_grid(list(PAPER_DESIGNS), mixes, cycles=CYCLES)
    traces = runner.TRACE_COUNT
    ref = _per_row_grid(list(PAPER_DESIGNS), mixes, CYCLES)
    # the eagerly stacked rows hit the same compiled program: same avals
    assert runner.TRACE_COUNT == traces
    for d, name in enumerate(PAPER_DESIGNS):
        for m in range(len(mixes)):
            assert _described(grid[d][m]) == _described(ref[d][m]), \
                (name, mixes[m])


def test_single_state_stats_equal_per_row_stats():
    cfg = SimConfig(n_apps=2, sim_cycles=CYCLES, design=get_design("mask"))
    st = runner._compiled_run(cfg)(jnp.asarray(app_matrix(["3DS", "BLK"])))
    want = _per_row_stats(cfg, jax.device_get(st))
    assert _described(runner._stats(cfg, st)) == _described(want)
    assert _described(runner.run_mix("mask", ["3DS", "BLK"], CYCLES)) == \
        _described(want)
    (batch,) = runner.run_batch("mask", [("3DS", "BLK")], CYCLES)
    assert _described(batch) == _described(want)


def test_every_answer_is_made_by_one_stats_call(monkeypatch):
    """Each dict `run_grid`, `run_batch`, `run_mix` and `run_trace` return
    is what one `runner._stats` call gave for it, so a wrapper round
    `_stats` sees, and can change, every answer."""
    mixes = MIXES[2]
    calls = [
        lambda: [s for r in runner.run_grid(["mask", "gpu-mmu"], mixes,
                                            cycles=CYCLES) for s in r],
        lambda: runner.run_batch("mask", mixes, CYCLES),
        lambda: [runner.run_mix("mask", ["3DS", "BLK"], CYCLES)],
        lambda: list(runner.run_trace("mask", mixes, seg_cycles=CYCLES)
                     .segments),
    ]
    plain = [c() for c in calls]
    real = runner._stats
    made = []

    def marked(cfg, st, audit=None):
        s = dict(real(cfg, st, audit), made=len(made))
        made.append(s)
        return s

    monkeypatch.setattr(runner, "_stats", marked)
    for call, want in zip(calls, plain):
        del made[:]
        got = call()
        assert [s["made"] for s in got] == list(range(len(want)))
        assert [s is m for s, m in zip(got, made)] == [True] * len(want)
        assert [_described({k: v for k, v in s.items() if k != "made"})
                for s in got] == [_described(s) for s in want]


def test_stats_only_fetch_bytes_per_row(monkeypatch):
    monkeypatch.delenv("REPRO_AUDIT", raising=False)
    mixes = MIXES[4]
    runner.run_grid(["mask"], mixes, cycles=CYCLES)          # compile
    before = runner.FETCHED_BYTES
    runner.run_grid(["mask"], mixes, cycles=CYCLES)
    fetched = runner.FETCHED_BYTES - before
    assert 0 < fetched <= STATS_ROW_BYTES * len(mixes)


def test_audit_fetches_whole_state_and_checks_every_row(monkeypatch):
    calls = []
    real = audit.check_state
    monkeypatch.setattr(audit, "check_state",
                        lambda cfg, st, *a, **k: (calls.append(int(st.t)),
                                                  real(cfg, st, *a, **k)))
    designs, mixes = ["mask", "gpu-mmu"], MIXES[2]
    monkeypatch.setenv("REPRO_AUDIT", "0")
    plain = runner.run_grid(designs, mixes, cycles=CYCLES)
    assert not calls
    monkeypatch.setenv("REPRO_AUDIT", "1")
    before = runner.FETCHED_BYTES
    audited = runner.run_grid(designs, mixes, cycles=CYCLES)
    fetched = runner.FETCHED_BYTES - before
    assert calls == [CYCLES] * (len(designs) * len(mixes))
    ccfg = SimConfig(n_apps=2, sim_cycles=CYCLES,
                     design=canonical_design(static_signature(
                         get_design("mask"))))
    whole = jax.device_get(runner._compiled_grid_run(ccfg)(
        *_eager_rows(designs, mixes)))
    assert fetched == sum(x.nbytes for x in jax.tree_util.tree_leaves(whole))
    assert fetched > STATS_ROW_BYTES * len(designs) * len(mixes)
    assert [[_described(s) for s in r] for r in audited] == \
        [[_described(s) for s in r] for r in plain]


def test_planted_nan_instructions_fail_their_chunk(monkeypatch):
    real = runner._compiled_grid_run

    def poisoned(ccfg):
        fn = real(ccfg)
        if ccfg.design.translation.kind == "ideal":
            return fn

        def run(dp, pm):
            st = fn(dp, pm)
            return st._replace(instr=st.instr.at[1, 0].set(jnp.nan))
        return run

    monkeypatch.setattr(runner, "_compiled_grid_run", poisoned)
    designs, mixes = ["ideal", "mask", "gpu-mmu"], MIXES[2]
    with pytest.raises(runner.NonFiniteStatsError, match="non-finite"):
        runner.run_grid(designs, mixes, cycles=CYCLES)
    out = runner.run_grid(designs, mixes, cycles=CYCLES, fail_soft=True)
    assert all(isinstance(s, dict) for s in out[0])
    recs = {s for row in out[1:] for s in row}
    assert len(recs) == 1
    (rec,) = recs
    assert isinstance(rec, runner.FailureRecord)
    assert rec.error_type == "NonFiniteStatsError"
    assert rec.stage == "grid-chunk" and rec.designs == ("mask", "gpu-mmu")


def _aval(x):
    a = jax.typeof(x)
    return a.dtype, a.shape, a.weak_type


@pytest.mark.parametrize("name", PAPER_DESIGNS)
def test_host_design_params_have_design_params_avals(name):
    host, dev = host_design_params(name), design_params(name)
    assert all(isinstance(x, np.ndarray) for x in host)
    assert [_aval(x) for x in host] == [_aval(x) for x in dev]
    assert [np.asarray(x).tolist() for x in host] == \
        [np.asarray(x).tolist() for x in dev]


def test_host_rows_have_eager_rows_avals():
    mixes = MIXES[2]
    designs = ["mask", "gpu-mmu", "static"]
    pms = np.stack([runner._mix_matrix(m) for m in mixes])
    host = runner._grid_rows([get_design(d) for d in designs], pms)
    eager = _eager_rows(designs, mixes)
    leaves = jax.tree_util.tree_leaves
    assert jax.tree_util.tree_structure(host) == \
        jax.tree_util.tree_structure(eager)
    assert all(isinstance(x, np.ndarray) for x in leaves(host))
    assert [_aval(x) for x in leaves(host)] == \
        [_aval(x) for x in leaves(eager)]
    assert all(np.array_equal(a, b)
               for a, b in zip(leaves(host), leaves(eager)))


def test_app_rows_are_memoised_read_only():
    for name in BENCHES:
        row = app_row(name)
        assert app_row(name) is row and not row.flags.writeable
        assert np.array_equal(row, make_app(name).as_array())
        with pytest.raises(ValueError):
            row[0] = 0
    mat = app_matrix(["3DS", None])
    assert mat.flags.writeable and mat.dtype == np.int32


def test_interleaved_calls_trace_nothing_new():
    mixes = [("3DS", "BLK"), ("MUM", None), ("RED", "BLK"), ("3DS", None)]
    calls = [
        lambda: runner.run_grid(["mask"], mixes, cycles=CYCLES),
        lambda: runner.predict_mixes("mask", [("3DS", "BLK"), ("MUM",)],
                                     cycles=CYCLES, slots=2, pad_rows=4),
        lambda: runner.run_mix("mask", ["3DS", "BLK"], cycles=CYCLES),
    ]
    first = [c() for c in calls]
    traces = runner.TRACE_COUNT
    for c in calls[::-1] + calls:
        c()
    assert runner.TRACE_COUNT == traces
    assert repr(calls[1]()) == repr(first[1])


_SHARDED = r"""
import os

import jax
assert jax.device_count() == 4, jax.device_count()
from repro.sim import audit, runner

calls = []
real = audit.check_state
audit.check_state = lambda cfg, st, *a, **k: (calls.append(1),
                                              real(cfg, st, *a, **k))
designs = ["mask", "gpu-mmu"]
mixes = [("3DS", "BLK"), ("MUM", None), ("RED", "3DS")]
one = runner.run_grid(designs, mixes, cycles=60)
os.environ["REPRO_AUDIT"] = "1"
four = runner.run_grid(designs, mixes, cycles=60, devices=4)
hexed = lambda s: {k: [float(x).hex() for x in __import__("numpy").ravel(v)]
                   for k, v in s.items()}
assert [[hexed(s) for s in r] for r in one] == \
    [[hexed(s) for s in r] for r in four]
assert len(calls) == 6, calls         # 6 real rows, padded to 8
print("SHARDED_HOST_PATH_OK")
"""


def test_sharded_padding_rows_are_dropped_before_stats():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, PYTHONPATH=os.path.join(root, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env.pop("REPRO_AUDIT", None)     # off for the single-device run
    out = subprocess.run([sys.executable, "-c", _SHARDED],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert "SHARDED_HOST_PATH_OK" in out.stdout, \
        (out.stdout[-2000:], out.stderr[-2000:])
