"""Unit tests for the layered memsys pipeline + N-app runner entry points.

Each pipeline stage (warp_sched / translation probe+commit / datapath /
accumulate_stats) is exercised in isolation (the `_translation` /
`_datapath` helpers compose the split stages with an empty partner lane
group); the vmapped L1 TLB bank is checked for exact equivalence against
the previous hand-rolled per-core implementation; and the N-app runner
invariants (run_mix == run_pair bit-for-bit, idle-partner run_mix ==
run_solo) are pinned down.
"""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tlb as tlb_mod
from repro.core import tokens as tok_mod
from repro.core.design import (design_params, get_design,
                               static_partition_index)
from repro.sim import memsys
from repro.sim.config import SimConfig
from repro.sim.golden import GOLDEN, golden_mismatches
from repro.sim.runner import run_mix, run_pair, run_solo
from repro.sim.workloads import (FIELD, IDLE_ROW, N_FIELDS, app_matrix,
                                 mix_workloads, pair_workloads)

SMALL = SimConfig(n_cores=4, warps_per_core=4, n_apps=2, sim_cycles=64,
                  design=get_design("gpu-mmu"))
CYCLES = 1_200


def _sched(cfg, vpn):
    """Hand-built SchedOut: warp 0 of each core, all cores active."""
    C = cfg.n_cores
    app = jnp.asarray(cfg.app_of_core, jnp.int32)
    return memsys.SchedOut(
        picked_warp=jnp.arange(C) * cfg.warps_per_core,
        slot=jnp.zeros(C, jnp.int32),
        active=jnp.ones(C, bool),
        app=app, asid=app,
        vpn=jnp.asarray(vpn, jnp.int32),
        pos=jnp.zeros(C, jnp.int32))


# ------------------------------------------------------------ warp_sched

def test_warp_sched_picks_oldest_ready():
    cfg = SMALL
    pm = jnp.asarray(app_matrix(["3DS", "BLK"]))
    stall = jnp.zeros(16, jnp.int32).at[jnp.arange(4)].set(
        jnp.asarray([9, 2, 8, 8], jnp.int32))     # core 0 waits: 1, 8, 2, 2
    stall = stall.at[jnp.arange(4, 8)].set(100)   # core 1 fully stalled
    pos = jnp.zeros(16, jnp.int32)
    out = memsys.warp_sched(cfg, pm, stall, pos, jnp.int32(10))
    assert int(out.picked_warp[0]) == 1           # oldest ready on core 0
    assert not bool(out.active[1])
    assert bool(out.active[0]) and bool(out.active[2]) and bool(out.active[3])
    # oracle core split: first half of cores -> app 0, second half -> app 1
    assert out.app.tolist() == [0, 0, 1, 1]
    assert out.asid.tolist() == out.app.tolist()


# ----------------------------------------------------------- translation

def _translation(cfg, trans, data, tokens, sched, t):
    """Translation in isolation: probe + walk-only shared memory round
    (empty data-lane group) + commit — the split stages `step` composes."""
    dp = design_params(cfg.design)
    C = cfg.n_cores
    trans, probe = memsys.translation_probe(cfg, dp, trans, tokens, sched, t)
    data, mem = memsys.shared_memory_access(
        cfg, dp, data, sched.app, probe.walk_lines, probe.walk_go,
        probe.walk_tags, jnp.zeros((0,), jnp.int32), jnp.zeros((C,), bool),
        t)
    trans, tout = memsys.translation_commit(cfg, trans, probe, mem, sched, t)
    return trans, data, tout


def _datapath(cfg, data, params_mat, sched, t):
    """Data path in isolation (empty walk-lane group; see `_translation`)."""
    dp = design_params(cfg.design)
    front = memsys.datapath_front(cfg, params_mat, sched, t)
    data, mem = memsys.shared_memory_access(
        cfg, dp, data, sched.app, jnp.zeros((0,), jnp.int32),
        jnp.zeros((0,), bool), jnp.zeros((0,), jnp.int32), front.lines,
        front.go_l2d, t)
    return data, memsys._data_out(cfg, front, mem)


def test_translation_stage_cold_then_hot():
    """A translation-only cycle: cold request walks, refetch hits the L1."""
    cfg = SMALL
    trans, data = memsys.init_trans(cfg), memsys.init_data(cfg)
    tokens = tok_mod.init(cfg.n_apps,
                          jnp.asarray(cfg.warps_per_app, jnp.int32), 0.25)
    sched = _sched(cfg, [7, 7, 9, 9])
    trans, data, out = _translation(cfg, trans, data, tokens, sched,
                                    jnp.int32(1))
    assert not bool(out.l1_hit.any())
    assert bool(out.need_walk.all())
    assert np.all(np.asarray(out.trans_lat) > cfg.lat_l2_tlb)
    # the miss filled the per-core L1 bank: same request now hits locally
    _, _, out2 = _translation(cfg, trans, data, tokens, sched,
                              jnp.int32(2))
    assert bool(out2.l1_hit.all())
    assert not bool(out2.need_walk.any())
    assert np.all(np.asarray(out2.trans_lat) == cfg.lat_l1_tlb)


def test_translation_asid_isolation_in_l1_bank():
    """Same VPN, different app -> no cross-address-space L1/L2 hits."""
    cfg = SMALL
    trans, data = memsys.init_trans(cfg), memsys.init_data(cfg)
    tokens = tok_mod.init(cfg.n_apps,
                          jnp.asarray(cfg.warps_per_app, jnp.int32), 0.25)
    # cores 0/1 (app 0) request VPN 5; cores 2/3 (app 1) request VPN 6
    # (distinct sets: the shared L2 TLB takes one fill per set per cycle)
    sched = _sched(cfg, [5, 5, 6, 6])
    trans, data, _ = _translation(cfg, trans, data, tokens, sched,
                                  jnp.int32(1))
    occ = tlb_mod.occupancy_by_asid(trans.l2tlb, cfg.n_apps)
    assert occ.tolist() == [1, 1]
    # (5, asid 0) is resident, (5, asid 1) must NOT hit across ASIDs
    _, hit = tlb_mod.probe(trans.l2tlb, jnp.asarray([5, 5], jnp.int32),
                           jnp.asarray([0, 1], jnp.int32),
                           jnp.ones(2, bool), jnp.int32(2))
    assert bool(hit[0]) and not bool(hit[1])


# -------------------------------------------------------------- datapath

def test_datapath_stage_miss_latency():
    cfg = SMALL
    pm = app_matrix(["3DS", "BLK"])
    pm[:, FIELD["l1d_hit_milli"]] = 0             # force L1D misses
    data = memsys.init_data(cfg)
    data, out = _datapath(cfg, data, jnp.asarray(pm),
                          _sched(cfg, [7, 8, 9, 10]), jnp.int32(1))
    assert not bool(np.asarray(out.l1d_hit).any())
    assert int(np.asarray(out.go_l2d).sum()) == cfg.n_cores
    assert np.all(np.asarray(out.data_lat)
                  >= cfg.lat_l1_data + cfg.lat_l2_cache)


def test_datapath_stage_hit_latency():
    cfg = SMALL
    pm = app_matrix(["3DS", "BLK"])
    pm[:, FIELD["l1d_hit_milli"]] = 1024          # force L1D hits
    data = memsys.init_data(cfg)
    _, out = _datapath(cfg, data, jnp.asarray(pm),
                       _sched(cfg, [7, 8, 9, 10]), jnp.int32(1))
    assert bool(np.asarray(out.l1d_hit).all())
    assert not bool(np.asarray(out.go_l2d).any())
    assert np.all(np.asarray(out.data_lat) == cfg.lat_l1_data)


# ------------------------------------------------------ accumulate_stats

def test_stats_stage_buckets_by_app():
    C, na = 4, 2
    z = jnp.zeros(C, jnp.int32)
    zb = jnp.zeros(C, bool)
    zf = jnp.zeros(C, jnp.float32)
    sched = memsys.SchedOut(
        picked_warp=jnp.arange(C), slot=z,
        active=jnp.asarray([True, True, True, False]),
        app=jnp.asarray([0, 0, 1, 1]), asid=jnp.asarray([0, 0, 1, 1]),
        vpn=z, pos=z)
    tout = memsys.TransOut(
        trans_lat=z, l1_hit=jnp.asarray([True, False, True, True]),
        l1_miss=jnp.asarray([False, True, False, False]),
        l2_hit=zb, byp_hit=zb, l2_hit_eff=zb,
        need_walk=jnp.asarray([False, True, False, False]),
        merged=zb, new_walk=jnp.asarray([False, True, False, False]),
        walk_done_new=jnp.full((C,), 90, jnp.int32),
        dram_tlb_lat=zf, dram_tlb_n=z,
        l2c_hit=jnp.int32(3), l2c_probe=jnp.int32(4))
    dout = memsys.DataOut(data_lat=z, l1d_hit=zb, go_l2d=zb, dlat=z,
                          l2d_hit=zb)
    st = memsys.accumulate_stats(memsys.init_stats(na), na, sched, tout,
                                 dout, jnp.int32(10))
    assert st.s_l1_hit.tolist() == [1, 1]         # inactive core 3 ignored
    assert st.s_l1_miss.tolist() == [1, 0]
    assert st.s_l2_miss.tolist() == [1, 0]
    assert st.s_walks.tolist() == [1, 0]
    assert st.s_walk_lat.tolist() == [80.0, 0.0]  # walk_done_new - t
    assert int(st.s_l2c_tlb_hit) == 3 and int(st.s_l2c_tlb_probe) == 4


# ----------------------------------------------- vmapped L1 bank vs. old

def _old_probe(tags, asids, lru, vpn, asid, t):
    """The pre-refactor hand-rolled per-core L1 probe (reference)."""
    match = (tags == vpn[:, None]) & (asids == asid[:, None])
    hit = match.any(axis=1)
    way = jnp.argmax(match, axis=1)
    cidx = jnp.arange(tags.shape[0])
    lru = lru.at[cidx, way].set(jnp.where(hit, t, lru[cidx, way]))
    return hit, lru


def _old_fill(tags, asids, lru, vpn, asid, do_fill, t):
    """The pre-refactor hand-rolled per-core L1 fill (reference)."""
    victim = jnp.argmin(lru, axis=1)
    cidx = jnp.arange(tags.shape[0])
    sel = lambda new, old: jnp.where(do_fill, new, old)  # noqa: E731
    tags = tags.at[cidx, victim].set(sel(vpn, tags[cidx, victim]))
    asids = asids.at[cidx, victim].set(sel(asid, asids[cidx, victim]))
    lru = lru.at[cidx, victim].set(sel(t, lru[cidx, victim]))
    return tags, asids, lru


def test_l1_bank_matches_handrolled():
    """probe_bank/fill_bank replicate the old per-core L1 exactly: same
    per-step hits and identical final tags/asids/lru."""
    C, E, T = 3, 8, 200
    rng = np.random.RandomState(0)
    tags = jnp.full((C, E), -1, jnp.int32)
    asids = jnp.full((C, E), -1, jnp.int32)
    lru = jnp.zeros((C, E), jnp.int32)
    bank = tlb_mod.init_bank(C, E, E)
    active = jnp.ones(C, bool)
    for t in range(1, T + 1):
        vpn = jnp.asarray(rng.randint(0, 12, C), jnp.int32)
        asid = jnp.asarray(rng.randint(0, 2, C), jnp.int32)
        hit_old, lru = _old_probe(tags, asids, lru, vpn, asid, t)
        tags, asids, lru = _old_fill(tags, asids, lru, vpn, asid,
                                     active & ~hit_old, t)
        bank, hit_new = tlb_mod.probe_bank(bank, vpn, asid, active, t)
        bank = tlb_mod.fill_bank(bank, vpn, asid, active & ~hit_new, t)
        np.testing.assert_array_equal(np.asarray(hit_old),
                                      np.asarray(hit_new), err_msg=f"t={t}")
    np.testing.assert_array_equal(np.asarray(tags),
                                  np.asarray(bank.tags[:, 0]))
    np.testing.assert_array_equal(np.asarray(asids),
                                  np.asarray(bank.asids[:, 0]))
    np.testing.assert_array_equal(np.asarray(lru),
                                  np.asarray(bank.lru[:, 0]))


# --------------------------------------------------- N-app config/helpers

def test_config_app_partitions():
    cfg = SimConfig(n_apps=4)
    assert sum(cfg.cores_per_app) == cfg.n_cores
    assert sum(cfg.warps_per_app) == cfg.total_warps
    assert sorted(set(cfg.app_of_core)) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        SimConfig(n_apps=0)
    with pytest.raises(ValueError):
        SimConfig(n_apps=31)


def test_static_partition_slices_disjoint():
    idx = jnp.arange(200)
    for na in (2, 3, 4):
        slices = [set(np.asarray(
            static_partition_index(idx, 64, na, jnp.int32(a))).tolist())
            for a in range(na)]
        for i in range(na):
            assert max(slices[i]) <= 63 and min(slices[i]) >= 0
            for j in range(i + 1, na):
                assert not (slices[i] & slices[j])


def test_mix_workloads_seed_stable_and_nary():
    # pinned draw sequence: cached sweeps depend on it
    assert pair_workloads()[:3] == [("BFS2", "CONS"), ("MM", "NW"),
                                    ("RAY", "BLK")]
    mixes = mix_workloads(n_mixes=8, n_apps=3)
    assert len(mixes) == 8
    assert all(len(set(m)) == 3 for m in mixes)
    assert len({frozenset(m) for m in mixes}) == 8


def test_idle_row_matches_n_fields():
    assert IDLE_ROW.shape == (N_FIELDS,)
    assert IDLE_ROW[FIELD["gap"]] == 4000
    assert IDLE_ROW[FIELD["l1d_hit_milli"]] == 1024


# ------------------------------------------------------- runner invariants

def _reference_run(design_name, rows, cycles):
    """Independently-assembled 2-app run: explicit config, explicit params
    matrix, direct compiled-scan call — bypasses run_mix's plumbing so the
    wrapper equivalence tests are not tautologies."""
    from repro.sim import runner
    cfg = SimConfig(n_apps=len(rows), sim_cycles=cycles,
                    design=get_design(design_name))
    pm = jnp.asarray(np.stack(rows))
    return runner._stats(cfg, runner._compiled_run(cfg)(pm))


def test_run_mix_matches_run_pair_bitforbit():
    from repro.sim.workloads import make_app
    p = run_pair("mask", "3DS", "BLK", cycles=CYCLES)
    m = run_mix("mask", ["3DS", "BLK"], cycles=CYCLES)
    ref = _reference_run("mask", [make_app("3DS").as_array(),
                                  make_app("BLK").as_array()], CYCLES)
    for k in p:
        np.testing.assert_array_equal(np.asarray(p[k]), np.asarray(m[k]),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(m[k]), np.asarray(ref[k]),
                                      err_msg=f"ref:{k}")


def test_run_mix_idle_partner_matches_run_solo():
    from repro.sim.workloads import make_app
    s = run_solo("gpu-mmu", "3DS", cycles=CYCLES)
    m = run_mix("gpu-mmu", ["3DS", None], cycles=CYCLES)
    ref = _reference_run("gpu-mmu", [make_app("3DS").as_array(), IDLE_ROW],
                         CYCLES)
    for k in s:
        np.testing.assert_array_equal(np.asarray(s[k]), np.asarray(m[k]),
                                      err_msg=k)
        np.testing.assert_array_equal(np.asarray(m[k]), np.asarray(ref[k]),
                                      err_msg=f"ref:{k}")


def test_run_mix_three_apps_under_jit():
    benches = ["3DS", "HISTO", "BLK"]
    s = run_mix("mask", benches, cycles=CYCLES)
    assert s["ipc"].shape == (3,)
    assert s["l1_hit_rate"].shape == (3,)
    assert s["tokens"].shape == (3,)
    assert np.all(s["ipc"] > 0)
    for k, v in s.items():
        assert np.all(np.isfinite(np.asarray(v, np.float64))), k


# ------------------------------------------------- built-in designs

# The float-hex goldens live in `repro.sim.golden` so that the chip
# smoke (`chip_smoke.py`) checks the very same table on the TPU.


@pytest.mark.parametrize("entry", sorted(GOLDEN))
def test_design_bitforbit_vs_goldens(entry):
    """Every registered design reproduces its pinned float-hex golden
    bit-for-bit (catches unintentional drift anywhere in the pipeline)."""
    bad = golden_mismatches(entry)
    assert not bad, f"{entry} drifted (key: (got, want)): {bad}"


def test_design_shim_legacy_fields_pinned():
    """The paper's eight built-in designs, pinned spec by spec: the
    translation kind, the partition kind, and which of tokens / bypass /
    MASK DRAM scheduling is on."""
    from repro.core.design import PAPER_DESIGNS
    expect = {
        # name: (translation, partition, tokens, bypass, dram)
        "ideal": ("ideal", "shared", False, False, False),
        "pwc": ("pwc", "shared", False, False, False),
        "gpu-mmu": ("shared_l2_tlb", "shared", False, False, False),
        "static": ("shared_l2_tlb", "static", False, False, False),
        "mask": ("shared_l2_tlb", "shared", True, True, True),
        "mask-tlb": ("shared_l2_tlb", "shared", True, False, False),
        "mask-cache": ("shared_l2_tlb", "shared", False, True, False),
        "mask-dram": ("shared_l2_tlb", "shared", False, False, True),
    }
    assert set(PAPER_DESIGNS) == set(expect)
    for name, want in expect.items():
        d = get_design(name)
        assert d.name == name
        assert (d.translation.kind, d.partition.kind, d.tokens.enabled,
                d.bypass.enabled, d.dram.enabled) == want, name
