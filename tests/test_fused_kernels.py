"""Equivalence coverage for the lane-fused memory path (PR 4).

Three layers of evidence that the fusion did not change the model:

  * exact — the direct bank kernels (`probe_bank`/`fill_bank`) replicate
    vmapping the general N-lane probe/fill at N=1 bit-for-bit, and the
    packed stat planes replicate the 17 separate one-hot updates
    bit-for-bit;
  * contract — `access_fused`'s documented cross-wave semantics
    (per-(set, wave) fill ports, duplicate suppression, forwarding, LRU
    victim chains) hold on constructed scenarios, and the round equals
    the plain reference's `line_round` bit for bit at the L2$ and PWC
    sizes;
  * statistical — the fused pipeline tracks the frozen sequential
    reference (`tests/reference_memsys.py`, the exact pre-fusion code)
    across ALL registered designs x n_apps in {1, 2, 3} within tight
    paper-metric tolerances.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import reference_memsys as ref
from perfbench import reference
from repro.core import tlb as tlb_mod
from repro.core.design import PAPER_DESIGNS, get_design
from repro.sim import memsys
from repro.sim import runner
from repro.sim.config import SimConfig
from repro.sim.workloads import app_matrix


# ------------------------------------------------ bank kernels: exact

def _vmapped_probe_bank(state, vpn, asid, active, time):
    """The pre-fusion implementation: vmap the general probe at N=1."""
    fn = jax.vmap(lambda s, v, a, act: tlb_mod.probe(
        s, v[None], a[None], act[None], time))
    state, hit = fn(state, vpn, asid, active)
    return state, hit[:, 0]


def _vmapped_fill_bank(state, vpn, asid, do_fill, time):
    fn = jax.vmap(lambda s, v, a, d: tlb_mod.fill(
        s, v[None], a[None], d[None], time))
    return fn(state, vpn, asid, do_fill)


@pytest.mark.parametrize("entries,ways", [(8, 8), (16, 4)])
def test_bank_kernels_match_vmapped_general(entries, ways):
    """Direct (B, sets, ways) indexing == vmapped general probe/fill,
    bit-for-bit over random traffic (incl. multi-set banks)."""
    B, T = 5, 300
    rng = np.random.RandomState(3)
    direct = tlb_mod.init_bank(B, entries, ways)
    vmapped = tlb_mod.init_bank(B, entries, ways)
    for t in range(1, T + 1):
        vpn = jnp.asarray(rng.randint(0, 40, B), jnp.int32)
        asid = jnp.asarray(rng.randint(0, 3, B), jnp.int32)
        active = jnp.asarray(rng.rand(B) < 0.8)
        direct, hit_d = tlb_mod.probe_bank(direct, vpn, asid, active, t)
        vmapped, hit_v = _vmapped_probe_bank(vmapped, vpn, asid, active, t)
        np.testing.assert_array_equal(np.asarray(hit_d), np.asarray(hit_v),
                                      err_msg=f"probe t={t}")
        fill = active & ~hit_d & jnp.asarray(rng.rand(B) < 0.9)
        direct = tlb_mod.fill_bank(direct, vpn, asid, fill, t)
        vmapped = _vmapped_fill_bank(vmapped, vpn, asid, fill, t)
    for leaf_d, leaf_v in zip(direct, vmapped):
        np.testing.assert_array_equal(np.asarray(leaf_d), np.asarray(leaf_v))


# ------------------------------------------- packed stat planes: exact

def _old_accumulate(stats17, n_apps, sched, tout, dout, t):
    """The pre-fusion 17-array one-hot update (reference arithmetic)."""
    oh = jax.nn.one_hot(sched.app, n_apps, dtype=jnp.int32) \
        * sched.active[:, None]
    ohf = oh.astype(jnp.float32)
    psum = lambda x: (oh * x[:, None]).sum(0)  # noqa: E731
    fsum = lambda x: (ohf * x[:, None]).sum(0)  # noqa: E731
    out = dict(stats17)
    out["s_l1_hit"] = stats17["s_l1_hit"] + psum(tout.l1_hit)
    out["s_l1_miss"] = stats17["s_l1_miss"] + psum(tout.l1_miss)
    out["s_l2_hit"] = stats17["s_l2_hit"] + psum(tout.l2_hit)
    out["s_l2_miss"] = stats17["s_l2_miss"] + psum(tout.need_walk)
    out["s_byp_hit"] = stats17["s_byp_hit"] + psum(tout.byp_hit)
    out["s_byp_probe"] = stats17["s_byp_probe"] \
        + psum(tout.l1_miss & ~tout.l2_hit)
    out["s_walk_lat"] = stats17["s_walk_lat"] \
        + fsum(jnp.where(tout.new_walk, tout.walk_done_new - t, 0))
    out["s_walks"] = stats17["s_walks"] + psum(tout.new_walk)
    out["s_stall_per_miss"] = stats17["s_stall_per_miss"] + fsum(tout.merged)
    out["s_dram_tlb_lat"] = stats17["s_dram_tlb_lat"] + fsum(tout.dram_tlb_lat)
    out["s_dram_tlb_n"] = stats17["s_dram_tlb_n"] + psum(tout.dram_tlb_n)
    out["s_dram_data_lat"] = stats17["s_dram_data_lat"] \
        + fsum(jnp.where(dout.go_l2d, dout.dlat, 0))
    out["s_dram_data_n"] = stats17["s_dram_data_n"] + psum(dout.go_l2d)
    out["s_l2c_tlb_hit"] = stats17["s_l2c_tlb_hit"] + tout.l2c_hit
    out["s_l2c_tlb_probe"] = stats17["s_l2c_tlb_probe"] + tout.l2c_probe
    out["s_l2c_data_hit"] = stats17["s_l2c_data_hit"] \
        + (dout.go_l2d & dout.l2d_hit).sum(dtype=jnp.int32)
    out["s_l2c_data_probe"] = stats17["s_l2c_data_probe"] \
        + dout.go_l2d.sum(dtype=jnp.int32)
    return out


def test_packed_stats_match_per_array_updates():
    """accumulate_stats on the packed planes == the 17 one-hot updates,
    bit-for-bit over random per-cycle outcomes (ints and floats)."""
    C, na, T = 6, 3, 60
    rng = np.random.RandomState(7)
    packed = memsys.init_stats(na)
    seventeen = {
        name: jnp.zeros((na,), jnp.float32) if name in (
            "s_walk_lat", "s_stall_per_miss", "s_dram_tlb_lat",
            "s_dram_data_lat") else
        jnp.zeros((), jnp.int32) if name.startswith("s_l2c_") else
        jnp.zeros((na,), jnp.int32)
        for name in ("s_l1_hit", "s_l1_miss", "s_l2_hit", "s_l2_miss",
                     "s_byp_hit", "s_byp_probe", "s_walk_lat", "s_walks",
                     "s_stall_per_miss", "s_dram_tlb_lat", "s_dram_tlb_n",
                     "s_dram_data_lat", "s_dram_data_n", "s_l2c_tlb_hit",
                     "s_l2c_tlb_probe", "s_l2c_data_hit", "s_l2c_data_probe")}
    for t in range(1, T + 1):
        b = lambda p: jnp.asarray(rng.rand(C) < p)  # noqa: E731
        z = lambda hi: jnp.asarray(rng.randint(0, hi, C), jnp.int32)  # noqa: E731
        l1_hit, l2_hit, byp_hit = b(.4), b(.3), b(.2)
        l1_miss = ~l1_hit & b(.9)
        need_walk = l1_miss & ~l2_hit
        new_walk = need_walk & b(.7)
        sched = memsys.SchedOut(
            picked_warp=jnp.arange(C), slot=jnp.zeros(C, jnp.int32),
            active=b(.8), app=z(na), asid=z(na),
            vpn=z(100), pos=jnp.zeros(C, jnp.int32))
        tout = memsys.TransOut(
            trans_lat=z(50), l1_hit=l1_hit, l1_miss=l1_miss, l2_hit=l2_hit,
            byp_hit=byp_hit, l2_hit_eff=l2_hit | byp_hit,
            need_walk=need_walk, merged=need_walk & ~new_walk,
            new_walk=new_walk, walk_done_new=t + z(300),
            dram_tlb_lat=z(400).astype(jnp.float32), dram_tlb_n=z(4),
            l2c_hit=z(3)[0], l2c_probe=z(3)[0] + 2)
        dout = memsys.DataOut(data_lat=z(60), l1d_hit=b(.5), go_l2d=b(.5),
                              dlat=z(500), l2d_hit=b(.5))
        packed = memsys.accumulate_stats(packed, na, sched, tout, dout,
                                         jnp.int32(t))
        seventeen = _old_accumulate(seventeen, na, sched, tout, dout,
                                    jnp.int32(t))
    for name, want in seventeen.items():
        np.testing.assert_array_equal(
            np.asarray(getattr(packed, name)), np.asarray(want),
            err_msg=name)


# ------------------------------------------- access_fused: contract

def _mini_cache(sets=4, ways=2):
    return tlb_mod.init(sets * ways, ways)


def test_access_fused_forwarding():
    """Lanes whose line is filled this cycle observe the fill (hit, no
    second fill) — across waves via duplicate suppression, and within a
    wave via the port (MSHR-merge-like resolution against final state)."""
    st = _mini_cache()
    # lanes: wave0 = [line 8, line 8], wave1 = [line 8, line 12]
    vpn = jnp.asarray([8, 8, 8, 12], jnp.int32)
    on = jnp.ones(4, bool)
    st, hit, filled = tlb_mod.access_fused(st, vpn, on, on, 1, n_waves=2)
    assert hit.tolist() == [False, True, True, False]
    assert filled.tolist() == [True, False, False, True]


def test_access_fused_per_set_per_wave_port():
    """Two same-set misses in one wave: first fills, second does not;
    the same set can still fill again in the NEXT wave."""
    st = _mini_cache(sets=4, ways=2)
    # set = vpn % 4: lanes 0,1 both set 1 in wave 0; lane 2 set 1 in wave 1
    vpn = jnp.asarray([5, 9, 13, 2], jnp.int32)
    on = jnp.ones(4, bool)
    st, hit, filled = tlb_mod.access_fused(st, vpn, on, on, 1, n_waves=2)
    assert filled.tolist() == [True, False, True, True]
    assert not bool(hit[1])              # port loss -> miss, no forward
    # both same-set winners landed in DISTINCT ways (LRU victim chain)
    occ = int((st.tags[1] >= 0).sum())
    assert occ == 2 and sorted(np.asarray(st.tags[1]).tolist()) == [5, 13]


def test_access_fused_duplicate_suppression_same_position():
    """The same flat position (core) re-touching one line in a later wave
    forwards instead of filling twice."""
    st = _mini_cache()
    # one core (C=1), 3 waves, same line every wave
    vpn = jnp.asarray([6, 6, 6], jnp.int32)
    on = jnp.ones(3, bool)
    st, hit, filled = tlb_mod.access_fused(st, vpn, on, on, 1, n_waves=3)
    assert filled.tolist() == [True, False, False]
    assert hit.tolist() == [False, True, True]
    assert int((st.tags >= 0).sum()) == 1    # exactly one entry installed


def test_access_fused_respects_may_fill_and_active():
    st = _mini_cache()
    vpn = jnp.asarray([3, 7, 11], jnp.int32)
    active = jnp.asarray([True, True, False])
    may_fill = jnp.asarray([False, True, True])
    st, hit, filled = tlb_mod.access_fused(st, vpn, active, may_fill, 1,
                                           n_waves=3)
    assert filled.tolist() == [False, True, False]
    assert hit.tolist() == [False, False, False]
    # bypassed lane went to DRAM without installing anything in its set
    assert int((st.tags >= 0).sum()) == 1


def test_access_fused_matches_probe_on_resident_lines():
    """With everything resident and a single wave, access_fused == probe
    (same hits, same LRU touches)."""
    st = _mini_cache(sets=8, ways=4)
    vpn = jnp.asarray([3, 11, 19, 27], jnp.int32)
    z = jnp.zeros(4, jnp.int32)
    on = jnp.ones(4, bool)
    for i in range(4):
        st = tlb_mod.fill(st, vpn[i:i + 1], z[:1], on[:1], i + 1)
    via_probe, hit_p = tlb_mod.probe(st, vpn, z, on, 9)
    via_fused, hit_f, filled = tlb_mod.access_fused(st, vpn, on, on, 9)
    assert bool(hit_p.all()) and bool(hit_f.all()) and not bool(filled.any())
    for leaf_p, leaf_f in zip(via_probe, via_fused):
        np.testing.assert_array_equal(np.asarray(leaf_p), np.asarray(leaf_f))


def _resident_cache(rng, n_sets, n_ways):
    """A cache as the round leaves it: each set holds distinct lines of
    that set, some ways empty (-1), with arbitrary last-use times."""
    lines = np.stack([rng.permutation(2 * n_ways)[:n_ways] * n_sets + s
                      for s in range(n_sets)])
    tags = np.where(rng.rand(n_sets, n_ways) < 0.2, -1, lines)
    lru = rng.randint(0, 100, (n_sets, n_ways))
    return jnp.asarray(tags, jnp.int32), jnp.asarray(lru, jnp.int32)


@pytest.mark.parametrize("sets,ways,N,W", [
    (1, 64, 30, 1), (32, 16, 30, 3), (64, 8, 64, 4), (4, 2, 24, 6),
    (1024, 16, 240, 8),      # Table 1 L2$: 30 cores x (4 walk + 4 data)
    (64, 16, 120, 4),        # Table 1 PWC: 30 cores x 4 walk levels
])
def test_access_fused_equals_reference_line_round(sets, ways, N, W):
    """`access_fused` == the plain reference's `line_round`: tags, LRU
    and hits, bit for bit, over lanes that crowd a few sets."""
    for seed in range(3):
        rng = np.random.RandomState(seed * 1000 + sets + W)
        tags, lru = _resident_cache(rng, sets, ways)
        line = jnp.asarray(rng.randint(0, min(sets, 8), N)
                           + sets * rng.randint(0, 2 * ways, N), jnp.int32)
        active = jnp.asarray(rng.rand(N) > 0.25)
        may_fill = jnp.asarray(rng.rand(N) > 0.2)
        st = tlb_mod.TLBState(tags=tags, asids=jnp.full_like(tags, -1),
                              lru=lru, hits=jnp.zeros((), jnp.int32),
                              misses=jnp.zeros((), jnp.int32))
        st, hit, _ = tlb_mod.access_fused(st, line, active, may_fill, 777,
                                          n_waves=W)
        want, want_hit = reference.line_round(
            dict(tag=tags, lru=lru), line, active, may_fill, 777, W)
        for got, exp, what in ((st.tags, want["tag"], "tags"),
                               (st.lru, want["lru"], "lru"),
                               (hit, want_hit, "hit")):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(exp),
                                          err_msg=f"{what} seed={seed}")


def _lane_loop_round(tags, lru, line, active, may_fill, t, W):
    """The round's fill port as a plain per-lane loop: per (set, wave) the
    lowest candidate lane fills; the k-th filling wave of a set takes the
    k-th way of the start-of-cycle (lru, way) order; a set takes at most
    n_ways fills. Writes land in lane order. Returns (tags, lru, hit,
    filled, cut), `cut` counting port winners lost to the n_ways cap."""
    tags0, lru0 = np.asarray(tags), np.asarray(lru)
    tags, lru = tags0.copy(), lru0.copy()
    n_sets, n_ways = tags.shape
    N = len(line)
    C = N // W
    line = [int(x) for x in line]
    sets = [x % n_sets for x in line]
    pre_hit, way = [], []
    for i in range(N):
        ways_hit = [k for k in range(n_ways) if tags0[sets[i], k] == line[i]]
        pre_hit.append(bool(active[i]) and bool(ways_hit))
        way.append(ways_hit[0] if ways_hit else 0)
    cand = [bool(active[i]) and not pre_hit[i] and bool(may_fill[i])
            for i in range(N)]
    # the same position (core) in an earlier wave already fills this line
    cand = [cand[i] and not any(cand[v * C + i % C]
                                and line[v * C + i % C] == line[i]
                                for v in range(i // C))
            for i in range(N)]
    port = {}
    for i in range(N):
        if cand[i]:
            port.setdefault((sets[i], i // C), i)
    filled, writes, cut = [False] * N, [], 0
    for i in range(N):
        if pre_hit[i]:
            writes.append((i, way[i]))
        elif port.get((sets[i], i // C)) == i:
            k = sum((sets[i], v) in port for v in range(i // C))
            if k < n_ways:
                order = sorted(range(n_ways),
                               key=lambda w: (lru0[sets[i], w], w))
                filled[i] = True
                writes.append((i, order[k]))
            else:
                cut += 1
    for i, w in writes:
        tags[sets[i], w] = line[i]
        lru[sets[i], w] = t
    hit = [pre_hit[i] or (bool(active[i]) and not filled[i]
                          and line[i] in tags[sets[i]].tolist())
           for i in range(N)]
    return tags, lru, np.asarray(hit, bool), np.asarray(filled, bool), cut


def _port_case(rng, sets, ways, N, draw):
    tags, lru = _resident_cache(rng, sets, ways)
    crowd = 1 if draw == "one_set" else min(sets, 8)
    line = (rng.randint(0, crowd, N) + sets * rng.randint(0, 2 * ways, N))
    active = rng.rand(N) > 0.25 if draw != "inactive" else np.zeros(N, bool)
    may_fill = rng.rand(N) > 0.2
    return (tags, lru, jnp.asarray(line, jnp.int32), jnp.asarray(active),
            jnp.asarray(may_fill))


@pytest.mark.parametrize("sets,ways,N,W,draw,rows", [
    (32, 4, 30, 1, "crowded", None),
    (16, 4, 32, 4, "crowded", None),
    (16, 4, 32, 8, "crowded", None),
    (1024, 16, 240, 8, "crowded", None),     # Table 1 L2$
    (64, 16, 120, 4, "crowded", None),       # Table 1 PWC
    (16, 4, 32, 4, "inactive", None),
    (16, 4, 32, 8, "one_set", None),
    (4, 2, 48, 16, "one_set", None),         # W > ways: the overflow cut
    (16, 4, 0, 4, "crowded", None),          # N = 0
    (1024, 16, 240, 8, "crowded", 3),        # vmapped over rows, as a grid
])
def test_access_fused_fill_port_matches_lane_loop(sets, ways, N, W, draw,
                                                  rows):
    """`access_fused`'s fill port == a plain per-lane loop: filled, hit,
    tags, LRU and the hit/miss counters, bit for bit."""
    rng = np.random.RandomState(sets + N + W)
    cases = [_port_case(rng, sets, ways, N, draw) for _ in range(rows or 1)]
    stack = [jnp.stack(x) for x in zip(*cases)]
    tags, lru, line, active, may_fill = stack
    st = tlb_mod.TLBState(tags=tags, asids=jnp.full_like(tags, -1), lru=lru,
                          hits=jnp.zeros(len(cases), jnp.int32),
                          misses=jnp.zeros(len(cases), jnp.int32))

    def round_(s, ln, a, m):
        return tlb_mod.access_fused(s, ln, a, m, 777, n_waves=W)

    if rows:
        got_st, got_hit, got_filled = jax.jit(jax.vmap(round_))(
            st, line, active, may_fill)
    else:
        one = jax.tree_util.tree_map(lambda x: x[0], (st, line, active,
                                                      may_fill))
        got = jax.jit(round_)(*one)
        got_st, got_hit, got_filled = jax.tree_util.tree_map(
            lambda x: x[None], got)
    for r, (tg, lr, ln, a, m) in enumerate(cases):
        w_tags, w_lru, w_hit, w_filled, cut = _lane_loop_round(
            tg, lr, ln, a, m, 777, W)
        for got_x, exp, what in (
                (got_st.tags[r], w_tags, "tags"), (got_st.lru[r], w_lru, "lru"),
                (got_hit[r], w_hit, "hit"), (got_filled[r], w_filled, "filled"),
                (got_st.hits[r], w_hit.sum(), "hits"),
                (got_st.misses[r], (np.asarray(a) & ~w_hit).sum(), "misses")):
            np.testing.assert_array_equal(np.asarray(got_x), np.asarray(exp),
                                          err_msg=f"{what} row={r}")
        if W > ways and draw == "one_set":
            assert cut > 0          # the case reaches the n_ways cap
        if draw != "inactive" and N:
            assert w_filled.any()   # the case exercises the port


@pytest.mark.parametrize("sets,ways,N,W", [
    (1024, 16, 240, 8),      # Table 1 L2$
    (64, 16, 120, 4),        # Table 1 PWC
])
def test_access_fused_port_lowers_without_scratch_scatter(sets, ways, N, W):
    """Vmapped over grid rows, the round lowers with exactly two scatters,
    the tag and LRU planes, and none with a `minimum` combiner: the fill
    port is dense compares, not a (set, wave) table, since XLA:TPU applies
    a scatter's indices one after another."""
    rows = 3
    st = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct((rows,) + x.shape, x.dtype),
        tlb_mod.init(sets * ways, ways))
    lanes = jax.ShapeDtypeStruct((rows, N), jnp.int32)
    flags = jax.ShapeDtypeStruct((rows, N), jnp.bool_)
    text = jax.jit(jax.vmap(lambda s, ln, a, m: tlb_mod.access_fused(
        s, ln, a, m, 777, n_waves=W))).lower(st, lanes, flags,
                                             flags).as_text()
    assert "stablehlo.reduce" in text       # the text is the program's
    scatters = text.split('"stablehlo.scatter"')[1:]
    assert len(scatters) == 2
    for op in scatters:
        assert "minimum" not in op[:op.index("})")]


# ---------------------------- fused pipeline vs sequential reference

BENCHES3 = ["3DS", "BLK", "MUM"]
# Tolerances sized from a measured grid sweep at this exact config: the
# worst absolute hit-rate delta was 0.022 and the worst relative
# latency/ipc delta 23% (pwc, n=1). At that scale the two models diverge
# CHAOTICALLY, not systematically — a slightly different walk latency
# reorders the schedule and the address streams decorrelate — while
# full-size (30-core) runs agree within ~5% on every metric. A real
# regression (dropped stat, broken port logic, wrong lane split) blows
# far past these bounds.
TOL = {
    "ipc": ("rel", 0.30),
    "l1_hit_rate": ("abs", 0.08),
    "l2_hit_rate": ("abs", 0.08),
    "l2c_tlb_hit_rate": ("abs", 0.08),
    "l2c_data_hit_rate": ("abs", 0.08),
    "walk_lat": ("rel", 0.35),
    "dram_tlb_lat": ("rel", 0.25),
    "dram_data_lat": ("rel", 0.20),
}


@pytest.mark.parametrize("name", PAPER_DESIGNS)
@pytest.mark.parametrize("n_apps", [1, 2, 3])
def test_fused_pipeline_tracks_sequential_reference(name, n_apps):
    """The fused one-round-per-cycle pipeline reproduces the sequential
    8-round reference within paper-metric tolerances, for every
    registered design and 1-3 concurrent apps (epochs crossed four
    times, so the adaptive token/bypass/DRAM paths are exercised)."""
    design = get_design(name).with_(epoch_cycles=400)
    cfg = SimConfig(n_cores=9, warps_per_core=8, n_apps=n_apps,
                    sim_cycles=1800, design=design)
    pm = jnp.asarray(app_matrix(BENCHES3[:n_apps]))
    new = runner._stats(cfg, runner._compiled_run(cfg)(pm))
    old = ref.metrics(cfg, ref.run_ref(cfg, pm))

    for key, (kind, tol) in TOL.items():
        nv = np.asarray(new[key], np.float64)
        ov = np.asarray(old[key], np.float64)
        assert np.all(np.isfinite(nv)), key
        if kind == "abs":
            err = np.max(np.abs(nv - ov))
        else:
            err = np.max(np.abs(nv - ov) / np.maximum(np.abs(ov), 1e-9))
        assert err <= tol, (f"{name} n_apps={n_apps} {key}: "
                            f"fused={nv} reference={ov} err={err:.3f}")
    # identical workload structure: the reference and the fused pipeline
    # must schedule the same instruction stream (exact, not statistical)
    np.testing.assert_array_equal(new["walks"] > 0, old["walks"] > 0)
