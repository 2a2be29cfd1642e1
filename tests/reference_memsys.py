"""SEQUENTIAL REFERENCE pipeline for the lane-fused memory path.

This is a frozen copy of `repro.sim.memsys` as it stood before the
lane-fused rewrite: per cycle it issues 8 back-to-back L2$/DRAM
round-trips (4 page-walk levels + 4 divergent data lines), each a full
probe + fill + DRAM-schedule sequence observing the fills of the rounds
before it, and it carries 17 separate per-app stat arrays.

It exists so `tests/test_fused_kernels.py` can quantify the fused
pipeline against the exact pre-fusion semantics across every registered
design — do not "fix" or modernize it; its value is being the old code.
The only additions are `run_ref` / `metrics` at the bottom.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from repro.core import bypass as bp_mod
from repro.core import dram_sched
from repro.core import page_table as pt_mod
from repro.core import tlb as tlb_mod
from repro.core import tokens as tok_mod
from repro.core.design import static_partition_index
from repro.core.page_table import _mix
from repro.sim.config import SimConfig
from repro.sim.workloads import FIELD, gen_vpn

DATA_WIDTH = 4           # divergent cache lines per memory instruction
BIG = jnp.int32(1 << 30)
# the concurrent-page-walk table size (Table 1: 64) comes from
# cfg.design.translation.max_concurrent_walks


# ---------------------------------------------------------------------------
# layered state
# ---------------------------------------------------------------------------

class TransState(NamedTuple):
    """Translation layer: TLB hierarchy + in-flight page-walk table."""
    l1: tlb_mod.TLBState         # per-core bank, leading axis (n_cores,)
    l2tlb: tlb_mod.TLBState
    bypass_tlb: tlb_mod.TLBState
    pwc: tlb_mod.TLBState        # page-walk cache (PTE lines)
    walk_vpn: jax.Array          # (max_concurrent_walks,) int32
    walk_asid: jax.Array         # (max_concurrent_walks,) int32
    walk_done: jax.Array         # (max_concurrent_walks,) completion time
    walk_merged: jax.Array       # (max_concurrent_walks,) warps merged on


class DataState(NamedTuple):
    """Shared data path: L2 data cache, DRAM, bypass accounting."""
    l2c: tlb_mod.TLBState        # line-addressed, reuses TLB machinery
    dram: dram_sched.DramState
    bypass: bp_mod.BypassState


class StatState(NamedTuple):
    """Per-app cumulative counters (all (n_apps,) unless noted)."""
    s_l1_hit: jax.Array
    s_l1_miss: jax.Array
    s_l2_hit: jax.Array
    s_l2_miss: jax.Array
    s_byp_hit: jax.Array         # bypass-cache hits
    s_byp_probe: jax.Array       # bypass-cache probes
    s_walk_lat: jax.Array        # float32 summed walk latency
    s_walks: jax.Array
    s_stall_per_miss: jax.Array  # accumulated merged-warp counts
    s_dram_tlb_lat: jax.Array    # float32
    s_dram_tlb_n: jax.Array
    s_dram_data_lat: jax.Array
    s_dram_data_n: jax.Array
    s_l2c_tlb_hit: jax.Array     # () cumulative L2$ hits for walk requests
    s_l2c_tlb_probe: jax.Array
    s_l2c_data_hit: jax.Array
    s_l2c_data_probe: jax.Array


class SimState(NamedTuple):
    t: jax.Array                 # () int32
    stall_until: jax.Array       # (W,) int32
    instr: jax.Array             # (W,) float32 retired instructions
    pos: jax.Array               # (W,) int32 stream position
    trans: TransState
    data: DataState
    tokens: tok_mod.TokenState
    stats: StatState


def init_trans(cfg: SimConfig) -> TransState:
    tr = cfg.design.translation
    tok = cfg.design.tokens
    wt = tr.max_concurrent_walks
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    return TransState(
        l1=tlb_mod.init_bank(cfg.n_cores, tr.l1_entries, tr.l1_entries),
        l2tlb=tlb_mod.init(tr.l2_entries, tr.l2_ways),
        bypass_tlb=tlb_mod.init(tok.bypass_cache_entries,
                                tok.bypass_cache_entries),
        pwc=tlb_mod.init(cfg.pwc_entries, cfg.pwc_ways),
        walk_vpn=jnp.full((wt,), -1, jnp.int32),
        walk_asid=jnp.full((wt,), -1, jnp.int32),
        walk_done=z(wt),
        walk_merged=z(wt),
    )


def init_data(cfg: SimConfig) -> DataState:
    return DataState(
        l2c=tlb_mod.init(cfg.l2_sets * cfg.l2_ways, cfg.l2_ways),
        dram=dram_sched.init(cfg.n_channels, cfg.n_banks, cfg.n_apps),
        bypass=bp_mod.init(),
    )


def init_stats(n_apps: int) -> StatState:
    z = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    zf = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731
    na = n_apps
    return StatState(
        s_l1_hit=z(na), s_l1_miss=z(na), s_l2_hit=z(na), s_l2_miss=z(na),
        s_byp_hit=z(na), s_byp_probe=z(na),
        s_walk_lat=zf(na), s_walks=z(na), s_stall_per_miss=zf(na),
        s_dram_tlb_lat=zf(na), s_dram_tlb_n=z(na),
        s_dram_data_lat=zf(na), s_dram_data_n=z(na),
        s_l2c_tlb_hit=z(), s_l2c_tlb_probe=z(),
        s_l2c_data_hit=z(), s_l2c_data_probe=z(),
    )


def init_state(cfg: SimConfig) -> SimState:
    W = cfg.total_warps
    return SimState(
        t=jnp.zeros((), jnp.int32),
        stall_until=jnp.zeros((W,), jnp.int32),
        instr=jnp.zeros((W,), jnp.float32),
        pos=jnp.zeros((W,), jnp.int32),
        trans=init_trans(cfg),
        data=init_data(cfg),
        tokens=tok_mod.init(cfg.n_apps,
                            jnp.asarray(cfg.warps_per_app, jnp.int32),
                            cfg.design.tokens.initial_frac),
        stats=init_stats(cfg.n_apps),
    )


# ---------------------------------------------------------------------------
# stage 1: warp scheduling
# ---------------------------------------------------------------------------

class SchedOut(NamedTuple):
    """One candidate memory instruction per core, all arrays (n_cores,)."""
    picked_warp: jax.Array       # global warp id
    slot: jax.Array              # warp slot within its core
    active: jax.Array            # bool: core found a ready warp
    app: jax.Array
    asid: jax.Array
    vpn: jax.Array
    pos: jax.Array               # stream position of the picked warp


def warp_sched(cfg: SimConfig, params_mat, stall_until, pos, t) -> SchedOut:
    """GTO-like pick: per core, the ready warp that has waited longest."""
    C, wpc = cfg.n_cores, cfg.warps_per_core
    ready = stall_until <= t
    waiting = jnp.where(ready, t - stall_until, -1)
    wait_grid = waiting.reshape(C, wpc)
    pick = jnp.argmax(wait_grid, axis=1)                  # (C,)
    picked_warp = jnp.arange(C) * wpc + pick
    active = wait_grid[jnp.arange(C), pick] >= 0          # (C,)

    app = jnp.asarray(cfg.app_of_core, jnp.int32)         # oracle split (§6)
    p = pos[picked_warp]
    vpn = gen_vpn(params_mat[app], app, picked_warp, p, t)
    # one address space per application
    return SchedOut(picked_warp=picked_warp, slot=pick, active=active,
                    app=app, asid=app, vpn=vpn, pos=p)


# ---------------------------------------------------------------------------
# shared L2 data cache + DRAM (used by both translation and datapath)
# ---------------------------------------------------------------------------

def _l2_cache_access(cfg: SimConfig, l2c, dram, line, app, is_tlb,
                     may_fill, active, t, static_split):
    """Shared L2 data cache + DRAM for a batch of line addresses.

    Returns (l2c', dram', latency, l2_hit). `may_fill` implements the MASK
    L2 bypass decision; `static_split` gives each app an equal slice of the
    sets/channels by restricting its index range (Static design)."""
    dr = cfg.design.dram
    key = jnp.where(static_split,
                    static_partition_index(line, cfg.l2_sets, cfg.n_apps,
                                           app),
                    line % cfg.l2_sets)
    # reuse TLB machinery: tag = full line id, "asid" field = 0
    zero = jnp.zeros_like(line)
    l2c, hit = tlb_mod.probe(l2c, line * cfg.l2_sets + key, zero, active, t)
    lat = jnp.where(hit, cfg.lat_l2_cache, 0)
    miss = active & ~hit

    channel = (line % cfg.n_channels).astype(jnp.int32)
    channel = jnp.where(static_split,
                        static_partition_index(line, cfg.n_channels,
                                               cfg.n_apps, app), channel)
    bank = ((line // cfg.n_channels) % cfg.n_banks).astype(jnp.int32)
    row = (line // (cfg.n_channels * cfg.n_banks * 32)).astype(jnp.int32)
    dram, dlat = dram_sched.access(
        dram, channel, bank, row, app, is_tlb, miss,
        mask_enabled=dr.enabled, thres_max=dr.thres_max)
    lat = lat + jnp.where(miss, cfg.lat_l2_cache + dlat, 0)
    l2c = tlb_mod.fill(l2c, line * cfg.l2_sets + key, zero,
                       miss & may_fill, t)
    return l2c, dram, lat, hit


# ---------------------------------------------------------------------------
# stage 2: translation (L1 TLB bank -> L2 TLB/bypass -> page walk)
# ---------------------------------------------------------------------------

class TransOut(NamedTuple):
    """Per-core translation results + walk-level L2$ counters."""
    trans_lat: jax.Array         # (C,) translation latency
    l1_hit: jax.Array            # (C,) bool
    l1_miss: jax.Array
    l2_hit: jax.Array
    byp_hit: jax.Array
    l2_hit_eff: jax.Array        # L2 or bypass-cache hit
    need_walk: jax.Array
    merged: jax.Array            # joined an in-flight walk
    new_walk: jax.Array          # started a fresh walk
    walk_done_new: jax.Array     # (C,) completion time of fresh walks
    dram_tlb_lat: jax.Array      # (C,) float32 DRAM latency on walk path
    dram_tlb_n: jax.Array        # (C,) int32
    l2c_hit: jax.Array           # () walk-request hits in the L2$
    l2c_probe: jax.Array         # () walk-request probes of the L2$


def translation(cfg: SimConfig, trans: TransState, data: DataState,
                tokens: tok_mod.TokenState, sched: SchedOut, t
                ) -> Tuple[TransState, DataState, TransOut]:
    """Translate one request per core through the full TLB hierarchy.

    Dispatch is by the translation/tokens/bypass policy specs: the
    spec fields are static Python values, so each design compiles to a
    specialized pipeline with the unused paths traced out."""
    des = cfg.design
    tr = des.translation
    ideal = tr.kind == "ideal"
    use_pwc = tr.kind == "pwc"
    use_l2tlb = tr.kind == "shared_l2_tlb"
    tokens_on = des.tokens.enabled
    C = cfg.n_cores
    vpn, asid, active = sched.vpn, sched.asid, sched.active

    # ---------------- L1 TLB bank --------------------------------------
    l1, l1_hit = tlb_mod.probe_bank(trans.l1, vpn, asid, active, t)
    if ideal:
        l1_hit = active
    l1_miss = active & ~l1_hit

    # ---------------- shared L2 TLB + bypass cache ---------------------
    l2tlb, byp_tlb = trans.l2tlb, trans.bypass_tlb
    if use_l2tlb:
        l2tlb, l2_hit = tlb_mod.probe(l2tlb, vpn, asid, l1_miss, t)
        if tokens_on:
            byp_tlb, byp_hit = tlb_mod.probe(byp_tlb, vpn, asid,
                                             l1_miss & ~l2_hit, t)
            l2_hit_eff = l2_hit | byp_hit
        else:
            byp_hit = jnp.zeros_like(l2_hit)
            l2_hit_eff = l2_hit
    else:
        l2_hit = jnp.zeros_like(l1_miss)
        byp_hit = jnp.zeros_like(l1_miss)
        l2_hit_eff = l2_hit

    need_walk = l1_miss & ~l2_hit_eff

    # ---------------- page walk (4 dependent PTE accesses) -------------
    # MSHR merge: outstanding walk for same (vpn, asid)?
    wmatch = (trans.walk_vpn[None, :] == vpn[:, None]) & \
             (trans.walk_asid[None, :] == asid[:, None]) & \
             (trans.walk_done[None, :] > t)
    merged = wmatch.any(axis=1) & need_walk
    merge_done = jnp.where(
        merged, jnp.max(jnp.where(wmatch, trans.walk_done[None, :], 0),
                        axis=1), 0)

    new_walk = need_walk & ~merged
    n_live = (trans.walk_done > t).sum()
    # walker occupancy queue penalty (finite walker threads)
    wt = tr.max_concurrent_walks
    over = jnp.maximum(n_live + jnp.cumsum(new_walk) - wt, 0)
    queue_pen = over * 30

    pte_lines = pt_mod.pte_line_addresses(
        pt_mod.PageTableConfig(levels=tr.walk_levels), asid, vpn)  # (C, L)

    walk_lat = jnp.zeros((C,), jnp.int32)
    dram_tlb_lat = jnp.zeros((C,), jnp.float32)
    dram_tlb_n = jnp.zeros((C,), jnp.int32)
    l2c, dram, bp_state = data.l2c, data.dram, data.bypass
    pwc = trans.pwc
    static = jnp.asarray(des.partition.kind == "static")
    l2c_hit = l2c_probe = jnp.zeros((), jnp.int32)
    for lvl in range(tr.walk_levels):
        line = pte_lines[:, lvl]
        lvl_active = new_walk
        depth_tag = jnp.full((C,), pt_mod.walk_depth_tag(lvl), jnp.int32)
        if use_pwc:
            pwc, pwc_hit = tlb_mod.probe(pwc, line, asid * 0, lvl_active, t)
            pwc = tlb_mod.fill(pwc, line, asid * 0, lvl_active & ~pwc_hit, t)
            go_l2 = lvl_active & ~pwc_hit
            walk_lat = walk_lat + jnp.where(lvl_active & pwc_hit, 5, 0)
        else:
            go_l2 = lvl_active
        if des.bypass.enabled:
            may_fill = bp_mod.should_fill(bp_state, depth_tag)
        else:
            may_fill = jnp.ones((C,), bool)
        l2c, dram, lat, l2hit = _l2_cache_access(
            cfg, l2c, dram, line, sched.app, jnp.ones((C,), bool),
            may_fill, go_l2, t, static)
        bp_state = bp_mod.record(bp_state, depth_tag, l2hit, go_l2)
        walk_lat = walk_lat + jnp.where(go_l2, lat, 0)
        went_dram = go_l2 & ~l2hit
        dram_tlb_lat = dram_tlb_lat + jnp.where(went_dram, lat, 0)
        dram_tlb_n = dram_tlb_n + went_dram.astype(jnp.int32)
        l2c_hit = l2c_hit + (go_l2 & l2hit).sum(dtype=jnp.int32)
        l2c_probe = l2c_probe + go_l2.sum(dtype=jnp.int32)

    walk_lat = walk_lat + queue_pen
    walk_done_new = t + cfg.lat_l2_tlb + walk_lat

    # install new walks into free slots (expired entries are free)
    free = trans.walk_done <= t
    order_slots = jnp.cumsum(new_walk) - 1
    free_idx = jnp.where(free, jnp.arange(wt), BIG)
    free_sorted = jnp.sort(free_idx)
    slot_for = jnp.where(new_walk,
                         free_sorted[jnp.clip(order_slots, 0, wt - 1)],
                         BIG)
    can_install = slot_for < wt
    slot_safe = jnp.clip(slot_for, 0, wt - 1).astype(jnp.int32)
    inst = new_walk & can_install
    walk_vpn = trans.walk_vpn.at[slot_safe].set(
        jnp.where(inst, vpn, trans.walk_vpn[slot_safe]))
    walk_asid = trans.walk_asid.at[slot_safe].set(
        jnp.where(inst, asid, trans.walk_asid[slot_safe]))
    walk_done = trans.walk_done.at[slot_safe].set(
        jnp.where(inst, walk_done_new, trans.walk_done[slot_safe]))
    walk_merged_arr = trans.walk_merged.at[slot_safe].set(
        jnp.where(inst, 1, trans.walk_merged[slot_safe]))
    # bump merge counters
    first_match = jnp.argmax(wmatch, axis=1)
    walk_merged_arr = walk_merged_arr.at[first_match].add(
        jnp.where(merged, 1, 0))

    # ---------------- translation latency ------------------------------
    trans_lat = jnp.where(
        l1_hit, cfg.lat_l1_tlb,
        jnp.where(l2_hit_eff, cfg.lat_l2_tlb,
                  jnp.where(merged, jnp.maximum(merge_done - t, 1),
                            jnp.maximum(walk_done_new - t, 1))))
    if ideal:
        trans_lat = jnp.where(active, cfg.lat_l1_tlb, 0)

    # ---------------- TLB fills on walk return -------------------------
    if use_l2tlb:
        if tokens_on:
            # tokens are distributed round-robin over the app's cores in
            # warpID order: per-core allowance = tokens / cores_per_app
            cores_per_app = jnp.asarray(cfg.cores_per_app, jnp.int32)
            tok_per_core = tokens.tokens[sched.app] // cores_per_app[sched.app]
            has_tok = sched.slot < tok_per_core
            fill_l2 = need_walk & has_tok & ~tokens.first_epoch
            fill_l2 = fill_l2 | (need_walk & tokens.first_epoch)
            fill_byp = need_walk & ~fill_l2
            byp_tlb = tlb_mod.fill(byp_tlb, vpn, asid, fill_byp, t)
        else:
            fill_l2 = need_walk
        l2tlb = tlb_mod.fill(l2tlb, vpn, asid, fill_l2, t)
    l1 = tlb_mod.fill_bank(l1, vpn, asid, l1_miss, t)

    trans_out = TransOut(
        trans_lat=trans_lat, l1_hit=l1_hit, l1_miss=l1_miss, l2_hit=l2_hit,
        byp_hit=byp_hit, l2_hit_eff=l2_hit_eff, need_walk=need_walk,
        merged=merged, new_walk=new_walk, walk_done_new=walk_done_new,
        dram_tlb_lat=dram_tlb_lat, dram_tlb_n=dram_tlb_n,
        l2c_hit=l2c_hit, l2c_probe=l2c_probe)
    return (TransState(l1=l1, l2tlb=l2tlb, bypass_tlb=byp_tlb, pwc=pwc,
                       walk_vpn=walk_vpn, walk_asid=walk_asid,
                       walk_done=walk_done, walk_merged=walk_merged_arr),
            DataState(l2c=l2c, dram=dram, bypass=bp_state),
            trans_out)


# ---------------------------------------------------------------------------
# stage 3: data path (L1D -> L2$ -> DRAM)
# ---------------------------------------------------------------------------

class DataOut(NamedTuple):
    """Per-core data-access results, all arrays (n_cores,)."""
    data_lat: jax.Array
    l1d_hit: jax.Array
    go_l2d: jax.Array            # bool: reached the shared L2$
    dlat: jax.Array              # L2$/DRAM part of the latency
    l2d_hit: jax.Array           # bool: any of the lines hit the L2$


def datapath(cfg: SimConfig, data: DataState, params_mat, sched: SchedOut, t
             ) -> Tuple[DataState, DataOut]:
    """Data access for the translated request (after the TLB hierarchy)."""
    C = cfg.n_cores
    l2c, dram, bp_state = data.l2c, data.dram, data.bypass
    static = jnp.asarray(cfg.design.partition.kind == "static")

    pfn = pt_mod.translate(pt_mod.PageTableConfig(), sched.asid, sched.vpn)
    r = _mix(pfn.astype(jnp.uint32) + sched.pos.astype(jnp.uint32))
    l1d_hit = (r % jnp.uint32(1024)).astype(jnp.int32) \
        < params_mat[sched.app, FIELD["l1d_hit_milli"]]
    # warp-wide (divergent) data access: one memory instruction touches
    # DATA_WIDTH cache lines, serviced in parallel (latency = max). This is
    # what gives data traffic its realistic flooding pressure on the shared
    # L2 relative to page-walk traffic.
    go_l2d = sched.active & ~l1d_hit
    dlat = jnp.zeros((C,), jnp.int32)
    l2d_hit_any = jnp.zeros((C,), bool)
    for k in range(DATA_WIDTH):
        r3 = _mix(r + jnp.uint32((0x85EBCA6B + 0x9E3779B9 * k) & 0xFFFFFFFF))
        data_line = pfn * 32 + (r3 % jnp.uint32(32)).astype(jnp.int32)
        l2c, dram, dlat_k, l2d_hit = _l2_cache_access(
            cfg, l2c, dram, data_line, sched.app, jnp.zeros((C,), bool),
            jnp.ones((C,), bool), go_l2d, t, static)
        dlat = jnp.maximum(dlat, dlat_k)
        l2d_hit_any = l2d_hit_any | l2d_hit
        bp_state = bp_mod.record(bp_state, jnp.zeros((C,), jnp.int32),
                                 l2d_hit, go_l2d)
    data_lat = jnp.where(l1d_hit, cfg.lat_l1_data, cfg.lat_l1_data + dlat)
    return (DataState(l2c=l2c, dram=dram, bypass=bp_state),
            DataOut(data_lat=data_lat, l1d_hit=l1d_hit, go_l2d=go_l2d,
                    dlat=dlat, l2d_hit=l2d_hit_any))


# ---------------------------------------------------------------------------
# stage 4: statistics accumulation
# ---------------------------------------------------------------------------

def accumulate_stats(stats: StatState, n_apps: int, sched: SchedOut,
                     tout: TransOut, dout: DataOut, t) -> StatState:
    """Fold one cycle's per-core outcomes into the per-app counters."""
    oh = jax.nn.one_hot(sched.app, n_apps, dtype=jnp.int32) \
        * sched.active[:, None]
    ohf = oh.astype(jnp.float32)
    psum = lambda x: (oh * x[:, None]).sum(0)  # noqa: E731
    fsum = lambda x: (ohf * x[:, None]).sum(0)  # noqa: E731
    return StatState(
        s_l1_hit=stats.s_l1_hit + psum(tout.l1_hit),
        s_l1_miss=stats.s_l1_miss + psum(tout.l1_miss),
        s_l2_hit=stats.s_l2_hit + psum(tout.l2_hit),
        s_l2_miss=stats.s_l2_miss + psum(tout.need_walk),
        s_byp_hit=stats.s_byp_hit + psum(tout.byp_hit),
        s_byp_probe=stats.s_byp_probe + psum(tout.l1_miss & ~tout.l2_hit),
        s_walk_lat=stats.s_walk_lat
        + fsum(jnp.where(tout.new_walk, tout.walk_done_new - t, 0)),
        s_walks=stats.s_walks + psum(tout.new_walk),
        s_stall_per_miss=stats.s_stall_per_miss + fsum(tout.merged),
        s_dram_tlb_lat=stats.s_dram_tlb_lat + fsum(tout.dram_tlb_lat),
        s_dram_tlb_n=stats.s_dram_tlb_n + psum(tout.dram_tlb_n),
        s_dram_data_lat=stats.s_dram_data_lat
        + fsum(jnp.where(dout.go_l2d, dout.dlat, 0)),
        s_dram_data_n=stats.s_dram_data_n + psum(dout.go_l2d),
        s_l2c_tlb_hit=stats.s_l2c_tlb_hit + tout.l2c_hit,
        s_l2c_tlb_probe=stats.s_l2c_tlb_probe + tout.l2c_probe,
        s_l2c_data_hit=stats.s_l2c_data_hit
        + (dout.go_l2d & dout.l2d_hit).sum(dtype=jnp.int32),
        s_l2c_data_probe=stats.s_l2c_data_probe
        + dout.go_l2d.sum(dtype=jnp.int32),
    )


# ---------------------------------------------------------------------------
# retire + epoch maintenance
# ---------------------------------------------------------------------------

def retire(stall_until, instr, pos, sched: SchedOut, total_lat, gap, t):
    """Stall issued warps until their latency resolves; credit instrs."""
    w = sched.picked_warp
    stall_until = stall_until.at[w].set(
        jnp.where(sched.active, t + total_lat, stall_until[w]))
    instr = instr.at[w].add(
        jnp.where(sched.active, (1 + gap).astype(jnp.float32), 0.0))
    pos = pos.at[w].add(jnp.where(sched.active, 1, 0))
    return stall_until, instr, pos


def epoch_maintenance(cfg: SimConfig, trans: TransState,
                      tokens: tok_mod.TokenState, data: DataState, t
                      ) -> Tuple[tok_mod.TokenState, DataState]:
    """Every epoch_cycles: token hill-climb, DRAM pressure, bypass latch.

    `trans` must be the PRE-update translation state: the walk table is
    sampled before this cycle's installs, matching the paper's epoch-end
    census of in-flight walks."""
    des = cfg.design
    na = cfg.n_apps

    def do_epoch(args):
        tokens, dram, bp = args
        warps_per_app = jnp.asarray(cfg.warps_per_app, jnp.int32)
        conc = jnp.zeros((na,), jnp.int32).at[
            jnp.clip(trans.walk_asid, 0, na - 1)].add(
            (trans.walk_done > t).astype(jnp.int32))
        stalled = jnp.zeros((na,), jnp.int32).at[
            jnp.clip(trans.walk_asid, 0, na - 1)].add(
            trans.walk_merged * (trans.walk_done > t))
        dram = dram_sched.update_pressure(dram, conc, stalled)
        return (tok_mod.epoch_update(tokens, warps_per_app,
                                     step_frac=des.tokens.step_frac), dram,
                bp_mod.epoch_update(bp))

    any_adaptive = (des.tokens.enabled or des.dram.enabled
                    or des.bypass.enabled)
    is_epoch = (t % des.epoch_cycles) == 0
    tokens, dram, bp_state = jax.lax.cond(
        is_epoch & jnp.asarray(any_adaptive),
        do_epoch, lambda args: args, (tokens, data.dram, data.bypass))
    return tokens, data._replace(dram=dram, bypass=bp_state)


# ---------------------------------------------------------------------------
# one-cycle transition: thin composition of the stages
# ---------------------------------------------------------------------------

def step(cfg: SimConfig, params_mat, state: SimState) -> SimState:
    """One cycle. params_mat: (n_apps, N_FIELDS) int32 workload params."""
    t = state.t + 1
    sched = warp_sched(cfg, params_mat, state.stall_until, state.pos, t)
    trans_st, data_st, tout = translation(
        cfg, state.trans, state.data, state.tokens, sched, t)
    data_st, dout = datapath(cfg, data_st, params_mat, sched, t)

    gap = params_mat[sched.app, FIELD["gap"]]
    total_lat = tout.trans_lat + dout.data_lat + gap
    stall_until, instr, pos = retire(
        state.stall_until, state.instr, state.pos, sched, total_lat, gap, t)

    tokens = tok_mod.record(state.tokens, sched.app, tout.l2_hit_eff,
                            tout.l1_miss)
    stats = accumulate_stats(state.stats, cfg.n_apps, sched, tout, dout, t)
    tokens, data_st = epoch_maintenance(cfg, state.trans, tokens, data_st, t)

    return SimState(t=t, stall_until=stall_until, instr=instr, pos=pos,
                    trans=trans_st, data=data_st, tokens=tokens, stats=stats)


# ---------------------------------------------------------------------------
# reference entry points (additions — everything above is the frozen copy)
# ---------------------------------------------------------------------------

def run_ref(cfg: SimConfig, params_mat) -> SimState:
    """Scan the reference step over cfg.sim_cycles under jit."""

    @jax.jit
    def run(pm):
        st = init_state(cfg)

        def body(s, _):
            return step(cfg, pm, s), None

        final, _ = jax.lax.scan(body, st, None, length=cfg.sim_cycles)
        return final

    return jax.device_get(run(params_mat))


def metrics(cfg: SimConfig, st: SimState) -> dict:
    """Paper-metric dict from a reference final state (old _stats maths)."""
    import numpy as np
    na = cfg.n_apps
    warp_app = np.repeat(np.asarray(cfg.app_of_core), cfg.warps_per_core)
    instr = np.asarray(st.instr)
    ipc = np.array([instr[warp_app == a].sum() for a in range(na)]) \
        / float(st.t)
    s = st.stats
    g = lambda x: np.asarray(x, np.float64)  # noqa: E731
    l1p = g(s.s_l1_hit) + g(s.s_l1_miss)
    l2p = g(s.s_l2_hit) + g(s.s_l2_miss)
    return {
        "ipc": ipc,
        "l1_hit_rate": g(s.s_l1_hit) / np.maximum(l1p, 1),
        "l2_hit_rate": g(s.s_l2_hit) / np.maximum(l2p, 1),
        "byp_hit_rate": g(s.s_byp_hit) / np.maximum(g(s.s_byp_probe), 1),
        "walk_lat": g(s.s_walk_lat) / np.maximum(g(s.s_walks), 1),
        "walks": g(s.s_walks),
        "dram_tlb_lat": g(s.s_dram_tlb_lat) / np.maximum(g(s.s_dram_tlb_n), 1),
        "dram_data_lat": g(s.s_dram_data_lat)
        / np.maximum(g(s.s_dram_data_n), 1),
        "l2c_tlb_hit_rate": (g(s.s_l2c_tlb_hit)
                             / np.maximum(g(s.s_l2c_tlb_probe), 1)),
        "l2c_data_hit_rate": (g(s.s_l2c_data_hit)
                              / np.maximum(g(s.s_l2c_data_probe), 1)),
        "tokens": np.asarray(st.tokens.tokens),
    }
