"""Vectorized memory-hierarchy simulator: a lane-fused one-cycle pipeline.

The cycle transition is composed of pure stages, each with its own state /
result NamedTuple so every layer is individually unit-testable:

  warp_sched           -- per-core GTO-like pick (oldest-ready-first): one
                          ready warp per core issues one memory instruction.
  translation_probe    -- per-core L1 TLB bank -> shared L2 TLB (+ bypass
                          cache) probes/fills, MSHR-style merging of
                          concurrent walks to the same (ASID, VPN) (Fig. 4's
                          multi-warp stalls), PWC lookups, and generation of
                          the page-walk PTE lanes.
  datapath_front       -- L1D hit draw + the DATA_WIDTH divergent line
                          addresses of the translated access.
  shared_memory_access -- ONE lane-flattened L2$ + DRAM round for ALL of a
                          cycle's sub-accesses: the walk_levels PTE lanes
                          and the DATA_WIDTH data lanes, (C*(L+K),) flat.
                          This used to be 8 back-to-back probe/fill/DRAM
                          pipelines per cycle; `tlb.access_fused` keeps the
                          cross-round semantics (later waves observing
                          earlier fills, per-(set, wave) fill ports, LRU
                          victim chains) inside the single batched call.
  translation_commit   -- walk latencies, walk-table install, translation
                          latency resolution.
  accumulate_stats     -- per-app counters behind the paper's tables and
                          figures, packed into one int32 plane + one
                          float32 plane + a 4-vector of shared counters,
                          each updated by a single segment-sum.

`step` is a thin composition of those stages plus warp retire and epoch
maintenance. Every design point (ideal / PWC / GPU-MMU / Static /
MASK±components, plus any user-registered composition) is this same
pipeline, dispatched on the design's two planes (`repro.core.design`):

  * the STATIC SIGNATURE (`cfg.design` — sizing, walk depth/table, epoch
    length, ideal-vs-not) picks the traced program structure; `cfg` is
    expected to carry the signature group's canonical design;
  * the traced `DesignParams` plane (`dp` — policy booleans, token
    budgets, DRAM quota ceiling) is selected on with `jnp.where` and
    masked probes/fills, never Python branches, so ONE compiled program
    serves every design in a signature group and a whole design x mix
    grid can be vmapped through it.

`n_apps` is arbitrary: the paper's 2-app pairs are just N=2.

All translation caches (L1 bank, L2 TLB, bypass cache, PWC, and the
line-addressed L2 data cache) share `core/tlb.py`'s probe/fill machinery;
the L1 bank is a TLBState with a leading (n_cores,) axis driven by the
direct bank kernels.

All state lives in `SimState` arrays -> the whole run is one lax.scan.
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
from repro.core.design import DesignParams, static_partition_index
from repro.core.page_table import _mix
from repro.sim.config import SimConfig
from repro.sim.workloads import FIELD, gen_vpn

DATA_WIDTH = 4           # divergent cache lines per memory instruction
BIG = jnp.int32(1 << 30)
# the concurrent-page-walk table size (Table 1: 64) comes from
# cfg.design.translation.max_concurrent_walks

# packed walk-table columns: TransState.walk is (max_concurrent_walks, 4)
WVPN, WASID, WDONE, WMERGED = range(4)

# packed per-app int32 counter plane: StatState.ints is (n_apps, N_INT)
(I_L1_HIT, I_L1_MISS, I_L2_HIT, I_L2_MISS, I_BYP_HIT, I_BYP_PROBE,
 I_WALKS, I_DRAM_TLB_N, I_DRAM_DATA_N) = range(9)
N_INT = 9
# packed per-app float32 plane: StatState.floats is (n_apps, N_FLOAT)
F_WALK_LAT, F_STALL_PER_MISS, F_DRAM_TLB_LAT, F_DRAM_DATA_LAT = range(4)
N_FLOAT = 4
# shared (not per-app) counters: StatState.scalars is (N_SCALAR,)
S_L2C_TLB_HIT, S_L2C_TLB_PROBE, S_L2C_DATA_HIT, S_L2C_DATA_PROBE = range(4)
N_SCALAR = 4


# ---------------------------------------------------------------------------
# layered state
# ---------------------------------------------------------------------------

class TransState(NamedTuple):
    """Translation layer: TLB hierarchy + in-flight page-walk table."""
    l1: tlb_mod.TLBState         # per-core bank, leading axis (n_cores,)
    l2tlb: tlb_mod.TLBState
    bypass_tlb: tlb_mod.TLBState
    pwc: tlb_mod.TLBState        # page-walk cache (PTE lines)
    walk: jax.Array              # (max_concurrent_walks, 4) int32 packed
    #                              columns: WVPN, WASID, WDONE, WMERGED

    @property
    def walk_vpn(self) -> jax.Array:
        return self.walk[..., WVPN]

    @property
    def walk_asid(self) -> jax.Array:
        return self.walk[..., WASID]

    @property
    def walk_done(self) -> jax.Array:
        return self.walk[..., WDONE]

    @property
    def walk_merged(self) -> jax.Array:
        return self.walk[..., WMERGED]


class DataState(NamedTuple):
    """Shared data path: L2 data cache, DRAM, bypass accounting."""
    l2c: tlb_mod.TLBState        # line-addressed, reuses TLB machinery
    dram: dram_sched.DramState
    bypass: bp_mod.BypassState


class StatState(NamedTuple):
    """Cumulative counters, packed into three planes.

    `ints` / `floats` have the app axis first and the counter index last
    (the I_* / F_* constants), so one segment-sum over the per-core lane
    outcomes updates a whole plane; `scalars` holds the shared
    (non-per-app) L2$ counters (S_* constants). The legacy `s_*` names are
    kept as read-only views so stats consumers and tests are unchanged.
    """
    ints: jax.Array              # (n_apps, N_INT) int32
    floats: jax.Array            # (n_apps, N_FLOAT) float32
    scalars: jax.Array           # (N_SCALAR,) int32

    s_l1_hit = property(lambda s: s.ints[..., I_L1_HIT])
    s_l1_miss = property(lambda s: s.ints[..., I_L1_MISS])
    s_l2_hit = property(lambda s: s.ints[..., I_L2_HIT])
    s_l2_miss = property(lambda s: s.ints[..., I_L2_MISS])
    s_byp_hit = property(lambda s: s.ints[..., I_BYP_HIT])
    s_byp_probe = property(lambda s: s.ints[..., I_BYP_PROBE])
    s_walks = property(lambda s: s.ints[..., I_WALKS])
    s_dram_tlb_n = property(lambda s: s.ints[..., I_DRAM_TLB_N])
    s_dram_data_n = property(lambda s: s.ints[..., I_DRAM_DATA_N])
    s_walk_lat = property(lambda s: s.floats[..., F_WALK_LAT])
    s_stall_per_miss = property(lambda s: s.floats[..., F_STALL_PER_MISS])
    s_dram_tlb_lat = property(lambda s: s.floats[..., F_DRAM_TLB_LAT])
    s_dram_data_lat = property(lambda s: s.floats[..., F_DRAM_DATA_LAT])
    s_l2c_tlb_hit = property(lambda s: s.scalars[..., S_L2C_TLB_HIT])
    s_l2c_tlb_probe = property(lambda s: s.scalars[..., S_L2C_TLB_PROBE])
    s_l2c_data_hit = property(lambda s: s.scalars[..., S_L2C_DATA_HIT])
    s_l2c_data_probe = property(lambda s: s.scalars[..., S_L2C_DATA_PROBE])


class SimState(NamedTuple):
    t: jax.Array                 # () int32
    stall_until: jax.Array       # (W,) int32
    instr: jax.Array             # (W,) float32 retired instructions
    pos: jax.Array               # (W,) int32 stream position
    trans: TransState
    data: DataState
    tokens: tok_mod.TokenState
    stats: StatState
    # (n_apps,) int32 live ASID per application SLOT. Fixed mixes keep the
    # identity map (asid == slot); the segmented trace runner bumps a
    # slot's ASID by n_apps on every membership change, so an arriving
    # app gets a FRESH address space (its translations can never alias a
    # predecessor's) and a departed app's ASID is dead forever. Slot
    # recovery is always `asid % n_apps`.
    asid_of_app: jax.Array


def init_trans(cfg: SimConfig) -> TransState:
    tr = cfg.design.translation
    tok = cfg.design.tokens
    wt = tr.max_concurrent_walks
    return TransState(
        l1=tlb_mod.init_bank(cfg.n_cores, tr.l1_entries, tr.l1_entries),
        l2tlb=tlb_mod.init(tr.l2_entries, tr.l2_ways),
        bypass_tlb=tlb_mod.init(tok.bypass_cache_entries,
                                tok.bypass_cache_entries),
        pwc=tlb_mod.init(cfg.pwc_entries, cfg.pwc_ways),
        walk=jnp.tile(jnp.asarray([-1, -1, 0, 0], jnp.int32), (wt, 1)),
    )


def init_data(cfg: SimConfig) -> DataState:
    return DataState(
        l2c=tlb_mod.init(cfg.l2_sets * cfg.l2_ways, cfg.l2_ways),
        dram=dram_sched.init(cfg.n_channels, cfg.n_banks, cfg.n_apps),
        bypass=bp_mod.init(),
    )


def init_stats(n_apps: int) -> StatState:
    return StatState(
        ints=jnp.zeros((n_apps, N_INT), jnp.int32),
        floats=jnp.zeros((n_apps, N_FLOAT), jnp.float32),
        scalars=jnp.zeros((N_SCALAR,), jnp.int32),
    )


def init_state(cfg: SimConfig, dp: DesignParams) -> SimState:
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
                            dp.initial_frac),
        stats=init_stats(cfg.n_apps),
        asid_of_app=jnp.arange(cfg.n_apps, dtype=jnp.int32),
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


def warp_sched(cfg: SimConfig, params_mat, stall_until, pos, t,
               asid_of_app=None) -> SchedOut:
    """GTO-like pick: per core, the ready warp that has waited longest.

    `asid_of_app` is the (n_apps,) live-ASID map carried in `SimState`;
    None means the identity map (asid == app slot), which is exactly what
    fixed-mix runs use — the gather then returns the slot ids bit-for-bit.
    """
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
    # one address space per application slot occupancy (see SimState)
    asid = app if asid_of_app is None else asid_of_app[app]
    return SchedOut(picked_warp=picked_warp, slot=pick, active=active,
                    app=app, asid=asid, vpn=vpn, pos=p)


# ---------------------------------------------------------------------------
# stage 2a: translation probes (L1 TLB bank -> L2 TLB/bypass -> walk setup)
# ---------------------------------------------------------------------------

class TransProbe(NamedTuple):
    """Front half of translation: everything before the shared L2$/DRAM.

    Per-core arrays are (C,); the walk lanes are flattened wave-major
    ((walk_levels * C,), level slowest) so the shared memory stage can
    service them in one batched call. For the "ideal" design the walk
    machinery traces out entirely and the lane arrays are empty.
    """
    l1_hit: jax.Array
    l1_miss: jax.Array
    l2_hit: jax.Array
    byp_hit: jax.Array
    l2_hit_eff: jax.Array        # L2 or bypass-cache hit
    need_walk: jax.Array
    merged: jax.Array            # joined an in-flight walk
    merge_done: jax.Array        # completion time of the joined walk
    first_match: jax.Array       # walk-table slot of the joined walk
    new_walk: jax.Array          # started a fresh walk
    queue_pen: jax.Array         # finite-walker-thread queue penalty
    pwc_lat: jax.Array           # (C,) summed 5-cycle PWC-hit latencies
    walk_lines: jax.Array        # (L*C,) PTE line ids, wave-major
    walk_go: jax.Array           # (L*C,) bool: lanes that access the L2$
    walk_tags: jax.Array         # (L*C,) page-walk depth tags (§5.3)


def translation_probe(cfg: SimConfig, dp: DesignParams, trans: TransState,
                      tokens: tok_mod.TokenState, sched: SchedOut, t
                      ) -> Tuple[TransState, TransProbe]:
    """TLB hierarchy probes/fills + page-walk lane generation.

    Structural dispatch (ideal-vs-not) is by the static signature carried
    in `cfg.design`; every policy knob below that — shared-L2-TLB vs PWC
    vs walk-only organization, tokens on/off — is a traced `dp` flag
    selected with masked probes/fills (a probe or fill whose active mask
    is all-False is a state no-op), so all non-ideal designs share one
    compiled pipeline."""
    tr = cfg.design.translation
    ideal = tr.kind == "ideal"
    C = cfg.n_cores
    vpn, asid, active = sched.vpn, sched.asid, sched.active

    # ---------------- L1 TLB bank --------------------------------------
    l1, l1_hit = tlb_mod.probe_bank(trans.l1, vpn, asid, active, t)
    if ideal:
        l1_hit = active
    l1_miss = active & ~l1_hit

    zb = jnp.zeros((C,), bool)
    zi = jnp.zeros((C,), jnp.int32)
    if ideal:
        l2_hit = jnp.zeros_like(l1_miss)
        need_walk = l1_miss          # identically False (l1_hit == active)
        # need_walk is identically False: the walk lanes, MSHR table, and
        # walker queue model all trace out of the compiled graph
        return (TransState(l1=l1, l2tlb=trans.l2tlb,
                           bypass_tlb=trans.bypass_tlb,
                           pwc=trans.pwc, walk=trans.walk),
                TransProbe(l1_hit=l1_hit, l1_miss=l1_miss, l2_hit=l2_hit,
                           byp_hit=jnp.zeros_like(l2_hit),
                           l2_hit_eff=l2_hit,
                           need_walk=need_walk, merged=zb, merge_done=zi,
                           first_match=zi, new_walk=zb, queue_pen=zi,
                           pwc_lat=zi,
                           walk_lines=jnp.zeros((0,), jnp.int32),
                           walk_go=jnp.zeros((0,), bool),
                           walk_tags=jnp.zeros((0,), jnp.int32)))

    # ---------------- shared L2 TLB + bypass cache ---------------------
    # organization selectors are traced: non-participating caches are
    # probed/filled with an all-False mask (a state no-op yielding
    # all-False hits) — identical to skipping them. The bypass cache is
    # additionally wrapped in a lax.cond so token-less designs skip its
    # work at runtime (under a design-batched vmap the cond becomes a
    # select, which computes both branches but picks identical values)
    use_l2 = dp.use_l2_tlb
    l2tlb, l2_hit = tlb_mod.probe(trans.l2tlb, vpn, asid,
                                  l1_miss & use_l2, t)
    byp_tlb, byp_hit = jax.lax.cond(
        dp.tokens_on & use_l2,
        lambda st: tlb_mod.probe(st, vpn, asid, l1_miss & ~l2_hit, t),
        lambda st: (st, jnp.zeros_like(l1_miss)),
        trans.bypass_tlb)
    l2_hit_eff = l2_hit | byp_hit
    need_walk = l1_miss & ~l2_hit_eff

    # ---------------- TLB fills on walk return -------------------------
    # (independent of the walk's memory latency, so they live here).
    # Tokens are distributed round-robin over the app's cores in warpID
    # order: per-core allowance = tokens / cores_per_app. With tokens off
    # the gate is identically True (every walk may fill the L2 TLB).
    cores_per_app = jnp.asarray(cfg.cores_per_app, jnp.int32)
    tok_per_core = tokens.tokens[sched.app] // cores_per_app[sched.app]
    has_tok = sched.slot < tok_per_core
    gate = jnp.where(dp.tokens_on,
                     (has_tok & ~tokens.first_epoch) | tokens.first_epoch,
                     True)
    fill_l2 = need_walk & use_l2 & gate
    fill_byp = need_walk & use_l2 & ~gate    # ~gate implies tokens_on
    byp_tlb = jax.lax.cond(
        dp.tokens_on & use_l2,
        lambda st: tlb_mod.fill(st, vpn, asid, fill_byp, t),
        lambda st: st, byp_tlb)
    l2tlb = tlb_mod.fill(l2tlb, vpn, asid, fill_l2, t)

    l1 = tlb_mod.fill_bank(l1, vpn, asid, l1_miss, t)

    # ---------------- MSHR merge: outstanding walk for same (vpn, asid)?
    walk_vpn, walk_asid, walk_done = (trans.walk[:, WVPN],
                                      trans.walk[:, WASID],
                                      trans.walk[:, WDONE])
    wmatch = (walk_vpn[None, :] == vpn[:, None]) & \
             (walk_asid[None, :] == asid[:, None]) & \
             (walk_done[None, :] > t)
    merged = wmatch.any(axis=1) & need_walk
    merge_done = jnp.where(
        merged, jnp.max(jnp.where(wmatch, walk_done[None, :], 0), axis=1), 0)
    first_match = jnp.argmax(wmatch, axis=1)

    new_walk = need_walk & ~merged
    n_live = (walk_done > t).sum()
    # walker occupancy queue penalty (finite walker threads)
    wt = tr.max_concurrent_walks
    over = jnp.maximum(n_live + jnp.cumsum(new_walk) - wt, 0)
    queue_pen = over * 30

    # ---------------- page-walk lanes (walk_levels dependent PTE lines)
    L = tr.walk_levels
    pte_lines = pt_mod.pte_line_addresses(
        pt_mod.PageTableConfig(levels=L), asid, vpn)      # (C, L)
    walk_lines = pte_lines.T.reshape(L * C)               # wave-major
    walk_active = jnp.tile(new_walk, L)
    walk_tags = jnp.repeat(jnp.asarray(
        [pt_mod.walk_depth_tag(lv) for lv in range(L)], jnp.int32), C)

    # fused probe+fill with per-(set, level) fill ports — PTE lines are
    # unique across levels, so the PWC is tag-only too. The organization
    # selector is a lax.cond so non-PWC designs skip the whole PWC round
    # at runtime (pwc_hit all-False makes the lines below reduce to
    # walk_go = walk_active, pwc_lat = 0); under a design-batched vmap
    # the cond lowers to a select over identical per-design values.
    pwc, pwc_hit = jax.lax.cond(
        dp.use_pwc,
        lambda st: tlb_mod.access_fused(
            st, walk_lines, walk_active, jnp.ones((L * C,), bool), t,
            n_waves=L)[:2],
        lambda st: (st, jnp.zeros((L * C,), bool)),
        trans.pwc)
    walk_go = walk_active & ~pwc_hit
    pwc_lat = 5 * (walk_active & pwc_hit).reshape(L, C) \
        .sum(0, dtype=jnp.int32)

    return (TransState(l1=l1, l2tlb=l2tlb, bypass_tlb=byp_tlb, pwc=pwc,
                       walk=trans.walk),
            TransProbe(l1_hit=l1_hit, l1_miss=l1_miss, l2_hit=l2_hit,
                       byp_hit=byp_hit, l2_hit_eff=l2_hit_eff,
                       need_walk=need_walk, merged=merged,
                       merge_done=merge_done, first_match=first_match,
                       new_walk=new_walk, queue_pen=queue_pen,
                       pwc_lat=pwc_lat, walk_lines=walk_lines,
                       walk_go=walk_go, walk_tags=walk_tags))


# ---------------------------------------------------------------------------
# stage 2b: data-path front (L1D draw + divergent line generation)
# ---------------------------------------------------------------------------

class DataFront(NamedTuple):
    """L1D outcome + the data lanes headed for the shared L2$."""
    l1d_hit: jax.Array           # (C,) bool
    go_l2d: jax.Array            # (C,) bool: reached the shared L2$
    lines: jax.Array             # (DATA_WIDTH*C,) line ids, wave-major


def datapath_front(cfg: SimConfig, params_mat, sched: SchedOut, t
                   ) -> DataFront:
    """Draw the L1D outcome and generate the divergent line addresses."""
    pfn = pt_mod.translate(pt_mod.PageTableConfig(), sched.asid, sched.vpn)
    r = _mix(pfn.astype(jnp.uint32) + sched.pos.astype(jnp.uint32))
    l1d_hit = (r % jnp.uint32(1024)).astype(jnp.int32) \
        < params_mat[sched.app, FIELD["l1d_hit_milli"]]
    # warp-wide (divergent) data access: one memory instruction touches
    # DATA_WIDTH cache lines, serviced in parallel (latency = max). This is
    # what gives data traffic its realistic flooding pressure on the shared
    # L2 relative to page-walk traffic.
    go_l2d = sched.active & ~l1d_hit
    lines = []
    for k in range(DATA_WIDTH):
        r3 = _mix(r + jnp.uint32((0x85EBCA6B + 0x9E3779B9 * k) & 0xFFFFFFFF))
        lines.append(pfn * 32 + (r3 % jnp.uint32(32)).astype(jnp.int32))
    return DataFront(l1d_hit=l1d_hit, go_l2d=go_l2d,
                     lines=jnp.stack(lines).reshape(DATA_WIDTH * pfn.shape[0]))


# ---------------------------------------------------------------------------
# stage 3: the ONE shared L2$ + DRAM round for all of a cycle's lanes
# ---------------------------------------------------------------------------

class MemOut(NamedTuple):
    """Per-core splits of the fused round (walk part + data part)."""
    walk_lat: jax.Array          # (C,) summed walk-level L2$/DRAM latency
    dram_tlb_lat: jax.Array      # (C,) float32 DRAM latency on walk path
    dram_tlb_n: jax.Array        # (C,) int32
    l2c_tlb_hit: jax.Array       # () walk-request hits in the L2$
    l2c_tlb_probe: jax.Array     # () walk-request probes of the L2$
    dlat: jax.Array              # (C,) max-over-lines data latency
    l2d_hit: jax.Array           # (C,) bool: any data line hit the L2$


def shared_memory_access(cfg: SimConfig, dp: DesignParams, data: DataState,
                         app, walk_lines, walk_go, walk_tags,
                         data_lines, go_l2d, t) -> Tuple[DataState, MemOut]:
    """Shared L2 data cache + DRAM for ALL of a cycle's sub-accesses.

    Lanes are flattened wave-major (walk level 0..L-1, then data line
    0..K-1, each wave C cores wide) so lane order equals the sequential
    model's program order: `tlb.access_fused` resolves cross-wave fills /
    forwarding inside one call, and `dram_sched.access`'s in-batch ranking
    gives walk (golden-class) requests priority over the same cycle's data
    requests. Either lane group may be empty (stage unit tests).
    """
    C = app.shape[0]
    nw = walk_lines.shape[0]
    nd = data_lines.shape[0]
    L, K = nw // C, nd // C

    lines = jnp.concatenate([walk_lines, data_lines])
    go = jnp.concatenate([walk_go, jnp.tile(go_l2d, K)])
    apps = jnp.tile(app, L + K)
    is_tlb = jnp.concatenate([jnp.ones((nw,), bool), jnp.zeros((nd,), bool)])
    depth = jnp.concatenate([walk_tags, jnp.zeros((nd,), jnp.int32)])

    l2c, dram, bp_state = data.l2c, data.dram, data.bypass
    # depth 0 (data) always fills, so one decision covers every lane;
    # with bypass off every lane may fill
    may_fill = jnp.where(dp.bypass_on,
                         bp_mod.should_fill(bp_state, depth), True)

    # `Static` gives each app an equal slice of the sets/channels by
    # restricting its index range; the selector is traced, so one program
    # serves both partitionings (both index computations are a handful of
    # integer lane ops)
    key = jnp.where(
        dp.static_part,
        static_partition_index(lines, cfg.l2_sets, cfg.n_apps, apps),
        lines % cfg.l2_sets)
    channel = jnp.where(
        dp.static_part,
        static_partition_index(lines, cfg.n_channels, cfg.n_apps, apps),
        lines % cfg.n_channels).astype(jnp.int32)

    # reuse TLB machinery: tag = full line id (unique, so the line cache
    # is tag-only)
    l2c, hit, _ = tlb_mod.access_fused(
        l2c, lines * cfg.l2_sets + key, go, may_fill, t,
        n_waves=max(L + K, 1))
    lat = jnp.where(hit, cfg.lat_l2_cache, 0)
    miss = go & ~hit

    bank = ((lines // cfg.n_channels) % cfg.n_banks).astype(jnp.int32)
    row = (lines // (cfg.n_channels * cfg.n_banks * 32)).astype(jnp.int32)
    dram, dram_lat = dram_sched.access(
        dram, channel, bank, row, apps, is_tlb, miss,
        mask_enabled=dp.dram_on, thres_max=dp.thres_max,
        waves=max(L + K, 1))
    lat = lat + jnp.where(miss, cfg.lat_l2_cache + dram_lat, 0)
    bp_state = bp_mod.record(bp_state, depth, hit, go)

    # ---------------- split back per core ------------------------------
    zi = jnp.zeros((C,), jnp.int32)
    zs = jnp.zeros((), jnp.int32)
    if nw:
        lat_w = lat[:nw].reshape(L, C)
        went = walk_go.reshape(L, C) & ~hit[:nw].reshape(L, C)
        walk_lat = lat_w.sum(0)          # inactive lanes contribute 0
        dram_tlb_lat = jnp.where(went, lat_w, 0).sum(0).astype(jnp.float32)
        dram_tlb_n = went.sum(0, dtype=jnp.int32)
        l2c_tlb_hit = (hit[:nw] & walk_go).sum(dtype=jnp.int32)
        l2c_tlb_probe = walk_go.sum(dtype=jnp.int32)
    else:
        walk_lat, dram_tlb_n, l2c_tlb_hit, l2c_tlb_probe = zi, zi, zs, zs
        dram_tlb_lat = jnp.zeros((C,), jnp.float32)
    if nd:
        dlat = lat[nw:].reshape(K, C).max(0)
        l2d_hit = hit[nw:].reshape(K, C).any(0)
    else:
        dlat = zi
        l2d_hit = jnp.zeros((C,), bool)

    return (DataState(l2c=l2c, dram=dram, bypass=bp_state),
            MemOut(walk_lat=walk_lat, dram_tlb_lat=dram_tlb_lat,
                   dram_tlb_n=dram_tlb_n, l2c_tlb_hit=l2c_tlb_hit,
                   l2c_tlb_probe=l2c_tlb_probe, dlat=dlat,
                   l2d_hit=l2d_hit))


# ---------------------------------------------------------------------------
# stage 4: translation commit (walk latency, walk-table install)
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


def translation_commit(cfg: SimConfig, trans: TransState, probe: TransProbe,
                       mem: MemOut, sched: SchedOut, t
                       ) -> Tuple[TransState, TransOut]:
    """Resolve walk latencies, install fresh walks, settle trans latency."""
    des = cfg.design
    tr = des.translation
    C = cfg.n_cores

    if tr.kind == "ideal":
        trans_lat = jnp.where(sched.active, cfg.lat_l1_tlb, 0)
        zi = jnp.zeros((C,), jnp.int32)
        return trans, TransOut(
            trans_lat=trans_lat, l1_hit=probe.l1_hit, l1_miss=probe.l1_miss,
            l2_hit=probe.l2_hit, byp_hit=probe.byp_hit,
            l2_hit_eff=probe.l2_hit_eff, need_walk=probe.need_walk,
            merged=probe.merged, new_walk=probe.new_walk, walk_done_new=zi,
            dram_tlb_lat=jnp.zeros((C,), jnp.float32), dram_tlb_n=zi,
            l2c_hit=jnp.zeros((), jnp.int32),
            l2c_probe=jnp.zeros((), jnp.int32))

    walk_lat = mem.walk_lat + probe.pwc_lat + probe.queue_pen
    walk_done_new = t + cfg.lat_l2_tlb + walk_lat

    # install new walks into free slots (expired entries are free); lanes
    # that install nothing are routed out of bounds and dropped
    wt = tr.max_concurrent_walks
    free = trans.walk[:, WDONE] <= t
    order_slots = jnp.cumsum(probe.new_walk) - 1
    free_idx = jnp.where(free, jnp.arange(wt), BIG)
    free_sorted = jnp.sort(free_idx)
    slot_for = jnp.where(probe.new_walk,
                         free_sorted[jnp.clip(order_slots, 0, wt - 1)],
                         BIG)
    inst = probe.new_walk & (slot_for < wt)
    slot = jnp.where(inst, slot_for, wt).astype(jnp.int32)
    rows = jnp.stack([sched.vpn, sched.asid, walk_done_new,
                      jnp.ones((C,), jnp.int32)], axis=1)      # (C, 4)
    walk = trans.walk.at[slot].set(rows, mode="drop")
    # bump merge counters on the joined in-flight walks
    walk = walk.at[probe.first_match, WMERGED].add(
        jnp.where(probe.merged, 1, 0))

    # ---------------- translation latency ------------------------------
    trans_lat = jnp.where(
        probe.l1_hit, cfg.lat_l1_tlb,
        jnp.where(probe.l2_hit_eff, cfg.lat_l2_tlb,
                  jnp.where(probe.merged,
                            jnp.maximum(probe.merge_done - t, 1),
                            jnp.maximum(walk_done_new - t, 1))))

    return (trans._replace(walk=walk),
            TransOut(trans_lat=trans_lat, l1_hit=probe.l1_hit,
                     l1_miss=probe.l1_miss, l2_hit=probe.l2_hit,
                     byp_hit=probe.byp_hit, l2_hit_eff=probe.l2_hit_eff,
                     need_walk=probe.need_walk, merged=probe.merged,
                     new_walk=probe.new_walk, walk_done_new=walk_done_new,
                     dram_tlb_lat=mem.dram_tlb_lat,
                     dram_tlb_n=mem.dram_tlb_n, l2c_hit=mem.l2c_tlb_hit,
                     l2c_probe=mem.l2c_tlb_probe))


# ---------------------------------------------------------------------------
# data-path result assembly
# ---------------------------------------------------------------------------

class DataOut(NamedTuple):
    """Per-core data-access results, all arrays (n_cores,)."""
    data_lat: jax.Array
    l1d_hit: jax.Array
    go_l2d: jax.Array            # bool: reached the shared L2$
    dlat: jax.Array              # L2$/DRAM part of the latency
    l2d_hit: jax.Array           # bool: any of the lines hit the L2$


def _data_out(cfg: SimConfig, front: DataFront, mem: MemOut) -> DataOut:
    """Assemble the data-path result from the shared-round split."""
    data_lat = jnp.where(front.l1d_hit, cfg.lat_l1_data,
                         cfg.lat_l1_data + mem.dlat)
    return DataOut(data_lat=data_lat, l1d_hit=front.l1d_hit,
                   go_l2d=front.go_l2d, dlat=mem.dlat, l2d_hit=mem.l2d_hit)


# ---------------------------------------------------------------------------
# stage 5: statistics accumulation (packed planes, one segment-sum each)
# ---------------------------------------------------------------------------

def accumulate_stats(stats: StatState, n_apps: int, sched: SchedOut,
                     tout: TransOut, dout: DataOut, t) -> StatState:
    """Fold one cycle's per-core outcomes into the packed stat planes."""
    act = sched.active
    i32 = lambda x: x.astype(jnp.int32)  # noqa: E731
    ints_rows = jnp.stack([
        i32(tout.l1_hit), i32(tout.l1_miss), i32(tout.l2_hit),
        i32(tout.need_walk), i32(tout.byp_hit),
        i32(tout.l1_miss & ~tout.l2_hit), i32(tout.new_walk),
        tout.dram_tlb_n, i32(dout.go_l2d),
    ], axis=1) * act[:, None].astype(jnp.int32)
    floats_rows = jnp.stack([
        jnp.where(tout.new_walk,
                  (tout.walk_done_new - t).astype(jnp.float32), 0.0),
        tout.merged.astype(jnp.float32),
        tout.dram_tlb_lat,
        jnp.where(dout.go_l2d, dout.dlat, 0).astype(jnp.float32),
    ], axis=1) * act[:, None].astype(jnp.float32)
    return StatState(
        ints=stats.ints + jax.ops.segment_sum(ints_rows, sched.app,
                                              num_segments=n_apps),
        floats=stats.floats + jax.ops.segment_sum(floats_rows, sched.app,
                                                  num_segments=n_apps),
        scalars=stats.scalars + jnp.stack([
            tout.l2c_hit, tout.l2c_probe,
            (dout.go_l2d & dout.l2d_hit).sum(dtype=jnp.int32),
            dout.go_l2d.sum(dtype=jnp.int32)]),
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


def epoch_maintenance(cfg: SimConfig, dp: DesignParams, trans: TransState,
                      tokens: tok_mod.TokenState, data: DataState, t
                      ) -> Tuple[tok_mod.TokenState, DataState]:
    """Every epoch_cycles: token hill-climb, DRAM pressure, bypass latch.

    `trans` must be the PRE-update translation state: the walk table is
    sampled before this cycle's installs, matching the paper's epoch-end
    census of in-flight walks. The epoch length is static (signature);
    whether any adaptive mechanism is live is a traced `dp` predicate
    (under a design-batched vmap the cond becomes a select, which is fine
    — `do_epoch` is pure)."""
    na = cfg.n_apps

    def do_epoch(args):
        tokens, dram, bp = args
        warps_per_app = jnp.asarray(cfg.warps_per_app, jnp.int32)
        live = (trans.walk[:, WDONE] > t).astype(jnp.int32)
        census = jnp.stack([live, trans.walk[:, WMERGED] * live], axis=1)
        # slot recovery: ASIDs are slot + k*n_apps after churn (see
        # SimState.asid_of_app). Invalid rows (asid -1) land on slot
        # n_apps-1 but carry live=0, so they contribute nothing — same
        # sums as the pre-churn clip-to-0 routing, bit-for-bit.
        census = jax.ops.segment_sum(
            census, trans.walk[:, WASID] % na, num_segments=na)
        dram = dram_sched.update_pressure(dram, census[:, 0], census[:, 1])
        return (tok_mod.epoch_update(tokens, warps_per_app,
                                     step_frac=dp.step_frac), dram,
                bp_mod.epoch_update(bp))

    any_adaptive = dp.tokens_on | dp.dram_on | dp.bypass_on
    is_epoch = (t % cfg.design.epoch_cycles) == 0
    tokens, dram, bp_state = jax.lax.cond(
        is_epoch & any_adaptive,
        do_epoch, lambda args: args, (tokens, data.dram, data.bypass))
    return tokens, data._replace(dram=dram, bypass=bp_state)


# ---------------------------------------------------------------------------
# one-cycle transition: thin composition of the stages
# ---------------------------------------------------------------------------

def step(cfg: SimConfig, dp: DesignParams, params_mat,
         state: SimState) -> SimState:
    """One cycle. params_mat: (n_apps, N_FIELDS) int32 workload params;
    dp: the design's traced knob plane (see `repro.core.design`).

    Each stage runs under a `jax.named_scope` ("mem.<stage>"), which only
    names its ops in the HLO metadata (`op_name`), so a profiler trace can
    attribute device time to stages; the prefix keeps the scopes apart
    from the Python function names JAX also writes there."""
    t = state.t + 1
    with jax.named_scope("mem.warp_sched"):
        sched = warp_sched(cfg, params_mat, state.stall_until, state.pos, t,
                           asid_of_app=state.asid_of_app)
    with jax.named_scope("mem.translation_probe"):
        trans_st, probe = translation_probe(cfg, dp, state.trans,
                                            state.tokens, sched, t)
    with jax.named_scope("mem.datapath_front"):
        dfront = datapath_front(cfg, params_mat, sched, t)
    with jax.named_scope("mem.shared_round"):
        data_st, mem = shared_memory_access(
            cfg, dp, state.data, sched.app, probe.walk_lines, probe.walk_go,
            probe.walk_tags, dfront.lines, dfront.go_l2d, t)
    with jax.named_scope("mem.translation_commit"):
        trans_st, tout = translation_commit(cfg, trans_st, probe, mem, sched,
                                            t)
    with jax.named_scope("mem.retire"):
        dout = _data_out(cfg, dfront, mem)
        gap = params_mat[sched.app, FIELD["gap"]]
        total_lat = tout.trans_lat + dout.data_lat + gap
        stall_until, instr, pos = retire(
            state.stall_until, state.instr, state.pos, sched, total_lat, gap,
            t)
        tokens = tok_mod.record(state.tokens, sched.app, tout.l2_hit_eff,
                                tout.l1_miss)
    with jax.named_scope("mem.stats"):
        stats = accumulate_stats(state.stats, cfg.n_apps, sched, tout, dout,
                                 t)
    with jax.named_scope("mem.epoch"):
        tokens, data_st = epoch_maintenance(cfg, dp, state.trans, tokens,
                                            data_st, t)

    return SimState(t=t, stall_until=stall_until, instr=instr, pos=pos,
                    trans=trans_st, data=data_st, tokens=tokens, stats=stats,
                    asid_of_app=state.asid_of_app)


# ---------------------------------------------------------------------------
# app churn: membership-change teardown at a segment boundary
# ---------------------------------------------------------------------------

def _flush_slots(st: tlb_mod.TLBState, change, n_apps: int
                 ) -> tlb_mod.TLBState:
    """ASID shootdown for every changed SLOT of an asid-tagged cache.

    Entries store generation-bumped ASIDs (slot + k*n_apps, see
    SimState.asid_of_app), so the kill predicate recovers the slot with
    `% n_apps`. Works on banked states too (extra leading axes). With an
    all-False change mask this is the identity, bit for bit.
    """
    slot = st.asids % n_apps
    kill = (st.asids >= 0) & change[slot]
    return st._replace(tags=jnp.where(kill, -1, st.tags),
                       asids=jnp.where(kill, -1, st.asids))


def apply_membership_change(cfg: SimConfig, dp: DesignParams,
                            state: SimState, change) -> SimState:
    """Teardown + cold-start for the slots flagged in `change` ((n_apps,)
    bool): the departing app's state is torn down and the slot is handed
    to its successor with a FRESH address space.

    Per paper §5.1 shootdown semantics plus the resource release MASK's
    mechanisms need:

      * L1 TLB bank / shared L2 TLB / bypass cache: every entry whose
        ASID maps to a changed slot is invalidated (no stale translations
        can survive — the departed generation's ASID is never reissued);
      * PWC: tag-only (no ASID plane), so it gets a conservative FULL
        flush whenever any slot changes — PTE lines of the dead address
        space are unidentifiable, and a real shootdown invalidates
        page-walk caches along with the TLBs;
      * walk table: in-flight walks of changed slots are cancelled;
      * tokens: changed rows release their TLB-fill tokens and restart
        from the InitialTokens state (fresh hill-climb); the shared
        `first_epoch` warm-up latch is deliberately left alone — it is
        a global bypass gate and re-arming it would perturb the apps
        that did NOT change;
      * DRAM pressure: the changed slots' Concurrent_i / WrpStalled_i
        inputs to the silver-quota Eq. (1) are zeroed until the next
        epoch census; the shared queues/open rows stay (they drain on
        their own and are not address-space state);
      * warps of changed slots rewind to a cold stream (pos 0, no
        retired instructions, ready immediately);
      * stat planes of changed slots reset — the arriving app starts
        with clean counters (the L2 data cache and the shared scalar
        counters are NOT per-address-space state and are untouched).

    Everything is a `jnp.where` on the change mask (plus one `change.any()`
    select for the PWC), so an all-False mask returns `state` bitwise
    unchanged — which is what makes constant-membership segmented runs
    float-hex identical to monolithic ones. The teardown runs under
    `jax.named_scope("mem.membership")`, which names its ops in the HLO
    metadata, so a profiler trace can attribute its device time.
    """
    with jax.named_scope("mem.membership"):
        na = cfg.n_apps
        change = jnp.asarray(change, bool)
        any_c = change.any()

        trans = state.trans
        pwc = trans.pwc._replace(
            tags=jnp.where(any_c, jnp.full_like(trans.pwc.tags, -1),
                           trans.pwc.tags))
        walk_slot = trans.walk[:, WASID] % na
        walk_kill = (trans.walk[:, WASID] >= 0) & change[walk_slot]
        empty_row = jnp.asarray([-1, -1, 0, 0], jnp.int32)
        walk = jnp.where(walk_kill[:, None], empty_row[None, :], trans.walk)
        trans = trans._replace(
            l1=_flush_slots(trans.l1, change, na),
            l2tlb=_flush_slots(trans.l2tlb, change, na),
            bypass_tlb=_flush_slots(trans.bypass_tlb, change, na),
            pwc=pwc, walk=walk)

        fresh_tok = tok_mod.init(
            na, jnp.asarray(cfg.warps_per_app, jnp.int32), dp.initial_frac)
        tok = state.tokens
        tok = tok._replace(
            tokens=jnp.where(change, fresh_tok.tokens, tok.tokens),
            direction=jnp.where(change, fresh_tok.direction, tok.direction),
            prev_miss_rate=jnp.where(change, fresh_tok.prev_miss_rate,
                                     tok.prev_miss_rate),
            epoch_hits=jnp.where(change, 0, tok.epoch_hits),
            epoch_misses=jnp.where(change, 0, tok.epoch_misses))

        dram = state.data.dram
        dram = dram._replace(
            conc_walks=jnp.where(change, 0, dram.conc_walks),
            warps_stalled=jnp.where(change, 0, dram.warps_stalled))

        warp_change = change[jnp.repeat(
            jnp.asarray(cfg.app_of_core, jnp.int32), cfg.warps_per_core)]
        stall_until = jnp.where(warp_change, state.t, state.stall_until)
        instr = jnp.where(warp_change, 0.0, state.instr)
        pos = jnp.where(warp_change, 0, state.pos)

        stats = state.stats._replace(
            ints=jnp.where(change[:, None], 0, state.stats.ints),
            floats=jnp.where(change[:, None], 0.0, state.stats.floats))

        return state._replace(
            stall_until=stall_until, instr=instr, pos=pos, trans=trans,
            data=state.data._replace(dram=dram), tokens=tok, stats=stats,
            asid_of_app=jnp.where(change, state.asid_of_app + na,
                                  state.asid_of_app))
