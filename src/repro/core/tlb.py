"""Set-associative, ASID-tagged TLBs as pure-JAX state (batched probe/fill).

One structure covers the paper's three translation caches:

  * per-core L1 TLB  — 64-entry fully associative (n_sets=1), LRU
  * shared L2 TLB    — 512-entry 16-way, ASID-tagged, LRU
  * bypass cache     — 32-entry fully associative (MASK §5.2)

State is a NamedTuple of arrays so a bank of TLBs (one per core) is just a
leading axis + vmap — `init_bank` / `probe_bank` / `fill_bank` package that
pattern for the simulator's per-core L1 TLBs. Fills are batched; when
several requests map to the same set in one step, one fill wins per set
(ports/fill-bandwidth model — the paper's L2 TLB has 2 ports per memory
partition).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp


class TLBState(NamedTuple):
    tags: jax.Array      # (sets, ways) int32 vpn  (-1 invalid)
    asids: jax.Array     # (sets, ways) int32
    lru: jax.Array       # (sets, ways) int32 last-use time
    hits: jax.Array      # () int32 cumulative
    misses: jax.Array    # () int32


def init(n_entries: int, n_ways: int) -> TLBState:
    n_sets = max(n_entries // n_ways, 1)
    shape = (n_sets, n_ways)
    return TLBState(
        tags=jnp.full(shape, -1, jnp.int32),
        asids=jnp.full(shape, -1, jnp.int32),
        lru=jnp.zeros(shape, jnp.int32),
        hits=jnp.zeros((), jnp.int32),
        misses=jnp.zeros((), jnp.int32),
    )


def probe(state: TLBState, vpn, asid, active, time) -> Tuple[TLBState, jax.Array]:
    """Batched probe. vpn/asid/active: (N,). Returns (state', hit (N,) bool).

    LRU is updated for hits; hit/miss counters accumulate only active lanes.
    """
    n_sets, n_ways = state.tags.shape
    set_ix = jnp.where(n_sets > 1, vpn % n_sets, 0).astype(jnp.int32)
    t = state.tags[set_ix]                       # (N, ways)
    a = state.asids[set_ix]
    match = (t == vpn[:, None]) & (a == asid[:, None])
    hit = match.any(axis=1) & active
    way = jnp.argmax(match, axis=1)

    # LRU touch for hits only: non-hit lanes are routed out of bounds and
    # dropped, so they can never scatter a stale value over a hit's touch
    touch_set = jnp.where(hit, set_ix, n_sets)
    lru = state.lru.at[touch_set, way].set(time, mode="drop")
    hits = state.hits + hit.sum(dtype=jnp.int32)
    misses = state.misses + (active & ~hit).sum(dtype=jnp.int32)
    return state._replace(lru=lru, hits=hits, misses=misses), hit


def fill(state: TLBState, vpn, asid, do_fill, time) -> TLBState:
    """Batched fill with LRU victim selection. do_fill: (N,) bool.

    One fill per set per call (first lane wins) — models fill-port limits.
    """
    n_sets, n_ways = state.tags.shape
    set_ix = jnp.where(n_sets > 1, vpn % n_sets, 0).astype(jnp.int32)

    # first-wins per set: lane i is masked out if an earlier lane fills the
    # same set
    order = jnp.arange(vpn.shape[0])
    same_earlier = (set_ix[None, :] == set_ix[:, None]) & \
        (order[None, :] < order[:, None]) & do_fill[None, :]
    do_fill = do_fill & ~same_earlier.any(axis=1)

    victim = jnp.argmin(state.lru[set_ix], axis=1)       # (N,)
    # masked lanes are routed out of bounds and dropped — a plain masked
    # scatter would write the stale old value back and could clobber the
    # winning lane's fill on duplicate sets
    fill_set = jnp.where(do_fill, set_ix, n_sets)
    tags = state.tags.at[fill_set, victim].set(vpn, mode="drop")
    asids = state.asids.at[fill_set, victim].set(asid, mode="drop")
    lru = state.lru.at[fill_set, victim].set(time, mode="drop")
    return state._replace(tags=tags, asids=asids, lru=lru)


def init_bank(n_banks: int, n_entries: int, n_ways: int) -> TLBState:
    """A bank of identical TLBs: one TLBState with leading axis (n_banks,)."""
    single = init(n_entries, n_ways)
    return jax.tree_util.tree_map(
        lambda x: jnp.broadcast_to(x[None], (n_banks,) + x.shape), single)


def probe_bank(state: TLBState, vpn, asid, active, time
               ) -> Tuple[TLBState, jax.Array]:
    """Probe a bank of TLBs, one request per bank. vpn/asid/active: (B,).

    Direct (B, sets, ways) indexing — bit-for-bit equal to vmapping the
    general N-lane `probe` at N=1, without paying its per-lane dedup and
    set-gather machinery (this is the simulator's per-cycle L1 path).
    """
    B, n_sets, n_ways = state.tags.shape
    set_ix = (vpn % n_sets if n_sets > 1
              else jnp.zeros_like(vpn)).astype(jnp.int32)
    b = jnp.arange(B)
    t = state.tags[b, set_ix]                    # (B, ways)
    a = state.asids[b, set_ix]
    match = (t == vpn[:, None]) & (a == asid[:, None])
    hit = match.any(axis=1) & active
    way = jnp.argmax(match, axis=1)
    touch_set = jnp.where(hit, set_ix, n_sets)   # miss lanes dropped
    lru = state.lru.at[b, touch_set, way].set(time, mode="drop")
    hits = state.hits + hit.astype(jnp.int32)
    misses = state.misses + (active & ~hit).astype(jnp.int32)
    return state._replace(lru=lru, hits=hits, misses=misses), hit


def fill_bank(state: TLBState, vpn, asid, do_fill, time) -> TLBState:
    """Fill a bank of TLBs, one request per bank. vpn/asid/do_fill: (B,).

    Direct indexing (see `probe_bank`); one request per bank means the
    per-set fill port is trivially satisfied. Masked lanes are routed out
    of bounds and dropped (no stale write-back, same as `fill`).
    """
    B, n_sets, n_ways = state.tags.shape
    set_ix = (vpn % n_sets if n_sets > 1
              else jnp.zeros_like(vpn)).astype(jnp.int32)
    b = jnp.arange(B)
    victim = jnp.argmin(state.lru[b, set_ix], axis=1)    # (B,)
    fill_set = jnp.where(do_fill, set_ix, n_sets)
    tags = state.tags.at[b, fill_set, victim].set(vpn, mode="drop")
    asids = state.asids.at[b, fill_set, victim].set(asid, mode="drop")
    lru = state.lru.at[b, fill_set, victim].set(time, mode="drop")
    return state._replace(tags=tags, asids=asids, lru=lru)


# names the round in the HLO metadata, nested under the calling memsys
# stage's scope; see `sim.memsys.step`
@jax.named_scope("mem.fused_tlb")
def access_fused(state: TLBState, line, active, may_fill, time,
                 n_waves: int = 1) -> Tuple[TLBState, jax.Array, jax.Array]:
    """One-call probe+fill for a whole cycle's sub-accesses ("waves").

    The simulator's shared L2 data cache used to be accessed by 8 dependent
    probe/fill/DRAM rounds per cycle (4 page-walk levels + 4 divergent data
    lines). This round services all of them in one batch: the lanes are
    `n_waves` contiguous equal groups ("waves", the old rounds in order),
    and the cross-wave semantics that matter are kept:

      * fill port: one fill per set per WAVE — the first fill candidate
        (active & miss & may_fill) of a set within a wave wins, matching
        `fill`'s first-wins port model per round;
      * duplicate suppression: a lane whose line was already a fill
        candidate in an earlier wave of the same flat position's group
        (e.g. the same core's earlier sub-access) does not fill again;
      * forwarding: fills are applied before the final hit resolution, so
        a lane whose line was filled this cycle — by another wave, or by
        the lane that beat it to its own wave's port (MSHR-merge-like) —
        observes the fill and hits instead of going to DRAM;
      * victims chain like sequential LRU: the k-th winning wave in a set
        takes the k-th least-recently-used way (stable (lru, way) order).

    Everything is O(N·ways²) gathers/scatters and small per-wave blocks —
    deliberately NO (N, N) lane matrices and no sort: on XLA CPU those
    dominated the entire cycle (argsort of the LRU rows alone cost more
    than the eight sequential rounds it replaced).

    Known deviations from running the waves sequentially: victim choice
    uses start-of-cycle LRU (a way probe-hit this cycle can be evicted by
    a same-cycle fill of its set), forwarding is resolved from the final
    filled state (a later wave's fill can forward to an earlier wave when
    the earlier lane was fill-blocked, e.g. bypassed), and duplicate
    fills are suppressed per flat position group (same core), not
    globally — cross-core same-line duplicate fills in different waves
    leave a transient duplicate tag (hits still resolve to the first
    way). A set also accepts at most n_ways fills per cycle (relevant
    only when n_waves > n_ways): overflow winners go to DRAM unfilled.

    The cache is tag-only: both callers (the line-addressed L2$ and the
    PWC) use tags that are already unique, so `state.asids` passes through
    untouched. line/active/may_fill: (N,) with N divisible by n_waves.
    Returns (state', hit (N,) bool, filled (N,) bool).
    """
    n_sets, n_ways = state.tags.shape
    N = line.shape[0]
    W = n_waves
    C = N // W
    set_ix = (line % n_sets if n_sets > 1
              else jnp.zeros_like(line)).astype(jnp.int32)
    rows_t = state.tags[set_ix]                  # (N, ways)
    match = rows_t == line[:, None]
    pre_hit = match.any(axis=1) & active
    way = jnp.argmax(match, axis=1)

    # ---- fill candidates --------------------------------------------------
    cand = active & ~pre_hit & may_fill
    if W > 1:
        # duplicate suppression per flat position (core): an earlier-wave
        # candidate with the same line makes later waves forward, not fill
        lines_wc = line.reshape(W, C)
        cand_wc = cand.reshape(W, C)
        tri_w = jnp.arange(W)[:, None, None] < jnp.arange(W)[None, :, None]
        dup = ((lines_wc[:, None, :] == lines_wc[None, :, :])
               & tri_w & cand_wc[:, None, :]).any(0).reshape(N)
        cand = cand & ~dup

    # ---- per-(set, wave) fill port via a scratch table --------------------
    # first candidate per (set, wave) wins; the occupied slots also give
    # every lane its same-set earlier-wave winner count (the LRU rank)
    wave = jnp.repeat(jnp.arange(W, dtype=jnp.int32), C)
    order = jnp.arange(N, dtype=jnp.int32)
    key = set_ix * W + wave
    scratch = jnp.full((n_sets * W,), jnp.int32(N), jnp.int32)
    scratch = scratch.at[jnp.where(cand, key, n_sets * W)].min(
        order, mode="drop")
    winner = cand & (scratch[key] == order)
    filled_sw = (scratch.reshape(n_sets, W) < N)[set_ix]        # (N, W)
    earlier_w = jnp.arange(W)[None, :] < wave[:, None]          # (N, W)
    rank = (filled_sw & earlier_w).sum(1)
    # a set holds at most n_ways fills per cycle: with more winning waves
    # than ways (only possible when n_waves > n_ways) the overflow lanes
    # lose their fill (straight to DRAM) instead of silently colliding on
    # the last victim way
    winner = winner & (rank < n_ways)

    # ---- victim = rank-th least-recently-used way -------------------------
    # pairwise (N, ways, ways) stable rank; XLA CPU sort is far slower
    lru_rows = state.lru[set_ix]                 # (N, ways)
    widx = jnp.arange(n_ways)
    lru_less = (lru_rows[:, None, :] < lru_rows[:, :, None]) | \
        ((lru_rows[:, None, :] == lru_rows[:, :, None])
         & (widx[None, None, :] < widx[None, :, None]))
    way_rank = lru_less.sum(-1)                  # (N, ways)
    victim = jnp.argmax(way_rank == jnp.minimum(rank, n_ways - 1)[:, None],
                        axis=1)

    # ---- one merged update pass per plane ---------------------------------
    # pre-hit lanes touch their way, winners fill their victim — both
    # write tag=line (a pre-hit lane's matched tag IS its line) and
    # lru=time, so each plane is ONE flat scatter; other lanes are routed
    # out of bounds and dropped
    flat = jnp.where(pre_hit, set_ix * n_ways + way,
                     jnp.where(winner, set_ix * n_ways + victim,
                               n_sets * n_ways))
    shape = state.tags.shape
    tags = state.tags.reshape(-1).at[flat].set(line, mode="drop").reshape(
        shape)
    lru = state.lru.reshape(-1).at[flat].set(time, mode="drop").reshape(shape)

    # ---- final hit resolution (forwarding falls out of the fills) ---------
    post = tags[set_ix] == line[:, None]
    hit = pre_hit | (active & ~winner & post.any(axis=1))
    hits = state.hits + hit.sum(dtype=jnp.int32)
    misses = state.misses + (active & ~hit).sum(dtype=jnp.int32)
    return (state._replace(tags=tags, lru=lru, hits=hits, misses=misses),
            hit, winner)


def flush_asid(state: TLBState, asid: int) -> TLBState:
    """TLB shootdown for one address space (paper §5.1)."""
    kill = state.asids == asid
    return state._replace(
        tags=jnp.where(kill, -1, state.tags),
        asids=jnp.where(kill, -1, state.asids))


def occupancy_by_asid(state: TLBState, n_asids: int) -> jax.Array:
    """(n_asids,) live-entry counts — used by fairness diagnostics.

    One-hot sum over every entry axis; invalid entries (asid -1) one-hot
    to all-zeros, so no explicit valid mask interplay is needed beyond
    the tag check. Also works on banked states (extra leading axes).
    """
    valid = state.tags >= 0
    oh = jax.nn.one_hot(state.asids, n_asids, dtype=jnp.int32)
    return (oh * valid[..., None]).sum(axis=tuple(range(oh.ndim - 1)))
