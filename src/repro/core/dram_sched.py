"""Address-Space-Aware DRAM Scheduler (paper §5.4).

Three queues per memory channel:

  Golden  — all translation (page-walk) requests; small FIFO; always first.
  Silver  — data requests of ONE application at a time; quota per Eq. (1):
              thres_i = thres_max * (Concurrent_i * WrpStalled_i)
                        / sum_j (Concurrent_j * WrpStalled_j)
  Normal  — everything else. FR-FCFS (row hits first) within Silver/Normal;
            Golden is FIFO (walk requests have poor row locality, fn. 5).

The batched model used by the simulator: each step a channel can service
``slots`` requests. Requests are ranked (queue priority, row-hit, age) and
the top ``slots`` complete with latencies derived from row hit/miss; the
per-bank open row and per-app silver accounting update functionally.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

T_ROW_HIT = 100      # cycles: CAS-only access (GPU clock domain)
T_ROW_MISS = 250     # cycles: precharge + activate + CAS
T_QUEUE_UNIT = 50    # serialization per queued-ahead request


class DramState(NamedTuple):
    open_row: jax.Array        # (channels, banks) int32 open row id
    silver_app: jax.Array      # () int32 — app currently owning Silver
    silver_left: jax.Array     # () int32 — remaining silver quota
    conc_walks: jax.Array      # (n_apps,) int32 'Concurrent_i' (6-bit, §5.4)
    warps_stalled: jax.Array   # (n_apps,) int32 'WrpStalled_i'
    queue_len: jax.Array       # (channels, 3) int32 backlog per class


def init(n_channels: int, n_banks: int, n_apps: int) -> DramState:
    return DramState(
        open_row=jnp.full((n_channels, n_banks), -1, jnp.int32),
        silver_app=jnp.zeros((), jnp.int32),
        silver_left=jnp.full((), 1, jnp.int32),
        conc_walks=jnp.zeros((n_apps,), jnp.int32),
        warps_stalled=jnp.zeros((n_apps,), jnp.int32),
        queue_len=jnp.zeros((n_channels, 3), jnp.int32),
    )


def silver_quota(state: DramState, thres_max=500) -> jax.Array:
    """(n_apps,) Eq. (1) thresholds, in exact integer arithmetic: a float
    quotient truncated to an int can land on either side of an integer
    depending on how the backend rounds division (the TPU's need not be
    exactly rounded). Products stay far below 2**31: Concurrent_i is at
    most the walk table's rows (64 in Table 1) and WrpStalled_i at most
    the warp count."""
    w = state.conc_walks * state.warps_stalled
    tot = jnp.maximum(w.sum(), 1)
    return jnp.maximum(thres_max * w // tot, 1)


def classify(state: DramState, app, is_tlb, mask_enabled):
    """queue class per request: 0 golden, 1 silver, 2 normal.

    `mask_enabled` may be a Python bool or a traced boolean scalar (the
    design-vectorized grid feeds it from `DesignParams`); disabled means
    one FR-FCFS queue, i.e. everything is class 2."""
    silver = (app == state.silver_app)
    cls = jnp.where(is_tlb, 0, jnp.where(silver, 1, 2)).astype(jnp.int32)
    return jnp.where(mask_enabled, cls, jnp.int32(2))


# names the DRAM round in the HLO metadata, nested under the calling
# memsys stage's scope; see `sim.memsys.step`
@jax.named_scope("mem.dram")
def access(state: DramState, channel, bank, row, app, is_tlb, active,
           mask_enabled, thres_max=500,
           fr_fcfs: bool = True, waves: int = 1) -> Tuple[DramState, jax.Array]:
    """Batched DRAM access model. All args (N,). Returns (state', latency (N,)).

    `mask_enabled` / `thres_max` may be Python values or traced scalars
    (see `classify`), so one compiled program serves every design point.

    Latency = service (row hit/miss) + queueing: number of requests this
    step that rank ahead of you on the same channel (priority-class first,
    then row-hit-first within class) × T_QUEUE_UNIT + standing backlog.

    `waves` partitions the batch into `waves` contiguous equal groups that
    are queued independently (in-batch ranking is block-diagonal): the
    simulator's fused memory path hands over all of a cycle's sub-access
    rounds in one call, and each round contends only with itself — exactly
    as when the rounds were separate sequential calls. `waves=1` is the
    plain fully-contending batch.

    The per-(channel, bank) and per-(channel, class) tables are read and
    written by one-hot compare-and-reduce over the lanes, with no gather
    or scatter: XLA:TPU applies the indices of a gather or scatter one
    after another, which cost a third of a cycle's device time. `channel`
    and `bank` must be in range.
    """
    n_channels, n_banks = state.open_row.shape
    cls = classify(state, app, is_tlb, mask_enabled)

    N = app.shape[0]
    C = N // waves
    cb = channel * n_banks + bank
    at_cb = cb[:, None] == jnp.arange(n_channels * n_banks)     # (N, C*B)
    row_hit = (at_cb & (state.open_row.reshape(-1) == row[:, None])).any(1)
    if waves > 1:
        # progressive open rows across waves, per flat position (the same
        # core's earlier sub-access opening the row it re-touches is the
        # dominant sequential row-hit source); cross-position openings and
        # closings between waves are not modeled
        row_w = row.reshape(waves, C)
        cb_w = cb.reshape(waves, C)
        tri_w = jnp.arange(waves)[:, None, None] \
            < jnp.arange(waves)[None, :, None]
        opened = ((row_w[:, None, :] == row_w[None, :, :])
                  & (cb_w[:, None, :] == cb_w[None, :, :])
                  & tri_w & active.reshape(waves, C)[:, None, :]) \
            .any(0).reshape(N)
        row_hit = row_hit | opened
    service = jnp.where(row_hit, T_ROW_HIT, T_ROW_MISS)

    # rank = priority ahead of me on my (channel, bank) within my wave —
    # banks service in parallel. (waves, C, C) blocks instead of (N, N).
    cb = cb.reshape(waves, C)
    key = cls * 2 + (~row_hit) if fr_fcfs else cls * 2
    key = key.reshape(waves, C)
    tri = jnp.arange(C)[None, :] < jnp.arange(C)[:, None]   # j before i
    ahead = (cb[:, None, :] == cb[:, :, None]) \
        & active.reshape(waves, C)[:, None, :] \
        & ((key[:, None, :] < key[:, :, None])
           | ((key[:, None, :] == key[:, :, None]) & tri[None]))
    n_ahead = ahead.sum(axis=2).reshape(N)

    # standing backlog + EWMA decay toward observed per-class pressure.
    # The EWMA chains once per wave (exactly the update the sequential
    # per-round calls applied 8x per cycle — a single update with the
    # summed counts would settle ~3x too high) and each wave reads the
    # backlog its round would have seen.
    quota = silver_quota(state, thres_max)
    n_apps = state.conc_walks.shape[0]
    cq = ((channel * 3 + cls)[:, None] == jnp.arange(n_channels * 3)) \
        .reshape(waves, C, n_channels * 3)
    counts = (cq & active.reshape(waves, C)[:, :, None]).sum(
        1, dtype=jnp.int32).reshape(waves, n_channels, 3)
    qs = []
    queue_len = state.queue_len
    for k in range(waves):
        qs.append(queue_len)
        queue_len = (queue_len * 3 + counts[k]) // 4
    backlog = jnp.where(cq, jnp.stack(qs).reshape(waves, 1, -1), 0) \
        .sum(2).reshape(N)
    served_w = (active & (cls == 1)).reshape(waves, C) \
        .sum(1, dtype=jnp.int32)

    latency = service + (n_ahead + backlog) * T_QUEUE_UNIT
    latency = jnp.where(active, latency, 0)

    # ---- state updates ----
    # open rows: the last active lane per (channel, bank) wins, by a dense
    # max over lanes; a (channel, bank) no active lane touched keeps its
    # row, so a trailing inactive lane changes nothing
    lane = jnp.arange(N, dtype=jnp.int32)
    last = jnp.where(active[:, None] & at_cb, lane[:, None], -1).max(0)
    won_row = jnp.where(lane[:, None] == last, row[:, None], 0).sum(0)
    new_open = jnp.where(last >= 0, won_row, state.open_row.reshape(-1)) \
        .reshape(n_channels, n_banks)

    # silver rotation: consume quota per wave (at most one rotation per
    # wave, like the sequential per-round calls); classification keeps the
    # cycle-start silver app — mid-cycle rotations reclassify nothing
    silver_app, silver_left = state.silver_app, state.silver_left
    for k in range(served_w.shape[0]):
        left = silver_left - served_w[k]
        next_app = (silver_app + 1) % n_apps
        rotate = left <= 0
        silver_app = jnp.where(rotate, next_app, silver_app)
        silver_left = jnp.where(rotate, quota[next_app], left)

    return state._replace(open_row=new_open, silver_app=silver_app,
                          silver_left=silver_left,
                          queue_len=queue_len), latency


def update_pressure(state: DramState, conc_walks, warps_stalled) -> DramState:
    """Refresh the Eq. (1) inputs (reset each epoch, §5.4)."""
    return state._replace(
        conc_walks=jnp.asarray(conc_walks, jnp.int32),
        warps_stalled=jnp.asarray(warps_stalled, jnp.int32))
