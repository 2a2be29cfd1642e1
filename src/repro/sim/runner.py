"""Simulation runner: N-app mixes, solo/pair wrappers, typed experiments.

Two API levels share one compiled core:

* Raw: `run_mix(design, benches)` co-runs len(benches) applications (None
  entries are idle partners) and returns a per-app stats dict.
  `run_pair` / `run_solo` are thin 2-app wrappers kept for the paper's
  pair-based experiments; `run_batch` vmaps many same-size mixes through
  one compile. `design` is a registered name or a
  `repro.core.design.Design` (including user-registered or ad-hoc
  compositions).

* Typed: `Experiment(design, mixes, cycles).run()` returns an
  `ExperimentResult` of `MixResult`/`AppStats` objects with the derived
  metrics (weighted speedup, unfairness, per-app hit rates) as
  methods/properties; `sweep(designs, mixes)` drives many designs.

Compilation is keyed on the design's STATIC SIGNATURE, not the design:
a design's dynamic knobs travel as a traced `DesignParams` plane (see
`repro.core.design`), so every design in a signature group shares one
executable, and `run_grid` / `sweep` stack (DesignParams, workload)
rows along a vmapped grid axis — one compile and ONE device execution
per (signature, n_apps) for a whole design x mix grid. The grid path
is bit-for-bit identical to running the designs one by one (pinned by
tests against the float-hex goldens).

Profiler spans (`jax.profiler.TraceAnnotation`, recorded only while a
`jax.profiler` trace is on, on the device trace's clock): the entry points
`runner.run_mix`, `runner.run_grid`, `runner.predict_mixes`,
`runner.sweep` and `runner.run_trace` each span their call, and inside
them every device call is split into host phases at chunk (or segment)
level, never per row:

* `runner.launch` -- building the workload matrices and stacking the
  (DesignParams, workload) rows with numpy on the host, padding them and
  placing them on the device (on the row sharding) in one transfer, and
  enqueueing the program; in `run_trace`, a segment's membership matrix,
  change mask and slice of the fault operands, and its enqueue;
* `runner.fetch` -- the `jax.device_get` of the leaves of the final state
  that `_stats` reads, or of the whole state under audit (it waits for
  the device);
* `runner.unpack` -- one `_stats_rows` pass over all of a chunk's rows,
  each row's dict from `_stats`, and the assembly of predictions or
  results from them.

`run_trace` also wraps each segment boundary in `runner.boundary`:
segment k's fetch and unpack, then segment k+1's launch -- the host work
the device waits on between two segments (the span opens when the host
starts waiting for segment k).
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Dict, List, Mapping, NamedTuple, Optional, Sequence, \
    Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.design import (Design, as_design, canonical_design,
                               design_params, host_design_params,
                               static_signature)
from repro.sim import faults as faults_mod
from repro.sim.config import SimConfig
from repro.sim.memsys import (SimState, apply_membership_change, init_state,
                              step)
from repro.sim.workloads import app_matrix

jax.config.update("jax_enable_x64", False)

_span = jax.profiler.TraceAnnotation

DesignLike = Union[str, Design]

# incremented every time a simulator program is traced for compilation
# (once per jit/vmap wrapper; re-executions hit the cache and do not
# bump it) — tests assert "one trace per signature group" against this
TRACE_COUNT = 0

# bytes every `runner.fetch` has copied to the host — tests read which
# fetch ran (the leaves `_stats` reads, or the whole state under audit)
FETCHED_BYTES = 0


def _canonical(cfg: SimConfig) -> SimConfig:
    """Replace the embedded design by its signature group's canonical
    representative: the compile-cache key for everything below. The
    fault plan is stripped too — fault operands are shape-stable data
    (`sim.faults`), so every chaos plan (and no plan) shares the one
    compiled trace of its signature group."""
    return dataclasses.replace(
        cfg, design=canonical_design(static_signature(cfg.design)),
        fault_plan=None)


def _run_fn(cfg: SimConfig):
    """The raw (DesignParams, params_mat) -> final-state scan.

    `cfg` must be canonical — the stages read only static-signature
    fields from it; every dynamic knob comes from the traced `dp`."""
    def run(dp, params_mat):
        global TRACE_COUNT
        TRACE_COUNT += 1              # runs at trace time only
        st = init_state(cfg, dp)

        def body(s, _):
            return step(cfg, dp, params_mat, s), None

        final, _ = jax.lax.scan(body, st, None, length=cfg.sim_cycles)
        return final

    return run


@functools.lru_cache(maxsize=64)
def _compiled_sig_run(ccfg: SimConfig):
    """One compiled (dp, pm) executable per (signature, SimConfig)."""
    return jax.jit(_run_fn(ccfg))


@functools.lru_cache(maxsize=64)
def _compiled_sig_batch_run(ccfg: SimConfig):
    """One design, many mixes: vmap over the workload axis only."""
    return jax.jit(jax.vmap(_run_fn(ccfg), in_axes=(None, 0)))


@functools.lru_cache(maxsize=64)
def _compiled_grid_run(ccfg: SimConfig):
    """Design x mix grid: vmap over stacked (DesignParams, params_mat)
    rows — one execution services every design of a signature group."""
    return jax.jit(jax.vmap(_run_fn(ccfg), in_axes=(0, 0)))


@functools.lru_cache(maxsize=64)
def _compiled_seg_run(ccfg: SimConfig):
    """One compiled SEGMENT executable per (signature, n_apps,
    seg_cycles): membership-change teardown + boundary faults + a
    seg_cycles scan, carrying `SimState` in and out.

    Everything that varies across a trace — the segment's workload rows,
    the change mask, the fault operands, K itself — is data, so a whole
    churn schedule (and every schedule of the same shape) replays through
    this one trace. With an all-False change mask and empty fault
    operands the boundary ops are bitwise identity, which is what makes
    constant-membership segmented runs float-hex equal to the monolithic
    scan."""
    def seg(dp, params_mat, state, change, fops: faults_mod.FaultOps):
        global TRACE_COUNT
        TRACE_COUNT += 1              # runs at trace time only
        st = apply_membership_change(ccfg, dp, state, change | fops.kill)
        st = faults_mod.apply_state_faults(ccfg, st, fops)

        def body(s, _):
            return step(ccfg, dp, params_mat, s), None

        final, _ = jax.lax.scan(body, st, None, length=ccfg.sim_cycles)
        return final

    return jax.jit(seg)


@functools.lru_cache(maxsize=128)
def _compiled_run(cfg: SimConfig):
    """Back-compat pm-only callable for one design; shares the signature
    group's executable (distinct designs, one compile)."""
    return functools.partial(_compiled_sig_run(_canonical(cfg)),
                             design_params(cfg.design))


@functools.lru_cache(maxsize=128)
def _compiled_batch_run(cfg: SimConfig):
    """vmapped over a leading batch of workload parameter matrices — one
    executable serves every mix/solo under the design's signature."""
    return functools.partial(_compiled_sig_batch_run(_canonical(cfg)),
                             design_params(cfg.design))


class ZeroCycleError(RuntimeError):
    """A stats request for a run that simulated no cycles (IPC undefined)."""


class NonFiniteStatsError(RuntimeError):
    """Per-app counters came back NaN/inf — corrupt state, not a metric."""


def _audit_enabled(audit: Optional[bool]) -> bool:
    """None defers to env REPRO_AUDIT; True/False force it on/off."""
    if audit is not None:
        return audit
    return os.environ.get("REPRO_AUDIT", "") in ("1", "true", "yes")


def _stats_leaves(st: SimState) -> SimState:
    """`st` with only the leaves `_stats` reads (`t`, `instr`, the
    `stats` planes and `tokens.tokens`), None in place of the rest."""
    return SimState(
        t=st.t, stall_until=None, instr=st.instr, pos=None, trans=None,
        data=None, stats=st.stats, asid_of_app=None,
        tokens=st.tokens._replace(**{f: None for f in st.tokens._fields
                                     if f != "tokens"}))


def _fetch(st: SimState, audit: bool) -> SimState:
    """Copy a final state to the host: the leaves `_stats` reads, or the
    whole state when auditing (`sim.audit.check_state` reads it all)."""
    global FETCHED_BYTES
    host = jax.device_get(st if audit else _stats_leaves(st))
    FETCHED_BYTES += sum(np.asarray(x).nbytes
                         for x in jax.tree_util.tree_leaves(host))
    return host


class _StatsRow(NamedTuple):
    """Row `r` of a stack of final states whose statistics `_stats_rows`
    has already computed (and audited)."""
    stats: Dict[str, np.ndarray]
    r: int


def _stats_rows(cfg: SimConfig, st: SimState,
                audit: bool) -> Dict[str, np.ndarray]:
    """Per-app statistics of a host stack of final states, in one pass
    over its leading row axis: every value gains the row axis ("cycles"
    too). Audits every row first when `audit` holds. Raises for the
    first row that simulated no cycles or has non-finite IPC."""
    if audit:
        from repro.sim.audit import check_state
        for r in range(len(st.t)):
            check_state(cfg, jax.tree_util.tree_map(lambda x, r=r: x[r], st))
    na = cfg.n_apps
    t = np.asarray(st.t, np.float64)
    instr = np.asarray(st.instr)
    R = len(t)
    if not np.all(t > 0):
        r = int(np.argmin(t > 0))
        raise ZeroCycleError(
            f"cannot derive per-app IPC from a {t[r]:.0f}-cycle run "
            f"(design={cfg.design.name!r}): IPC = instructions / cycles "
            "would be NaN/inf and silently poison weighted_speedup / "
            "unfairness downstream — run with cycles >= 1")
    # one bincount over (row, app) bins: each bin sums its warps in warp
    # order, exactly as a per-row bincount does
    warp_app = np.repeat(np.asarray(cfg.app_of_core), cfg.warps_per_core)
    bins = (np.arange(R)[:, None] * na + warp_app).ravel()
    ipc = np.bincount(bins, weights=instr.ravel(),
                      minlength=R * na).reshape(R, na) / t[:, None]
    finite = np.isfinite(ipc).all(axis=1)
    if not finite.all():
        r = int(np.argmin(finite))
        raise NonFiniteStatsError(
            f"non-finite per-app IPC {ipc[r]} after {t[r]:.0f} cycles "
            f"(design={cfg.design.name!r}): the retired-instruction "
            "counters are corrupt (overflow or injected fault); refusing "
            "to propagate NaN into weighted_speedup / unfairness")
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
        # L2 data-cache hit rate for TLB requests (Table 5). np.maximum
        # (not builtin max) so these survive the counters going per-app.
        "l2c_tlb_hit_rate": (g(s.s_l2c_tlb_hit)
                             / np.maximum(g(s.s_l2c_tlb_probe), 1)),
        "l2c_data_hit_rate": (g(s.s_l2c_data_hit)
                              / np.maximum(g(s.s_l2c_data_probe), 1)),
        "tokens": np.asarray(st.tokens.tokens),
        "cycles": t,
    }


def _stats(cfg: SimConfig, st,
           audit: Optional[bool] = None) -> Dict[str, np.ndarray]:
    """Per-app statistics of one final state, the dict of one answer.

    `st` is a host tree (`_fetch` copies one; device leaves are read one
    at a time), which is `_stats_rows` on a row axis of length one, or a
    `_StatsRow` of a stack whose rows `_stats_rows` did at once. Every
    answer the runner returns is made here, once."""
    if not isinstance(st, _StatsRow):
        one = jax.tree_util.tree_map(lambda x: np.asarray(x)[None], st)
        st = _StatsRow(_stats_rows(cfg, one, _audit_enabled(audit)), 0)
    return {k: float(v[st.r]) if k == "cycles" else v[st.r]
            for k, v in st.stats.items()}


def _row_stats(cfg: SimConfig, st: SimState, audit: bool) -> List[Dict]:
    """`_stats` of every row of a host stack of final states, from one
    `_stats_rows` pass over the stack."""
    rows = _stats_rows(cfg, st, audit)
    return [_stats(cfg, _StatsRow(rows, r))
            for r in range(len(rows["cycles"]))]


def _mix_matrix(benches: Sequence[Optional[str]]) -> np.ndarray:
    """(n_apps, N_FIELDS) parameter matrix; None entries are idle apps."""
    return app_matrix(list(benches))


def _row_sharding(devices: int):
    """NamedSharding splitting a leading "rows" axis over `devices`.

    Grid rows are fully independent under vmap (no cross-row ops, so no
    collectives): placing the stacked (DesignParams, pm) rows on a 1-D
    device mesh makes XLA partition the whole scanned program row-wise —
    same math per row, so results stay bit-for-bit equal to the
    single-device path. More devices than are visible is an error; spawn
    a subprocess with `XLA_FLAGS=--xla_force_host_platform_device_count=N`
    to split a CPU host (see tests/test_sharded_grid.py).
    """
    devs = jax.devices()
    if devices > len(devs):
        raise ValueError(
            f"devices={devices} but only {len(devs)} JAX devices visible; "
            "on CPU, relaunch with XLA_FLAGS="
            f"--xla_force_host_platform_device_count={devices}")
    mesh = jax.sharding.Mesh(np.asarray(devs[:devices]), ("rows",))
    return jax.sharding.NamedSharding(mesh,
                                      jax.sharding.PartitionSpec("rows"))


def _pad_rows(tree, multiple: int):
    """Pad every (host) leaf's leading axis up to a multiple of `multiple`
    by repeating the first rows; returns (padded_tree, real_row_count).

    Repeated leading rows keep every row a valid simulation (no NaN/zero
    design surprises); callers slice results back to the real count.
    """
    rows = jax.tree_util.tree_leaves(tree)[0].shape[0]
    pad = (-rows) % multiple
    if pad:
        tree = jax.tree_util.tree_map(
            lambda x: np.concatenate([x, x[:pad]], axis=0), tree)
    return tree, rows


def _grid_rows(designs: Sequence[Design], pms: np.ndarray):
    """One chunk's stacked (DesignParams, params_mat) rows, built with
    numpy on the host. Rows are design-major: row g*M + m is
    (designs[g], mix m) for the M mixes of `pms`."""
    M = len(pms)
    dp = jax.tree_util.tree_map(
        lambda *leaves: np.repeat(np.stack(leaves), M, axis=0),
        *[host_design_params(d) for d in designs])
    return dp, np.tile(pms, (len(designs), 1, 1))


@functools.partial(jax.profiler.annotate_function, name="runner.run_mix")
def run_mix(design: DesignLike, benches: Sequence[Optional[str]],
            cycles: int = 60_000) -> Dict:
    """Co-run N apps under a design; returns per-app stats.

    `benches` may contain None for idle partners (the §6 `IPC_alone`
    emulation keeps the core split of the shared run but removes memory
    contention from the partner slots).
    """
    aud = _audit_enabled(None)
    with _span("runner.launch"):
        cfg = SimConfig(n_apps=len(benches), sim_cycles=cycles,
                        design=as_design(design))
        pm = jnp.asarray(_mix_matrix(benches))
        st = _compiled_run(cfg)(pm)
    with _span("runner.fetch"):
        st = _fetch(st, aud)
    with _span("runner.unpack"):
        return _stats(cfg, st, aud)


@dataclasses.dataclass(frozen=True, eq=False)
class TraceResult:
    """A segmented churn run: final stats + per-boundary snapshots.

    `stats` is the run_mix-shaped dict of the FINAL state — for a
    constant-membership schedule it is float-hex identical to
    `run_mix(design, schedule[0], cycles=K * seg_cycles)`. `segments[k]`
    is the cumulative stats snapshot after segment k. Counters of a slot
    reset when its membership changes (the arriving app starts cold), so
    a churned slot's numbers read "since its last arrival"; `ipc` always
    divides by the TOTAL elapsed cycles, and the shared L2$ hit rates
    (`l2c_tlb_hit_rate`, `l2c_data_hit_rate`) are not per slot and count
    from cycle 0.
    """
    design: Design
    schedule: Tuple[Tuple[Optional[str], ...], ...]
    seg_cycles: int
    stats: Mapping[str, np.ndarray]
    segments: Tuple[Mapping[str, np.ndarray], ...]
    final_state: Optional[SimState] = None

    def __getitem__(self, key: str):
        return self.stats[key]


@functools.partial(jax.profiler.annotate_function, name="runner.run_trace")
def run_trace(design: DesignLike,
              schedule: Sequence[Tuple[Optional[str], ...]],
              seg_cycles: int = 2_000,
              fault_plan: Optional[faults_mod.FaultPlan] = None,
              audit: Optional[bool] = None,
              collect_segments: bool = True,
              return_state: bool = False) -> TraceResult:
    """Run a time-varying mix: one membership tuple per segment.

    `schedule[k]` is the bench tuple live during segment k (None entries
    are idle slots); all tuples must share one length (the slot count is
    an array shape). Between segments, every slot whose entry CHANGED
    gets full teardown + cold-start semantics — ASID shootdown across
    the TLB hierarchy, walk cancellation, token/DRAM-pressure release,
    fresh ASID generation, cold warps and counters
    (`memsys.apply_membership_change`) — and the boundary's faults from
    `fault_plan` (plus the fault plan's kills) are applied
    (`sim.faults`). Membership, `AppParams` rows, change masks, and
    fault operands are all DATA: the whole trace replays through one
    compiled segment executable per (signature, n_apps, seg_cycles) —
    K, the schedule, and the plan never retrace.

    `audit`: None defers to env `REPRO_AUDIT` (the state auditor runs on
    every collected snapshot, `sim.audit`); True/False force it.
    `collect_segments=False` skips intermediate snapshots (one
    device->host transfer instead of K). `return_state` attaches the
    final device `SimState` for state-level inspection in tests.
    """
    schedule = [tuple(s) for s in schedule]
    if not schedule:
        raise ValueError("schedule needs at least one segment")
    sizes = {len(s) for s in schedule}
    if len(sizes) != 1:
        raise ValueError(
            f"all schedule segments must have the same slot count "
            f"(it is an array shape), got {sizes}")
    if seg_cycles < 1:
        raise ValueError(f"seg_cycles must be >= 1, got {seg_cycles}")
    n = sizes.pop()
    K = len(schedule)
    cfg = SimConfig(n_apps=n, sim_cycles=seg_cycles,
                    design=as_design(design), fault_plan=fault_plan)
    ccfg = _canonical(cfg)
    dp = design_params(cfg.design)
    ops = (faults_mod.plan_operands(fault_plan, cfg, K) if fault_plan
           else faults_mod.empty_operands(cfg, K))
    seg_run = _compiled_seg_run(ccfg)
    aud = _audit_enabled(audit)

    snaps: List[Dict] = []

    def launch(k, state):
        with _span("runner.launch"):
            pm = jnp.asarray(_mix_matrix(schedule[k]))
            # segment 0's membership is the cold init itself: no teardown
            change = np.zeros(n, bool) if k == 0 else np.array(
                [a != b for a, b in zip(schedule[k - 1], schedule[k])])
            fops = jax.tree_util.tree_map(lambda x: x[k], ops)
            return seg_run(dp, pm, state, jnp.asarray(change), fops)

    def collect(k, state):
        if collect_segments or k == K - 1:
            with _span("runner.fetch"):
                host = _fetch(state, aud)
            with _span("runner.unpack"):
                snaps.append(_stats(cfg, host, aud))

    state = launch(0, init_state(ccfg, dp))
    for k in range(1, K):
        with _span("runner.boundary"):
            collect(k - 1, state)
            state = launch(k, state)
    collect(K - 1, state)
    return TraceResult(
        design=cfg.design, schedule=tuple(schedule), seg_cycles=seg_cycles,
        stats=snaps[-1], segments=tuple(snaps) if collect_segments else (),
        final_state=state if return_state else None)


def run_batch(design: DesignLike,
              bench_mixes: Sequence[Tuple[Optional[str], ...]],
              cycles: int = 60_000) -> List[Dict]:
    """Run many same-size workload mixes at once (vmap). An entry may
    contain None for a solo run (idle partner)."""
    sizes = {len(m) for m in bench_mixes}
    if len(sizes) != 1:
        raise ValueError(f"all mixes must have the same size, got {sizes}")
    cfg = SimConfig(n_apps=sizes.pop(), sim_cycles=cycles,
                    design=as_design(design))
    pm = jnp.asarray(np.stack([_mix_matrix(m) for m in bench_mixes]))
    aud = _audit_enabled(None)
    # one bulk device->host transfer and one stats pass over every mix
    return _row_stats(cfg, _fetch(_compiled_batch_run(cfg)(pm), aud), aud)


@dataclasses.dataclass(frozen=True)
class FailureRecord:
    """A sweep cell (or whole signature-group chunk) that failed.

    Fail-soft sweeps return these IN PLACE of stats/results instead of
    aborting the remaining groups: one poisoned design point costs its
    own group, not the grid. The record carries everything needed to
    reproduce the failure standalone."""
    designs: Tuple[str, ...]      # design names sharing the failed call
    n_apps: int
    cycles: int
    error_type: str               # exception class name
    message: str
    stage: str                    # e.g. "grid-chunk", "experiment-batch"

    def __bool__(self) -> bool:   # a failed cell is falsy; stats are truthy
        return False

    def reraise(self) -> None:
        raise RuntimeError(
            f"[{self.stage}] designs={self.designs} n_apps={self.n_apps} "
            f"cycles={self.cycles}: {self.error_type}: {self.message}")


@functools.partial(jax.profiler.annotate_function, name="runner.run_grid")
def run_grid(designs: Sequence[DesignLike],
             bench_mixes: Sequence[Tuple[Optional[str], ...]],
             cycles: int = 60_000,
             max_rows: int = 64,
             devices: Optional[int] = None,
             fail_soft: bool = False
             ) -> List[List[Union[Dict, "FailureRecord"]]]:
    """Run the full designs x mixes cross product, one compile per
    static-signature group and as few device executions as `max_rows`
    allows.

    Designs are grouped by `static_signature`; each group's
    `DesignParams` are stacked design-major against a tiled copy of the
    mix matrices and vmapped through the group's shared executable.
    Groups whose full grid exceeds `max_rows` simulation rows are
    executed in whole-design chunks of EQUAL width — the largest
    divisor of the group size within the cap — so every chunk reuses
    the group's one compiled program (per-row results are independent
    under vmap, so chunking cannot change them). This bounds peak state
    memory; per-sim throughput is flat in the batch width anyway, so
    narrower chunks cost nothing but per-call dispatch.

    `devices=N` (> 1) shards each chunk's rows over the first N visible
    JAX devices on a 1-D mesh (`_row_sharding`), padding the row count
    up to a multiple of N with repeated rows (`_pad_rows`, sliced back
    off). Rows are independent, so sharded results are bit-for-bit
    identical to the single-device path (pinned by
    tests/test_sharded_grid.py); the per-call row cap scales to
    `max_rows * devices` so each device still sees at most `max_rows`.
    Returns `stats[d][m]` aligned with the inputs — bit-for-bit equal to
    `run_mix(designs[d], bench_mixes[m], cycles)`.

    `fail_soft=True` catches a failing chunk (trace/compile error,
    execution error, or corrupt stats) into a `FailureRecord` placed in
    every cell the chunk covered, and CONTINUES with the remaining
    chunks and signature groups — one poisoned design cannot abort the
    sweep. Default False preserves raise-on-first-error semantics.

    Each chunk's host work is array-at-a-time: its rows are stacked with
    numpy and placed in one transfer, only the leaves `_stats` reads are
    fetched, and one `_stats_rows` pass covers every row. Under env
    `REPRO_AUDIT` the whole state is fetched instead, and
    `sim.audit.check_state` runs on every real row.
    """
    ds = [as_design(d) for d in designs]
    sizes = {len(m) for m in bench_mixes}
    if len(sizes) != 1:
        raise ValueError(f"all mixes must have the same size, got {sizes}")
    if not ds:
        return []
    n = sizes.pop()
    M = len(bench_mixes)
    aud = _audit_enabled(None)
    with _span("runner.launch"):
        pms = np.stack([_mix_matrix(m) for m in bench_mixes])
    sharding = _row_sharding(devices) if devices and devices > 1 else None
    row_cap = max_rows * (devices if sharding is not None else 1)
    designs_per_call = max(row_cap // M, 1)

    out: List[List[Optional[Dict]]] = [[None] * M for _ in ds]
    groups: Dict[object, List[int]] = {}
    for i, d in enumerate(ds):
        groups.setdefault(static_signature(d), []).append(i)
    for sig, g_idxs in groups.items():
        ccfg = SimConfig(n_apps=n, sim_cycles=cycles,
                         design=canonical_design(sig))
        G = len(g_idxs)
        # equal-width chunks only: a ragged tail would be a second
        # compiled program for the group
        width = G if G <= designs_per_call else max(
            w for w in range(1, designs_per_call + 1) if G % w == 0)
        for lo in range(0, G, width):
            idxs = g_idxs[lo:lo + width]
            real = len(idxs) * M
            try:
                with _span("runner.launch"):
                    rows = _grid_rows([ds[i] for i in idxs], pms)
                    if sharding is not None:
                        rows, _ = _pad_rows(rows, devices)
                    # one host->device transfer of the chunk's rows
                    final = _compiled_grid_run(ccfg)(
                        *jax.device_put(rows, sharding))
                # one bulk device->host transfer of the chunk's final
                # state (padding rows ride along and are dropped here)
                with _span("runner.fetch"):
                    final = _fetch(final, aud)
                with _span("runner.unpack"):
                    final = jax.tree_util.tree_map(lambda x: x[:real], final)
                    for r, s in enumerate(_row_stats(ccfg, final, aud)):
                        out[idxs[r // M]][r % M] = s
            except Exception as e:  # noqa: BLE001 — fail-soft boundary
                if not fail_soft:
                    raise
                rec = FailureRecord(
                    designs=tuple(ds[i].name for i in idxs), n_apps=n,
                    cycles=cycles, error_type=type(e).__name__,
                    message=str(e), stage="grid-chunk")
                for di in idxs:
                    for m in range(M):
                        out[di][m] = rec
    return out


@dataclasses.dataclass(frozen=True)
class MixPrediction:
    """One candidate co-placement's predicted contention metrics.

    Produced by `predict_mixes` (the serving oracle's entry point into
    the simulator): per-app slowdown/speedup are §6 semantics — the
    solo baseline keeps the app's core share (idle partners) and
    removes memory contention, so `slowdown[i]` isolates what SHARING
    the memory system costs app i in this mix."""

    benches: Tuple[str, ...]
    weighted_speedup: float
    max_slowdown: float
    slowdown: Tuple[float, ...]   # aligned with benches
    ipc: Tuple[float, ...]
    solo_ipc: Tuple[float, ...]


@functools.partial(jax.profiler.annotate_function,
                   name="runner.predict_mixes")
def predict_mixes(design: DesignLike,
                  mixes: Sequence[Sequence[str]],
                  cycles: int = 2_000,
                  slots: Optional[int] = None,
                  pad_rows: int = 0,
                  fail_soft: bool = False,
                  solo_cache: Optional[Dict[str, float]] = None
                  ) -> List[Union[MixPrediction, FailureRecord]]:
    """Predict contention for candidate co-placement mixes in ONE
    `run_grid` call (the oracle-facing helper).

    Every mix (a tuple of bench names, no Nones) is padded with idle
    partners to a common `slots` count, so candidates of different
    co-run degrees batch into one (signature, n_apps) grid execution
    together with the IPC_alone solo-baseline rows their benches need.
    Slowdowns are therefore comparable across candidate sizes: each app
    holds the same 1/slots core share in its mix AND in its baseline,
    and the prediction isolates memory-system contention (§6).

    `pad_rows > 0` pads the ROW COUNT up to the next multiple by
    repeating the last row, keeping the vmapped grid shape stable
    across calls: a serving loop that predicts every decision epoch
    compiles exactly one program for the oracle's lifetime
    (`runner.TRACE_COUNT` pins this in tests/test_serving_oracle.py).

    `solo_cache` (mutated in place when given) carries solo IPCs across
    calls so previously-seen benches don't re-simulate their baselines.
    With `fail_soft=True` a failing chunk yields `FailureRecord`s in
    place of predictions (and poisons only the mixes that needed it).
    """
    mixes = [tuple(b for b in m if b is not None) for m in mixes]
    if not mixes:
        return []
    if any(not m for m in mixes):
        raise ValueError("every candidate mix needs at least one bench")
    n = max(len(m) for m in mixes)
    slots = n if slots is None else slots
    if n > slots:
        raise ValueError(f"a candidate mix has {n} apps > slots={slots}")
    solo_cache = {} if solo_cache is None else solo_cache
    need_solo = sorted({b for m in mixes for b in m} - set(solo_cache))
    rows = [m + (None,) * (slots - len(m)) for m in mixes]
    rows += [(b,) + (None,) * (slots - 1) for b in need_solo]
    if pad_rows > 0:
        target = -(-len(rows) // pad_rows) * pad_rows
        rows += [rows[-1]] * (target - len(rows))
    grid = run_grid([design], rows, cycles, fail_soft=fail_soft)[0]
    with _span("runner.unpack"):
        solo_fail: Dict[str, FailureRecord] = {}
        solos = grid[len(mixes):len(mixes) + len(need_solo)]
        for b, s in zip(need_solo, solos):
            if isinstance(s, FailureRecord):
                solo_fail[b] = s
            else:
                solo_cache[b] = float(s["ipc"][0])
        out: List[Union[MixPrediction, FailureRecord]] = []
        for m, s in zip(mixes, grid[:len(mixes)]):
            if isinstance(s, FailureRecord):
                out.append(s)
                continue
            bad = next((solo_fail[b] for b in m if b in solo_fail), None)
            if bad is not None:
                out.append(bad)
                continue
            solo = tuple(solo_cache[b] for b in m)
            ipc = tuple(float(s["ipc"][i]) for i in range(len(m)))
            slow = tuple(a / max(i, 1e-9) for a, i in zip(solo, ipc))
            out.append(MixPrediction(
                benches=m,
                weighted_speedup=float(sum(i / max(a, 1e-9)
                                           for i, a in zip(ipc, solo))),
                max_slowdown=float(max(slow)),
                slowdown=slow, ipc=ipc, solo_ipc=solo))
        return out


def run_pair(design: DesignLike, bench_a: str, bench_b: str,
             cycles: int = 60_000) -> Dict:
    """Co-run two apps under a design; returns per-app stats."""
    return run_mix(design, [bench_a, bench_b], cycles)


def run_solo(design: DesignLike, bench: str, cycles: int = 60_000) -> Dict:
    """IPC_alone: same core count as in the shared run (paper §6),
    exclusive memory system — emulated by pairing with an idle app."""
    return run_mix(design, [bench, None], cycles)


def weighted_speedup(mix_stats, *solos) -> float:
    """Sum of per-app IPC / IPC_alone over the mix (any N)."""
    return float(sum(mix_stats["ipc"][i] / max(s["ipc"][0], 1e-9)
                     for i, s in enumerate(solos)))


def max_slowdown(mix_stats, *solos) -> float:
    """Unfairness: worst per-app IPC_alone / IPC over the mix (any N)."""
    return float(max(s["ipc"][0] / max(mix_stats["ipc"][i], 1e-9)
                     for i, s in enumerate(solos)))


# ---------------------------------------------------------------------------
# typed results layer
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AppStats:
    """One application's slice of a mix run. `ipc_alone` is the §6
    IPC_alone baseline (same core share, idle partners) when the
    experiment computed solo baselines, else None."""

    bench: Optional[str]          # None = idle partner slot
    index: int                    # position in the mix
    ipc: float
    ipc_alone: Optional[float]
    l1_tlb_hit_rate: float
    l2_tlb_hit_rate: float        # shared L2 TLB (Table 3)
    bypass_hit_rate: float        # token bypass cache (Table 4)
    walk_lat: float               # mean page-walk latency (cycles)
    walks: float
    stalls_per_miss: float
    dram_tlb_lat: float           # mean DRAM latency, walk requests
    dram_data_lat: float          # mean DRAM latency, data requests
    tokens: int                   # final TLB-fill token count

    @property
    def speedup(self) -> float:
        """IPC / IPC_alone (this app's weighted-speedup contribution)."""
        if self.ipc_alone is None:
            raise ValueError("run the experiment with solo baselines")
        return self.ipc / max(self.ipc_alone, 1e-9)

    @property
    def slowdown(self) -> float:
        """IPC_alone / IPC (this app's unfairness contribution)."""
        if self.ipc_alone is None:
            raise ValueError("run the experiment with solo baselines")
        return self.ipc_alone / max(self.ipc, 1e-9)


@dataclasses.dataclass(frozen=True, eq=False)
class MixResult:
    """One mix under one design: per-app `AppStats` + mix-level metrics.
    The raw stats dict stays reachable via `.raw` / `res[key]`."""

    design: Design
    benches: Tuple[Optional[str], ...]
    cycles: int
    apps: Tuple[AppStats, ...]
    raw: Mapping[str, np.ndarray]

    def __getitem__(self, key: str):
        return self.raw[key]

    def app(self, bench: str) -> AppStats:
        """First AppStats running `bench` (mixes may repeat a bench)."""
        for a in self.apps:
            if a.bench == bench:
                return a
        raise KeyError(f"{bench!r} not in mix {self.benches}")

    @property
    def real_apps(self) -> Tuple[AppStats, ...]:
        """Apps excluding idle-partner (None) slots."""
        return tuple(a for a in self.apps if a.bench is not None)

    @property
    def l2c_tlb_hit_rate(self) -> float:
        """L2 data-cache hit rate for TLB (walk) requests (Table 5)."""
        return float(self.raw["l2c_tlb_hit_rate"])

    @property
    def l2c_data_hit_rate(self) -> float:
        return float(self.raw["l2c_data_hit_rate"])

    def weighted_speedup(self) -> float:
        """Sum of IPC / IPC_alone over the real apps (paper Eq. WS)."""
        return float(sum(a.speedup for a in self.real_apps))

    def unfairness(self) -> float:
        """Max per-app slowdown over the real apps (paper max slowdown)."""
        return float(max(a.slowdown for a in self.real_apps))

    max_slowdown = unfairness


@dataclasses.dataclass(frozen=True, eq=False)
class ExperimentResult:
    """All mixes of one `Experiment`, aligned with its mix list."""

    design: Design
    cycles: int
    results: Tuple[MixResult, ...]
    solo_ipc: Mapping[Tuple[str, int], float]  # (bench, n_apps) -> IPC_alone

    def __iter__(self):
        return iter(self.results)

    def __len__(self) -> int:
        return len(self.results)

    def __getitem__(self, i) -> MixResult:
        return self.results[i]

    def mean_weighted_speedup(self) -> float:
        return float(np.mean([r.weighted_speedup() for r in self.results]))

    def mean_unfairness(self) -> float:
        return float(np.mean([r.unfairness() for r in self.results]))


def _normalize_mixes(mixes) -> Tuple[Tuple[Optional[str], ...], ...]:
    """Normalize a mix list: bare bench strings become 1-app mixes."""
    if isinstance(mixes, str):
        raise TypeError(
            f"mixes must be a sequence of mixes, got the bare string "
            f"{mixes!r} — did you mean [({mixes!r},)]?")
    norm = tuple((m,) if isinstance(m, str) else tuple(m) for m in mixes)
    if not norm:
        raise ValueError("need at least one mix")
    return norm


class _NPlan(NamedTuple):
    """Per-n_apps slice of an experiment: which simulation rows to run
    (user mixes + IPC_alone solo mixes) and how to map them back."""
    items: Tuple[Tuple[int, Tuple[Optional[str], ...]], ...]  # (orig idx, mix)
    rows: Tuple[Tuple[Optional[str], ...], ...]   # mixes + solo_mixes
    n_mixes: int
    solo_shaped: frozenset                        # user mixes that ARE solos
    solo_mixes: Tuple[Tuple[Optional[str], ...], ...]


def _mix_plan(mixes, solo_baselines: bool) -> Dict[int, _NPlan]:
    """Group normalized mixes by n_apps and plan each group's simulation
    rows, deduplicating solo baselines against solo-shaped user mixes."""
    by_n: Dict[int, List[Tuple[int, Tuple[Optional[str], ...]]]] = {}
    for i, m in enumerate(mixes):
        by_n.setdefault(len(m), []).append((i, m))
    plans: Dict[int, _NPlan] = {}
    for n, items in sorted(by_n.items()):
        ms = [m for _, m in items]
        benches = sorted({b for m in ms for b in m
                          if b is not None}) if solo_baselines else []
        # a user mix that IS the canonical solo shape (bench + idle
        # partners) doubles as its own baseline — don't simulate twice
        solo_shaped = {m for m in ms if m[0] is not None and not any(m[1:])}
        solo_mixes = [(b,) + (None,) * (n - 1) for b in benches]
        solo_mixes = [sm for sm in solo_mixes if sm not in solo_shaped]
        plans[n] = _NPlan(items=tuple(items),
                          rows=tuple(ms) + tuple(solo_mixes),
                          n_mixes=len(ms),
                          solo_shaped=frozenset(solo_shaped),
                          solo_mixes=tuple(solo_mixes))
    return plans


def _mk_mix_result(design: Design, cycles: int, benches, s, solo_ipc,
                   n: int) -> MixResult:
    apps = tuple(
        AppStats(
            bench=b, index=i,
            ipc=float(s["ipc"][i]),
            ipc_alone=solo_ipc.get((b, n)),
            l1_tlb_hit_rate=float(s["l1_hit_rate"][i]),
            l2_tlb_hit_rate=float(s["l2_hit_rate"][i]),
            bypass_hit_rate=float(s["byp_hit_rate"][i]),
            walk_lat=float(s["walk_lat"][i]),
            walks=float(s["walks"][i]),
            stalls_per_miss=float(s["stalls_per_miss"][i]),
            dram_tlb_lat=float(s["dram_tlb_lat"][i]),
            dram_data_lat=float(s["dram_data_lat"][i]),
            tokens=int(s["tokens"][i]),
        ) for i, b in enumerate(benches))
    return MixResult(design=design, benches=tuple(benches),
                     cycles=cycles, apps=apps, raw=s)


def _assemble_result(design: Design, cycles: int, n_results: int,
                     plans: Dict[int, _NPlan],
                     stats_by_n: Dict[int, List[Dict]]) -> ExperimentResult:
    """Fold per-row stats back into an ExperimentResult (shared by the
    per-design `Experiment.run` and the grid-path `sweep`)."""
    results: List[Optional[MixResult]] = [None] * n_results
    solo_ipc: Dict[Tuple[str, int], float] = {}
    for n, plan in sorted(plans.items()):
        stats = stats_by_n[n]
        for m, s in zip(plan.rows[:plan.n_mixes], stats):
            if m in plan.solo_shaped:
                solo_ipc[(m[0], n)] = float(s["ipc"][0])
        for sm, s in zip(plan.solo_mixes, stats[plan.n_mixes:]):
            solo_ipc[(sm[0], n)] = float(s["ipc"][0])
        for (i, m), s in zip(plan.items, stats[:plan.n_mixes]):
            results[i] = _mk_mix_result(design, cycles, m, s, solo_ipc, n)
    return ExperimentResult(design=design, cycles=cycles,
                            results=tuple(results), solo_ipc=solo_ipc)


@dataclasses.dataclass(frozen=True)
class Experiment:
    """Typed façade over `run_batch`: a design × a list of mixes.

    `design` may be a registered name or a `Design`; `mixes` entries are
    bench tuples (a bare bench name means a 1-app run; None entries are
    idle partners). Mixes of different sizes are allowed — each
    (design, n_apps) group is one vmapped compile, with the solo
    baselines batched into the same call.

        exp = Experiment("mask", [("3DS", "BLK"), ("MUM", "RED")])
        res = exp.run()
        res.mean_weighted_speedup()
        res[0].app("3DS").l2_tlb_hit_rate
    """

    design: DesignLike
    mixes: Tuple[Tuple[Optional[str], ...], ...]
    cycles: int = 60_000

    def __post_init__(self):
        object.__setattr__(self, "design", as_design(self.design))
        object.__setattr__(self, "mixes", _normalize_mixes(self.mixes))

    def run(self, solo_baselines: bool = True, fail_soft: bool = False
            ) -> Union[ExperimentResult, FailureRecord]:
        """`fail_soft=True` converts a failure (compile, execution, or
        corrupt stats) into this experiment's `FailureRecord` instead of
        raising, so sweep loops over many experiments keep going."""
        plans = _mix_plan(self.mixes, solo_baselines)
        # one executable per (signature, n_apps): mixes + solos per batch
        stats_by_n = {}
        for n, plan in plans.items():
            try:
                stats_by_n[n] = run_batch(self.design, plan.rows,
                                          self.cycles)
            except Exception as e:  # noqa: BLE001 — fail-soft boundary
                if not fail_soft:
                    raise
                return FailureRecord(
                    designs=(self.design.name,), n_apps=n,
                    cycles=self.cycles, error_type=type(e).__name__,
                    message=str(e), stage="experiment-batch")
        return _assemble_result(self.design, self.cycles, len(self.mixes),
                                plans, stats_by_n)


@functools.partial(jax.profiler.annotate_function, name="runner.sweep")
def sweep(designs: Sequence[DesignLike],
          mixes: Sequence, cycles: int = 60_000,
          solo_baselines: bool = True,
          grid: bool = True,
          devices: Optional[int] = None,
          fail_soft: bool = False
          ) -> Dict[str, Union[ExperimentResult, FailureRecord]]:
    """Run several designs over the same mixes, keyed by design name.

    With `grid=True` (default) the designs are grouped by static
    signature and each (signature, n_apps) slice — every design of the
    group x every mix of that size, solo baselines included — runs as
    ONE compiled, vmapped grid execution (`run_grid`). The paper's
    8-design ablation grid compiles two programs instead of eight and
    executes two device calls per n_apps. `grid=False` keeps the
    per-design `Experiment` loop; results are bit-for-bit identical
    either way (pinned by tests).

    `devices=N` shards the grid rows over N devices (see `run_grid`);
    it requires the grid path.

    `fail_soft=True`: a failing signature group (or per-design
    experiment with `grid=False`) becomes a `FailureRecord` VALUE for
    each affected design name, and every other design's
    `ExperimentResult` is still computed and returned — one poisoned
    design point costs its group, not the sweep."""
    ds: List[Design] = []
    for d in designs:
        dd = as_design(d)
        if any(x.name == dd.name for x in ds):
            raise ValueError(f"duplicate design name in sweep: {dd.name!r}")
        ds.append(dd)
    if not grid:
        if devices and devices > 1:
            raise ValueError("devices > 1 requires the grid path "
                             "(sweep(grid=True))")
        return {d.name: Experiment(d, tuple(mixes), cycles).run(
            solo_baselines=solo_baselines, fail_soft=fail_soft)
            for d in ds}
    norm = _normalize_mixes(mixes)
    plans = _mix_plan(norm, solo_baselines)
    stats = {n: run_grid(ds, plan.rows, cycles, devices=devices,
                         fail_soft=fail_soft)
             for n, plan in plans.items()}        # stats[n][design][row]
    out: Dict[str, Union[ExperimentResult, FailureRecord]] = {}
    with _span("runner.unpack"):
        for i, d in enumerate(ds):
            rows_by_n = {n: stats[n][i] for n in plans}
            failed = [s for rows in rows_by_n.values() for s in rows
                      if isinstance(s, FailureRecord)]
            out[d.name] = failed[0] if failed else _assemble_result(
                d, cycles, len(norm), plans, rows_by_n)
    return out
