"""Composable design points: per-layer policy specs + a design registry.

A `Design` is a frozen, hashable composition of one policy spec per
memory-system layer:

  translation — which TLB organization serves address translation
                (ideal / page-walk-cache / shared L2 TLB) and its sizing
  partition   — whether shared L2$/DRAM resources are statically split
                per app (the paper's `Static` baseline) or fully shared
  tokens      — TLB-Fill Tokens (§5.2): epoch hill-climb on fill rights
  bypass      — TLB-request-aware L2 data-cache bypass (§5.3)
  dram        — address-space-aware DRAM scheduling (§5.4)

Every design point of the paper (ideal / PWC / GPU-MMU / Static /
MASK±components) is a registered composition of these specs, and new
points — e.g. MASK with a different token schedule, or bypass-only with a
bigger shared TLB — are expressed by composing specs, never by editing
simulator internals:

    mask = get_design("mask")
    mine = mask.with_(name="mask-small-tokens",
                      tokens=dict(initial_frac=0.1),
                      bypass=dict(enabled=False))
    register_design(mine)

Specs are plain frozen dataclasses: hashable (so a `SimConfig` carrying a
`Design` keys jit/compile caches correctly) and static under jit (stage
dispatch in `repro.sim.memsys` branches on them at trace time).

`repro.core.mask` keeps the legacy `DesignPoint`/`MaskConfig` dataclasses
and the `design(name)` / `ALL_DESIGNS` shims on top of this registry.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

# translation organizations (paper Fig. 2a/2b + the ideal upper bound)
TRANSLATION_KINDS = ("ideal", "pwc", "shared_l2_tlb", "walk_only")
PARTITION_KINDS = ("shared", "static")
DRAM_KINDS = ("fr_fcfs", "mask")


@dataclasses.dataclass(frozen=True)
class TranslationSpec:
    """Translation-layer policy: organization + cache sizing (Table 1).

    kind:
      "ideal"         — every TLB access hits (no translation overhead)
      "pwc"           — per-core L1 TLBs + shared page-walk cache (Fig. 2a)
      "shared_l2_tlb" — per-core L1 TLBs + shared L2 TLB (Fig. 2b)
      "walk_only"     — L1 TLBs only; every miss walks (no shared level)
    """

    kind: str = "shared_l2_tlb"
    l1_entries: int = 64             # fully associative, per core
    l2_entries: int = 512            # 16-way, ASID-tagged, shared
    l2_ways: int = 16
    walk_levels: int = 4             # radix page-table depth
    max_concurrent_walks: int = 64   # walker threads (Table 1)

    def __post_init__(self):
        if self.kind not in TRANSLATION_KINDS:
            raise ValueError(f"translation kind {self.kind!r} not in "
                             f"{TRANSLATION_KINDS}")


@dataclasses.dataclass(frozen=True)
class PartitionSpec:
    """Shared-resource partitioning: "shared" contends everything;
    "static" gives each app a contiguous ~1/n slice of L2 sets and DRAM
    channels (the `Static` baseline, §6)."""

    kind: str = "shared"

    def __post_init__(self):
        if self.kind not in PARTITION_KINDS:
            raise ValueError(f"partition kind {self.kind!r} not in "
                             f"{PARTITION_KINDS}")


@dataclasses.dataclass(frozen=True)
class TokenSpec:
    """TLB-Fill Tokens (§5.2): only token-holding warps may fill the
    shared L2 TLB; the rest fill a small bypass cache. Token counts adapt
    per epoch by hill-climbing on the shared-TLB miss rate."""

    enabled: bool = False
    # paper initializes at 0.8 with 100K-cycle epochs; our scaled runs see
    # ~7 epochs, so the default starts near the converged region
    initial_frac: float = 0.25
    step_frac: float = 0.5           # geometric hill-climb step
    bypass_cache_entries: int = 32   # fully associative


@dataclasses.dataclass(frozen=True)
class BypassSpec:
    """TLB-request-aware L2 data-cache bypass (§5.3): per-walk-level fill
    gating against the data-request hit rate."""

    enabled: bool = False


@dataclasses.dataclass(frozen=True)
class DramSpec:
    """DRAM scheduling: "fr_fcfs" is the baseline; "mask" adds the
    golden/silver/normal queues with Eq. (1) silver quotas (§5.4)."""

    kind: str = "fr_fcfs"
    thres_max: int = 500             # Eq. (1) quota ceiling

    def __post_init__(self):
        if self.kind not in DRAM_KINDS:
            raise ValueError(f"dram kind {self.kind!r} not in {DRAM_KINDS}")

    @property
    def enabled(self) -> bool:
        return self.kind == "mask"


@dataclasses.dataclass(frozen=True)
class Design:
    """A named, frozen, hashable design point: one policy spec per layer.

    Hashability matters: `SimConfig` embeds the `Design`, and the runner's
    compile caches are keyed on the full config — two designs that differ
    in any spec field never share a compiled executable, even if they
    share a name.
    """

    name: str
    translation: TranslationSpec = TranslationSpec()
    partition: PartitionSpec = PartitionSpec()
    tokens: TokenSpec = TokenSpec()
    bypass: BypassSpec = BypassSpec()
    dram: DramSpec = DramSpec()
    epoch_cycles: int = 8_000        # paper: 100K; scaled to sim length

    # ---------------------------------------------------------- overrides

    def with_(self, **overrides) -> "Design":
        """Ablation-grid helper: `dataclasses.replace` with nested-merge
        sugar — a dict value merges into the corresponding spec instead of
        replacing it wholesale.

            mask.with_(name="my-mask", tokens=dict(initial_frac=0.1),
                       bypass=dict(enabled=False))
        """
        fields = {f.name for f in dataclasses.fields(self)}
        updates = {}
        for key, val in overrides.items():
            if key not in fields:
                raise TypeError(f"Design has no layer/field {key!r} "
                                f"(have: {', '.join(sorted(fields))})")
            cur = getattr(self, key)
            if isinstance(val, dict) and dataclasses.is_dataclass(cur):
                val = dataclasses.replace(cur, **val)
            updates[key] = val
        return dataclasses.replace(self, **updates)

    replace = with_

    # ------------------------------------------------- legacy flag views
    # Read-only views matching the pre-registry `DesignPoint` flag bag, so
    # code written against `design(name).mask.epoch_cycles` etc. keeps
    # working unchanged.

    @property
    def ideal_tlb(self) -> bool:
        return self.translation.kind == "ideal"

    @property
    def use_pwc(self) -> bool:
        return self.translation.kind == "pwc"

    @property
    def use_l2_tlb(self) -> bool:
        return self.translation.kind in ("shared_l2_tlb", "ideal")

    @property
    def static_partition(self) -> bool:
        return self.partition.kind == "static"

    @property
    def mask(self):
        from repro.core.mask import MaskConfig
        return MaskConfig(
            tlb_tokens=self.tokens.enabled,
            l2_bypass=self.bypass.enabled,
            dram_sched=self.dram.enabled,
            l1_tlb_entries=self.translation.l1_entries,
            l2_tlb_entries=self.translation.l2_entries,
            l2_tlb_ways=self.translation.l2_ways,
            bypass_cache_entries=self.tokens.bypass_cache_entries,
            epoch_cycles=self.epoch_cycles,
            initial_token_frac=self.tokens.initial_frac,
            token_step_frac=self.tokens.step_frac,
            thres_max=self.dram.thres_max,
            walk_levels=self.translation.walk_levels,
            max_concurrent_walks=self.translation.max_concurrent_walks,
        )


# ---------------------------------------------------------------------------
# static / traced split: StaticSignature + DesignParams
# ---------------------------------------------------------------------------
# A Design splits into two planes:
#
#   * the STATIC SIGNATURE — every field that changes array shapes or the
#     traced program structure (cache sizing, walk depth, walk-table size,
#     epoch length, and whether translation is "ideal", which traces the
#     whole walk machinery out of the program). Designs sharing a
#     signature share ONE compiled executable.
#   * the traced DESIGN PARAMS — every remaining knob (policy booleans,
#     token budgets, hill-climb step, DRAM quota ceiling), packed as a
#     small pytree of scalars and fed to the compiled program as inputs.
#     The memsys stages select on them with `jnp.where`, so a whole
#     design x mix grid can be vmapped through one executable.


@dataclasses.dataclass(frozen=True)
class StaticSignature:
    """The compile-relevant plane of a Design (hashable compile key).

    Two designs with equal signatures are guaranteed to lower to the same
    XLA program; everything else about them rides in `DesignParams`.
    """

    ideal: bool                   # "ideal" translation traces out the walks
    l1_entries: int
    l2_entries: int
    l2_ways: int
    walk_levels: int
    max_concurrent_walks: int
    bypass_cache_entries: int
    epoch_cycles: int


def static_signature(d) -> StaticSignature:
    """The static (shape/structure) plane of a design — the compile key."""
    d = as_design(d)
    tr = d.translation
    return StaticSignature(
        ideal=tr.kind == "ideal",
        l1_entries=tr.l1_entries,
        l2_entries=tr.l2_entries,
        l2_ways=tr.l2_ways,
        walk_levels=tr.walk_levels,
        max_concurrent_walks=tr.max_concurrent_walks,
        bypass_cache_entries=d.tokens.bypass_cache_entries,
        epoch_cycles=d.epoch_cycles,
    )


def canonical_design(sig: StaticSignature) -> Design:
    """The canonical representative `Design` of a signature group.

    Deterministic in the signature, so configs built from it compare/hash
    equal and key one shared compile-cache entry per group. Its dynamic
    fields are placeholders: the simulator must read those from
    `DesignParams` only (the float-hex goldens enforce this — a stage
    reading a placeholder statically would collapse all same-signature
    designs onto one behavior)."""
    kind = "ideal" if sig.ideal else "shared_l2_tlb"
    return Design(
        name=f"__sig:{'ideal' if sig.ideal else 'std'}__",
        translation=TranslationSpec(
            kind=kind, l1_entries=sig.l1_entries,
            l2_entries=sig.l2_entries, l2_ways=sig.l2_ways,
            walk_levels=sig.walk_levels,
            max_concurrent_walks=sig.max_concurrent_walks),
        tokens=TokenSpec(bypass_cache_entries=sig.bypass_cache_entries),
        epoch_cycles=sig.epoch_cycles,
    )


class DesignParams(NamedTuple):
    """The traced plane of a Design: scalar knobs fed to the compiled sim.

    All leaves are 0-d jax arrays so a stack of designs is just a leading
    axis + vmap. Policy selectors are booleans the stages `jnp.where` on
    (masked TLB probes/fills are state no-ops), never Python branches.
    """

    use_l2_tlb: jax.Array       # () bool: shared L2 TLB organization
    use_pwc: jax.Array          # () bool: page-walk-cache organization
    tokens_on: jax.Array        # () bool: TLB-Fill Tokens (§5.2)
    initial_frac: jax.Array     # () float32 initial token fraction
    step_frac: jax.Array        # () float32 hill-climb step
    bypass_on: jax.Array        # () bool: L2 data-cache bypass (§5.3)
    dram_on: jax.Array          # () bool: MASK DRAM scheduler (§5.4)
    thres_max: jax.Array        # () int32 Eq. (1) quota ceiling
    static_part: jax.Array      # () bool: static L2$/DRAM partitioning


def host_design_params(d) -> DesignParams:
    """`design_params` as 0-d numpy arrays on the host, with the same
    (non-weak) dtypes: rows stacked from them with numpy feed the
    compiled grid the avals of stacked `design_params`, so it never
    retraces."""
    d = as_design(d)
    return DesignParams(
        use_l2_tlb=np.asarray(d.translation.kind == "shared_l2_tlb", bool),
        use_pwc=np.asarray(d.translation.kind == "pwc", bool),
        tokens_on=np.asarray(d.tokens.enabled, bool),
        initial_frac=np.asarray(d.tokens.initial_frac, np.float32),
        step_frac=np.asarray(d.tokens.step_frac, np.float32),
        bypass_on=np.asarray(d.bypass.enabled, bool),
        dram_on=np.asarray(d.dram.enabled, bool),
        thres_max=np.asarray(d.dram.thres_max, np.int32),
        static_part=np.asarray(d.partition.kind == "static", bool),
    )


def design_params(d) -> DesignParams:
    """Pack a design's dynamic knobs into the traced `DesignParams` plane."""
    return jax.tree_util.tree_map(jnp.asarray, host_design_params(d))


def from_legacy(dp) -> Design:
    """Convert a legacy `repro.core.mask.DesignPoint` to a `Design`."""
    if isinstance(dp, Design):
        return dp
    m = dp.mask
    if dp.ideal_tlb:
        kind = "ideal"
    elif dp.use_pwc:
        if dp.use_l2_tlb:
            # the old pipeline would run BOTH the shared L2 TLB and the
            # PWC for this flag combo; no TranslationSpec kind expresses
            # that, so refuse rather than silently drop one of them
            raise ValueError(
                f"legacy DesignPoint {dp.name!r} sets both use_l2_tlb and "
                "use_pwc; that combination has no Design equivalent — "
                "pick one translation organization")
        kind = "pwc"
    elif dp.use_l2_tlb:
        kind = "shared_l2_tlb"
    else:
        kind = "walk_only"
    return Design(
        name=dp.name,
        translation=TranslationSpec(
            kind=kind, l1_entries=m.l1_tlb_entries,
            l2_entries=m.l2_tlb_entries, l2_ways=m.l2_tlb_ways,
            walk_levels=m.walk_levels,
            max_concurrent_walks=m.max_concurrent_walks),
        partition=PartitionSpec(
            "static" if dp.static_partition else "shared"),
        tokens=TokenSpec(enabled=m.tlb_tokens,
                         initial_frac=m.initial_token_frac,
                         step_frac=m.token_step_frac,
                         bypass_cache_entries=m.bypass_cache_entries),
        bypass=BypassSpec(enabled=m.l2_bypass),
        dram=DramSpec("mask" if m.dram_sched else "fr_fcfs",
                      thres_max=m.thres_max),
        epoch_cycles=m.epoch_cycles,
    )


def as_design(d) -> Design:
    """Normalize str | Design | legacy DesignPoint to a Design."""
    if isinstance(d, Design):
        return d
    if isinstance(d, str):
        return get_design(d)
    if hasattr(d, "mask") and hasattr(d, "name"):  # legacy DesignPoint
        return from_legacy(d)
    raise TypeError(f"not a design name/Design/DesignPoint: {d!r}")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Design] = {}


def register_design(d: Design, *, overwrite: bool = False) -> Design:
    """Register a design under its name; returns it for chaining.

    Refuses to silently shadow an existing *different* design (re-registering
    an identical one is a no-op) unless `overwrite=True`.
    """
    if not isinstance(d, Design):
        d = as_design(d)
    prev = _REGISTRY.get(d.name)
    if prev is not None and prev != d and not overwrite:
        raise ValueError(
            f"design {d.name!r} already registered with different specs; "
            "pass overwrite=True or pick another name")
    _REGISTRY[d.name] = d
    return d


def get_design(name: str) -> Design:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown design {name!r}; registered: "
                       f"{', '.join(sorted(_REGISTRY))}") from None


def list_designs() -> Tuple[str, ...]:
    """Registered design names, built-ins first (registration order)."""
    return tuple(_REGISTRY)


# ------------------------------------------------------------- built-ins
# The paper's named baselines and MASK±component ablations (§6).

_MECHS_OFF = dict(tokens=TokenSpec(enabled=False),
                  bypass=BypassSpec(enabled=False),
                  dram=DramSpec("fr_fcfs"))

BUILTIN_DESIGNS: Tuple[Design, ...] = (
    Design("ideal", translation=TranslationSpec(kind="ideal"), **_MECHS_OFF),
    Design("pwc", translation=TranslationSpec(kind="pwc"), **_MECHS_OFF),
    Design("gpu-mmu", **_MECHS_OFF),
    Design("static", partition=PartitionSpec("static"), **_MECHS_OFF),
    Design("mask", tokens=TokenSpec(enabled=True),
           bypass=BypassSpec(enabled=True), dram=DramSpec("mask")),
    Design("mask-tlb", tokens=TokenSpec(enabled=True)),
    Design("mask-cache", bypass=BypassSpec(enabled=True)),
    Design("mask-dram", dram=DramSpec("mask")),
)

for _d in BUILTIN_DESIGNS:
    register_design(_d)
