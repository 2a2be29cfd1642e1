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

`PAPER_DESIGNS` names the paper's eight built-in design points.
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


def static_partition_index(index, n_resources: int, n_apps: int, app):
    """Static resource partitioning (the `Static` design, §6): app `a` owns
    a contiguous ~1/n_apps slice of an index space (L2 sets, DRAM channels).
    Slice bounds are proportional ((a*n)//n_apps .. ((a+1)*n)//n_apps) so no
    trailing resources are stranded when n_apps does not divide n_resources;
    if there are fewer resources than apps the slice floor is one unit and
    the result clips into range.

    index/app may be traced arrays; n_resources/n_apps are static ints.
    """
    na = max(n_apps, 1)
    start = (app * n_resources) // na
    span = jnp.maximum((app + 1) * n_resources // na - start, 1)
    return jnp.minimum(start + index % span, n_resources - 1)


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


def as_design(d) -> Design:
    """Normalize a registered name or a Design to a Design."""
    if isinstance(d, Design):
        return d
    if isinstance(d, str):
        return get_design(d)
    raise TypeError(f"not a design name or Design: {d!r}")


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Design] = {}


def register_design(d: Design, *, overwrite: bool = False) -> Design:
    """Register a design under its name; returns it for chaining.

    Refuses to silently shadow an existing *different* design (re-registering
    an identical one is a no-op) unless `overwrite=True`.
    """
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

# the paper's named designs (the registry may hold user designs beyond these)
PAPER_DESIGNS: Tuple[str, ...] = tuple(d.name for d in BUILTIN_DESIGNS)
