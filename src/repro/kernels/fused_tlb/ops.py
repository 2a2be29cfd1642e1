"""Dispatch wrapper for the fused TLB round kernel.

`interpret=None` means "lower for real" and is only legal on platforms
with a Pallas lowering (TPU/GPU); anywhere else it raises instead of
silently interpreting — interpret mode must be an explicit opt-in
(`interpret=True`, or `tlb_backend="pallas-interpret"` /
`REPRO_TLB_INTERPRET=1` one layer up in `sim/config.py`).

The TPU compiler refuses this kernel today: the dynamic row gather
`tags[set_ix]` in `kernel.py` fails Mosaic lowering with "Shape mismatch
in input, indices and output" (tests/test_chip_compile.py keeps that
refusal as a strict xfail).
"""
from __future__ import annotations

import functools

import jax

from repro.kernels import resolve_interpret

from .kernel import fused_tlb_round

PALLAS_PLATFORMS = ("tpu", "gpu")


@functools.partial(jax.jit,
                   static_argnames=("n_waves", "track_asids", "interpret"))
def fused_tlb_access(tags, asids, lru, vpn, asid, active, may_fill, time, *,
                     n_waves: int = 1, track_asids: bool = True,
                     interpret: bool | None = None):
    """One fused probe+fill round; returns (tags', asids', lru', hit, filled).

    hit/filled come back as int32 masks; counter arithmetic stays with
    the caller so both backends share it bit for bit.
    """
    interpret = resolve_interpret(
        interpret, "fused_tlb", PALLAS_PLATFORMS,
        hint=" (tlb_backend='pallas-interpret' or REPRO_TLB_INTERPRET=1), "
             "or use the 'xla' backend,")
    return fused_tlb_round(tags, asids, lru, vpn, asid, active, may_fill,
                           time, n_waves=n_waves, track_asids=track_asids,
                           interpret=interpret)
