# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# fused_tlb/ is the simulator's hot spot: the fused cross-wave shared
# L2$/PWC round (core/tlb.py::access_fused) as a Pallas kernel, selected
# via SimConfig.tlb_backend / REPRO_TLB_BACKEND (xla | pallas |
# pallas-interpret) and parity-pinned bit-for-bit against the XLA path.
# It replaces the retired seed tlb_probe/ kernel, whose single-round
# probe+fill contract predated the fused semantics.
#
# Every ops.py wrapper takes `interpret`: None lowers for real on a
# platform that has the lowering and raises anywhere else; interpret mode
# is an explicit opt-in (`interpret=True`). Nothing falls back quietly.

import jax


def resolve_interpret(interpret, kernel: str, platforms=("tpu",),
                      hint: str = "") -> bool:
    """`interpret` for a kernel call: True/False pass through; None means
    "lower for real", legal only when the default backend is one of
    `platforms` — elsewhere it raises rather than silently interpreting."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend not in platforms:
        raise RuntimeError(
            f"{kernel}: no Pallas lowering for platform {backend!r}; pass "
            f"interpret=True{hint} to run the interpreter explicitly")
    return False
