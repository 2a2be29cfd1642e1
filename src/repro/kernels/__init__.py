# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
#
# The simulator's hot spot, the fused cross-wave shared L2$/PWC round,
# has no kernel here: it runs as XLA (core/tlb.py::access_fused). A
# Pallas round belongs here only once it replaces that path after winning
# on the chip, never as a second path behind a switch. The kernels here
# serve the model stack (attention, SSD scan).
#
# Every ops.py wrapper takes `interpret`: None lowers for real on a
# platform that has the lowering and raises anywhere else; interpret mode
# is an explicit opt-in (`interpret=True`). Nothing falls back quietly.

import jax


def resolve_interpret(interpret, kernel: str) -> bool:
    """`interpret` for a kernel call: True/False pass through; None means
    "lower for real", legal only when the default backend is the TPU —
    elsewhere it raises rather than silently interpreting."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{kernel}: no Pallas lowering for platform {backend!r}; pass "
            "interpret=True to run the interpreter explicitly")
    return False
