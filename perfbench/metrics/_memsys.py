"""The names the program gives the stages of its simulated cycle
(`jax.named_scope` in `src/repro/sim/memsys.py::step`, and the nested
rounds in `core/tlb.py::access_fused` and `core/dram_sched.py::access`),
and the share of the scan's device time under each, for the `scan.*`
readers."""
from perfbench import scopes

STAGES = ("mem.warp_sched", "mem.translation_probe", "mem.datapath_front",
          "mem.shared_round", "mem.translation_commit", "mem.retire",
          "mem.stats", "mem.epoch")
NESTED = ("mem.dram", "mem.fused_tlb")
UNSCOPED = ""


def share(run, scope: str):
    """Share of the traced slice's leaf device time under `scope` (one of
    STAGES, NESTED or UNSCOPED), mean over chips; None without a trace or
    when no op carries a stage scope. The ops' names come from the trace
    context's "op_names" where a saved trace gave them, else from the
    programs this process holds."""
    t = run.trace
    if not t:
        return None
    if "memsys_shares" not in t:       # eleven readers, one reduction
        names = t.get("op_names") or scopes.live_op_names(t["events"])
        t["memsys_shares"] = scopes.scope_shares(
            t["events"], names, t["lo"], t["hi"], STAGES, NESTED)
    shares = t["memsys_shares"]
    return shares[scope] if shares else None
