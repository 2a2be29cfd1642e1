"""Share of the traced slice's leaf device time in the membership teardown
at `run_trace`'s segment boundaries: ops with `mem.membership`
(`memsys.apply_membership_change`) anywhere in their op name, over all leaf
time, mean over chips. None without a trace, or when no device op of the
trace carries the scope, as in a program that does not name it."""
from perfbench import scopes
from perfbench.metrics._memsys import STAGES

SCOPE = "mem.membership"


def read(run):
    t = run.trace
    if not t:
        return None
    names = t.get("op_names") or scopes.live_op_names(t["events"])
    if not any(SCOPE in op.split("/") for ops in names.values()
               for op in ops):
        return None
    shares = scopes.scope_shares(t["events"], names, t["lo"], t["hi"],
                                 STAGES, (SCOPE,))
    return shares[SCOPE] if shares else None
