"""`runner.predict_mixes`: the serving oracle's decision call. Candidate
mixes are padded with idle partners to the oracle's slot count and to a
fixed row count; solo baselines come from a cache that set-up fills, as
the oracle keeps it."""
from __future__ import annotations

import numpy as np

from perfbench.compare import Answer
from perfbench.traffic import eligible

FIELDS = ("ipc", "solo_ipc", "slowdown", "weighted_speedup", "max_slowdown")


def _predict(ctx, mixes):
    spec = ctx.spec
    return ctx.runner.predict_mixes(
        spec["designs"][0], mixes, cycles=spec["cycles"],
        slots=spec["slots"], pad_rows=spec["pad_rows"],
        solo_cache=ctx.state.setdefault("solo_cache", {}))


def setup(ctx) -> None:
    """Fill the solo cache with every bench the traffic may draw, in calls
    of the timed row count: single-bench mixes, each with its solo row."""
    pool = eligible(ctx.cfg)
    per_call = ctx.spec["pad_rows"] // 2
    for at in range(0, len(pool), per_call):
        _predict(ctx, [(b,) for b in pool[at:at + per_call]])


def call(ctx, mixes):
    return _predict(ctx, mixes)


def work(ctx, mixes, result) -> int:
    return len(mixes) * ctx.spec["cycles"]


def _derive(mix, slots):
    def derive(s):
        # Python floats: the builtin sum of floats is compensated
        ipc = [float(x) for x in s["mix"]["ipc"][:len(mix)]]
        solo = [float(s[f"solo:{b}"]["ipc"][0]) for b in mix]
        slow = [a / max(i, 1e-9) for a, i in zip(solo, ipc)]
        ws = sum(i / max(a, 1e-9) for i, a in zip(ipc, solo))
        return {"ipc": np.asarray(ipc), "solo_ipc": np.asarray(solo),
                "slowdown": np.asarray(slow),
                "weighted_speedup": np.asarray(ws),
                "max_slowdown": np.asarray(max(slow))}
    return derive


def answers(ctx, mixes, result, rng) -> list:
    slots = ctx.spec["slots"]
    out = []
    for mix, pred in zip(mixes, result):
        rows = {"mix": tuple(mix) + (None,) * (slots - len(mix))}
        rows.update({f"solo:{b}": (b,) + (None,) * (slots - 1) for b in mix})
        got = {k: np.asarray(getattr(pred, k), np.float64) for k in FIELDS}
        out.append(Answer(ctx.spec["designs"][0], rows, got,
                          _derive(mix, slots)))
    return out
