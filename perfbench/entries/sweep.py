"""`runner.sweep`: the design ablation over seeded pairs, with solo
baselines, through the grid path (optionally sharded over devices)."""
from __future__ import annotations

import numpy as np

from perfbench.compare import Answer


def setup(ctx) -> None:
    pass


def call(ctx, mixes):
    spec = ctx.spec
    return ctx.runner.sweep(spec["designs"], mixes, cycles=spec["cycles"],
                            devices=spec.get("devices"))


def _solos(mixes):
    return sorted({b for m in mixes for b in m})


def work(ctx, mixes, result) -> int:
    rows = len(mixes) + len(_solos(mixes))
    return len(ctx.spec["designs"]) * rows * ctx.spec["cycles"]


def answers(ctx, mixes, result, rng) -> list:
    """`rows_per_design` rows of each design, mix or solo, drawn from rng."""
    n_apps = len(mixes[0])
    solos = _solos(mixes)
    out = []
    for name in ctx.spec["designs"]:
        res = result[name]
        picks = rng.choice(len(mixes) + len(solos),
                           ctx.spec["check"]["rows_per_design"], replace=False)
        for r in picks:
            if r < len(mixes):
                got = {key: np.asarray(v) for key, v in res[r].raw.items()}
                out.append(Answer(name, {"row": tuple(mixes[r])}, got,
                                  lambda s: s["row"]))
            else:
                b = solos[r - len(mixes)]
                row = (b,) + (None,) * (n_apps - 1)
                out.append(Answer(
                    name, {"row": row},
                    {"solo_ipc": np.asarray([res.solo_ipc[(b, n_apps)]])},
                    lambda s: {"solo_ipc": s["row"]["ipc"][:1]}))
    return out
