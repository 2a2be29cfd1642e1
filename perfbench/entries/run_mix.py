"""`runner.run_mix`: one researcher's single co-run simulation."""
from __future__ import annotations

import numpy as np

from perfbench.compare import Answer


def setup(ctx) -> None:
    pass


def call(ctx, mixes):
    (mix,) = mixes
    return ctx.runner.run_mix(ctx.spec["designs"][0], list(mix),
                              cycles=ctx.spec["cycles"])


def work(ctx, mixes, result) -> int:
    return ctx.spec["cycles"]


def answers(ctx, mixes, result, rng) -> list:
    (mix,) = mixes
    keep = {k: np.asarray(v) for k, v in result.items()}
    return [Answer(ctx.spec["designs"][0], {"mix": tuple(mix)}, keep,
                   lambda s: s["mix"])]
