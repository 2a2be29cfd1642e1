"""`runner.run_trace`: tenants arriving and leaving a shared GPU at
segment boundaries. The traffic's `segments` give each segment's slots as
indices into the call's drawn apps (None for an idle slot); the call's
`cycles` are split evenly over the segments. Every segment's snapshot is
an answer, compared with the churn reference's snapshot of that segment."""
from __future__ import annotations

import numpy as np

from perfbench import reference, reference_trace
from perfbench.compare import Answer


def setup(ctx) -> None:
    pass


def _schedule(ctx, mixes):
    (apps,) = mixes
    return [tuple(None if i is None else apps[i] for i in row)
            for row in ctx.spec["segments"]]


def _seg_cycles(ctx) -> int:
    return ctx.spec["cycles"] // len(ctx.spec["segments"])


def call(ctx, mixes):
    return ctx.runner.run_trace(ctx.spec["designs"][0],
                                _schedule(ctx, mixes),
                                seg_cycles=_seg_cycles(ctx))


def work(ctx, mixes, result) -> int:
    return len(ctx.spec["segments"]) * _seg_cycles(ctx)


def answers(ctx, mixes, result, rng) -> list:
    """One answer per segment; the reference runs the whole schedule once
    per precision, when the first answer is derived. `derive` takes the
    reference's precisions as keywords, so a control can ask for lower
    ones."""
    design, schedule = ctx.spec["designs"][0], _schedule(ctx, mixes)
    runs = {}

    def snapshots(acc_dtype, stat_dtype):
        key = (acc_dtype, stat_dtype)
        if key not in runs:
            finals = reference_trace.simulate_trace(
                ctx.cfg, design, schedule, _seg_cycles(ctx), acc_dtype)
            runs[key] = [reference.stats(ctx.cfg, f, 0, stat_dtype)
                         for f in finals]
        return runs[key]

    def derive(k):
        return lambda rows, acc_dtype="float32", stat_dtype=np.float64: \
            snapshots(acc_dtype, stat_dtype)[k]

    return [Answer(design, {}, {key: np.asarray(v) for key, v in s.items()},
                   derive(k))
            for k, s in enumerate(result.segments)]
