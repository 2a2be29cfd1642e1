"""The comparison that decides `correct`.

Each entry module turns a sample of its timed calls into answers: what
the program returned for some grid rows, and how the same quantities
follow from the plain reference's statistics of the rows they depend on.
The reference simulates every distinct (design, row) once, after the
window. The simulator is deterministic, so the comparison is exact: the
number compared is the largest relative gap over every value of every
answer, and its limit is 0.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Tuple

import numpy as np

from perfbench import reference

LIMIT = 0.0          # exact: a deterministic simulator repeats bit for bit
MISSING = 1.0e300    # the gap of a value that is missing or of another shape


@dataclasses.dataclass
class Answer:
    """`got` is what the program returned; `derive` maps the reference
    statistics of `rows` (label -> bench tuple) to the same keys."""
    design: str
    rows: Dict[str, Tuple]
    got: Dict[str, np.ndarray]
    derive: Callable[[Dict[str, dict]], Dict[str, np.ndarray]]


def gap(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return MISSING
    if not np.all(np.isfinite(got)):
        return MISSING
    diff = np.abs(got - want)
    scale = np.maximum(np.abs(want), 1e-12)
    return float(np.max(np.where(diff == 0, 0.0, diff / scale), initial=0.0))


def check(cfg: dict, answers, cycles: int):
    """(worst relative gap, values compared) of `answers` against the
    reference."""
    ref = reference_stats(cfg, answers, cycles)
    worst, n = 0.0, 0
    for a in answers:
        want = a.derive({k: ref[a.design, r] for k, r in a.rows.items()})
        for key, got in a.got.items():
            worst = max(worst, gap(got, want[key]) if key in want
                        else MISSING)
            n += np.size(got)
    return worst, n


def control(cfg: dict, answers, cycles: int) -> float:
    """The control's worst relative gap: the reference one precision below
    the configuration's (bfloat16 accumulators for float32, statistics
    derived in float32 for float64), put in the program's place for the
    same answers."""
    ref = reference_stats(cfg, answers, cycles)
    low = reference_stats(cfg, answers, cycles, "bfloat16", np.float32)
    worst = 0.0
    for a in answers:
        want = a.derive({k: ref[a.design, r] for k, r in a.rows.items()})
        got = a.derive({k: low[a.design, r] for k, r in a.rows.items()})
        worst = max([worst] + [gap(got[k], want[k]) for k in a.got])
    return worst


def reference_stats(cfg: dict, answers, cycles: int,
                    acc_dtype: str = "float32",
                    stat_dtype=np.float64) -> dict:
    """{(design, row): reference statistics} for every row the answers
    depend on, each simulated once."""
    todo: Dict[str, list] = {}
    for a in answers:
        for row in a.rows.values():
            if row not in todo.setdefault(a.design, []):
                todo[a.design].append(row)
    ref: Dict[Tuple[str, Tuple], dict] = {}
    for design, rows in todo.items():
        final = reference.simulate(
            cfg, design, np.stack([reference.app_rows(cfg, r) for r in rows]),
            cycles, acc_dtype)
        for i, row in enumerate(rows):
            ref[design, row] = reference.stats(cfg, final, i, stat_dtype)
    return ref
