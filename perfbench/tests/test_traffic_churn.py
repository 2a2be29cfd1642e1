"""The traffic of the churn cell and of the Ideal pair: the churn cell
draws 8 distinct apps and replays one slot pattern for every seed, so every
seed runs the same boundaries; the Ideal pair is one fixed pair."""
import types

import pytest

from perfbench.run import load_module
from perfbench.tests.test_traffic import HERE, SEEDS, load, traffic

ENTRY = load_module(f"{HERE}/entries/run_trace.py", "entry_run_trace")


def changes(schedule):
    """Per boundary, the slots whose tenant changes."""
    return [tuple(i for i, (a, b) in enumerate(zip(p, q)) if a != b)
            for p, q in zip(schedule, schedule[1:])]


@pytest.mark.parametrize("seed", SEEDS)
def test_churn_is_one_pattern_of_8_apps(seed):
    tr = traffic("churn.mask.k8", "table1.4slot.churn", seed)
    (apps,) = first = tr.draw()
    assert len(apps) == len(set(apps)) == 8
    assert all(b in tr.pool for b in apps)
    assert all(tr.draw() == first for _ in range(3))
    ctx = types.SimpleNamespace(spec=tr.spec)
    schedule = ENTRY._schedule(ctx, first)
    assert [len(s) for s in schedule] == [4] * 8
    # the pattern of the traffic file, whatever the seed: A B - - first,
    # and every kind of boundary
    assert schedule[0] == (apps[0], apps[1], None, None)
    assert changes(schedule) == [(2,), (1,), (3,), (0,), (0, 2), (),
                                 (1, 3)]
    assert ENTRY._seg_cycles(ctx) * len(schedule) == tr.spec["cycles"]


def test_churn_cell_runs_the_config_slots():
    spec = load("traffic", "churn.mask.k8")
    cfg = load("configs", "table1.4slot.churn")
    assert {len(row) for row in spec["segments"]} == {cfg["n_apps"]}
    assert spec["cycles"] // len(spec["segments"]) == cfg["epoch_cycles"]


@pytest.mark.parametrize("seed", SEEDS)
def test_ideal_pair_is_one_fixed_pair(seed):
    tr = traffic("pair.ideal.60k", "table1.2app", seed)
    first = tr.draw()
    assert len(first) == 1 and len(set(first[0])) == 2
    assert all(tr.draw() == first for _ in range(3))
    assert tr.spec["designs"] == ["ideal"]
