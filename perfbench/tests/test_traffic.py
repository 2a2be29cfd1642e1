"""The benchmark's traffic copies: deterministic in the seed, distinct
apps, and one row count (so one compiled program) for every seed."""
import json
import os

import pytest

from perfbench.traffic import Traffic, eligible

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SEEDS = [0, 1, 7, 2**31 - 1, 2**31 + 12345, 4_100_000_003]


def load(kind, name):
    with open(os.path.join(HERE, kind, name + ".json")) as f:
        return json.load(f)


def traffic(name, config, seed):
    return Traffic(load("traffic", name), load("configs", config), seed)


def sweep_rows(spec, mixes):
    """(ideal group rows, seven-design group rows) of one sweep call."""
    per_design = len(mixes) + len({b for m in mixes for b in m})
    std = sum(d != "ideal" for d in spec["designs"])
    return per_design * (len(spec["designs"]) - std), per_design * std


@pytest.mark.parametrize("name,config", [
    ("pair.mask.60k", "table1.2app"), ("sweep8.pairs2.8k", "table1.2app"),
    ("sweep8.pairs8.8k", "table1.2app"), ("oracle.16x300", "table1.4slot")])
def test_same_seed_same_calls(name, config):
    a, b = traffic(name, config, 99), traffic(name, config, 99)
    assert [a.draw() for _ in range(5)] == [b.draw() for _ in range(5)]
    c = traffic(name, config, 100)
    if not load("traffic", name).get("same_every_call"):
        assert [a.draw() for _ in range(5)] != [c.draw() for _ in range(5)]


def test_pool_leaves_out_low_low():
    pool = eligible(load("configs", "table1.2app"))
    assert len(pool) == 25 and "LUD" not in pool and "NN" not in pool


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("name,rows", [("sweep8.pairs2.8k", (6, 42)),
                                       ("sweep8.pairs8.8k", (24, 168))])
def test_sweep_rows_fixed(seed, name, rows):
    tr = traffic(name, "table1.2app", seed)
    for _ in range(4):
        mixes = tr.draw()
        apps = [b for m in mixes for b in m]
        assert len(set(apps)) == len(apps)          # distinct in the call
        assert all(len(m) == 2 for m in mixes)
        assert sweep_rows(tr.spec, mixes) == rows


@pytest.mark.parametrize("seed", SEEDS)
def test_oracle_rows_fixed(seed):
    tr = traffic("oracle.16x300", "table1.4slot", seed)
    for _ in range(4):
        mixes = tr.draw()
        assert len(mixes) == 16                      # 16 rows, no padding
        assert all(2 <= len(m) <= 4 and len(set(m)) == len(m) for m in mixes)
        assert all(b in tr.pool for m in mixes for b in m)


@pytest.mark.parametrize("seed", SEEDS)
def test_pair_is_one_fixed_pair(seed):
    tr = traffic("pair.mask.60k", "table1.2app", seed)
    first = tr.draw()
    assert len(first) == 1 and len(set(first[0])) == 2
    assert all(tr.draw() == first for _ in range(3))
