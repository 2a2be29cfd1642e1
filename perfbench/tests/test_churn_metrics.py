"""The churn cell's two readers on a made-up trace: `runner.boundary_ms`
takes each boundary span wholly inside the slice less the device-busy time
in it; `scan.membership_share` is the teardown's share of leaf device time,
and reads nothing where no op carries its scope."""
import types

import pytest

from perfbench.run import load_module
from perfbench.tests.test_traffic import HERE

PLANE = "/device:TPU:0"
# a scan, a teardown op between two segments, the next scan; the `while`
# events enclose their bodies' ops, so they are no leaves
DEVICE = [("%while.1", 0.0, 40e6), ("%fusion.1", 0.0, 40e6),
          ("%fusion.2", 42e6, 43e6), ("%while.1", 45e6, 95e6),
          ("%fusion.3", 45e6, 95e6)]
SCAN = "jit(seg)/while/body/closed_call/mem.shared_round/scatter"
TEARDOWN = "jit(seg)/mem.membership/select_n"


def reader(name):
    return load_module(f"{HERE}/metrics/{name}.py", "metric_" + name)


def run_of(host, op_names):
    events = {"device": {PLANE: DEVICE}, "host": host}
    return types.SimpleNamespace(trace=dict(
        events=events, lo=0.0, hi=100e6, op_names={PLANE: op_names}))


def test_boundary_ms():
    # inside the slice: 35-45 ms with 5 + 1 ms busy, 50-54 ms all busy;
    # the third crosses the slice's end and is left out
    host = [("runner.boundary", 35e6, 45e6), ("runner.boundary", 50e6, 54e6),
            ("runner.boundary", 96e6, 120e6), ("runner.fetch", 35e6, 41e6)]
    read = reader("runner.boundary_ms").read
    assert read(run_of(host, [""] * 5)) == pytest.approx(((10 - 6) + 0) / 2)
    assert read(run_of(host[3:], [""] * 5)) is None


def test_membership_share():
    read = reader("scan.membership_share").read
    got = read(run_of([], ["", SCAN, TEARDOWN, "", SCAN]))
    assert got == pytest.approx(1 / 91)
    # a program that does not name the teardown reads nothing
    assert read(run_of([], ["", SCAN, SCAN, "", SCAN])) is None
