"""The reduction from trace events to busy time, idle share, host time per
call and the breakdown: on hand-made events, and on a trace recorded on
one TPU v5e chip through the harness's traced window (16-row oracle calls
of 40 cycles, each in a `perfbench.call` span)."""
import os

import pytest

from perfbench import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "fixtures", "oracle_call.xplane.pb.gz")


def test_union_and_busy():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    ev = [("a", 0, 2), ("b", 1, 3), ("c", 5, 8)]
    assert trace.busy_ns(ev, 0, 10) == 6
    assert trace.busy_ns(ev, 2, 6) == 2


def test_reduce_hand_made():
    t = {"device": {"/device:TPU:0": [("fusion.1", 10, 20), ("fusion.2", 20, 30),
                                      ("fusion.1", 60, 70)]},
         "host": [("perfbench.slice", 0, 100), ("perfbench.call", 5, 50),
                  ("device_get", 35, 55)]}
    red = trace.reduce(t, 0, 100)
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["idle_share"] == pytest.approx(0.7)
    assert red["device_ops"] == [["fusion.1", 20e-9], ["fusion.2", 10e-9]]
    # gaps, longest first, named by the innermost host event at their
    # middle (the slice itself names none): 30-60, 70-100, 0-10
    assert red["idle_gaps"] == [["device_get", 30e-9],
                                ["no host event", 30e-9],
                                ["perfbench.call", 10e-9]]
    # the one call wholly inside: 45 ns long, 20 ns of it busy
    assert trace.host_ms_per_call(t, 0, 100) == pytest.approx(25e-6)
    assert trace.host_ms_per_call(t, 10, 100) is None


def test_reduce_without_device_reads_nothing():
    t = {"device": {}, "host": [("perfbench.slice", 0, 10)]}
    assert trace.reduce(t, 0, 10) == {}
    assert trace.host_ms_per_call(t, 0, 10) is None


def test_recorded_chip_trace():
    t = trace.load(FIXTURE)
    assert t["device"], "the recorded trace holds a TPU plane"
    (lo, hi), = trace.spans(t, "perfbench.slice")
    red = trace.reduce(t, lo, hi)
    assert 0 < red["busy_s"] < red["window_s"]
    assert 0 < red["idle_share"] < 1
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) == 10
    inside = [(s, e) for s, e in trace.spans(t, "perfbench.call")
              if lo <= s and e <= hi]
    assert inside
    ms = trace.host_ms_per_call(t, lo, hi)
    assert 0 < ms < max(e - s for s, e in inside) / 1e6
    # the tracing thread's own events are left out; the slice remains
    assert ("perfbench.slice", lo, hi) in t["host"]
