"""The churn reference (`perfbench/reference_trace.py`) on the CPU: with a
constant schedule it is `reference.simulate`, and under churn it equals
`runner.run_trace` snapshot by snapshot, bit for bit, at published widths
and tens of cycles per segment."""
import json
import os

import numpy as np
import pytest

from perfbench import reference, reference_trace

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(HERE, "configs", "table1.4slot.churn.json")) as f:
    CFG = json.load(f)

# every kind of boundary: arrival into an idle slot (0->1, 2->3), hand-off
# (1->2), departure to idle (3->4, 6->7), several changes at once (4->5,
# 6->7) and none (5->6)
CHURN = [("3DS", "BLK", None, None), ("3DS", "BLK", "MUM", None),
         ("3DS", "HISTO", "MUM", None), ("3DS", "HISTO", "MUM", "BP"),
         (None, "HISTO", "MUM", "BP"), ("CFD", "HISTO", "SAD", "BP"),
         ("CFD", "HISTO", "SAD", "BP"), ("CFD", "FFT", "SAD", None)]


def _bits(stats) -> dict:
    return {k: np.asarray(v, np.float64).tobytes()
            for k, v in sorted(stats.items())}


def test_constant_schedule_is_simulate():
    mix = ("3DS", "BLK", "MUM", None)
    seg = 20
    finals = reference_trace.simulate_trace(CFG, "mask", [mix] * 2, seg)
    for k, final in enumerate(finals):
        whole = reference.simulate(CFG, "mask",
                                   reference.app_rows(CFG, mix)[None],
                                   (k + 1) * seg)
        assert final.keys() == whole.keys()
        for key in whole:
            assert np.asarray(final[key]).tobytes() == \
                np.asarray(whole[key]).tobytes(), (k, key)


@pytest.mark.parametrize("design", ["mask", "gpu-mmu", "pwc"])
def test_churn_equals_run_trace(design):
    from repro.sim import runner
    seg = 25
    got = runner.run_trace(design, CHURN, seg_cycles=seg).segments
    want = [reference.stats(CFG, f, 0) for f in
            reference_trace.simulate_trace(CFG, design, CHURN, seg)]
    assert len(got) == len(want) == len(CHURN)
    for k, (g, w) in enumerate(zip(got, want)):
        assert _bits(g) == _bits(w), k


def test_another_membership_definition_is_refused():
    cfg = dict(CFG, membership=dict(CFG["membership"], pwc="keep"))
    with pytest.raises(ValueError, match="membership"):
        reference_trace.simulate_trace(cfg, "mask", CHURN[:2], 5)
