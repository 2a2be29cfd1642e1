"""The `pair.ideal.60k` cell on the CPU at a tiny size: whole, its answers
equal the plain reference's; with each fault it can have planted in the
timed path, `correct` comes out false."""
import pytest

from perfbench.tests.faults import run_cell

WORKLOAD = "pair.ideal.60k"
CYCLES = 60


def test_whole_run_is_correct():
    res = run_cell(WORKLOAD, 2**31 + 77, CYCLES)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert res["compared"]["worst_rel_gap"]["value"] == 0.0
    assert list(res)[-1] == "compared"
    assert res["metrics"]["sim_cycles_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_is_not_correct(fault):
    assert run_cell(WORKLOAD, 5, CYCLES, fault)["correct"] is False
