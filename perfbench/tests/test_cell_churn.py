"""The `churn.mask.k8` cell on the CPU at a tiny size: whole, every
segment's snapshot equals the churn reference's; with each fault planted
in the timed path, the harness's and two of the teardown's own, `correct`
comes out false."""
import contextlib

import pytest

from perfbench.tests.faults import run_cell

WORKLOAD = "churn.mask.k8"
CYCLES = 80          # 8 segments of 10 cycles


def test_whole_run_is_correct():
    res = run_cell(WORKLOAD, 2**31 + 77, CYCLES)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    gap = res["compared"]["worst_rel_gap"]
    assert gap["value"] == 0.0 and gap["values"] > 0
    assert list(res)[-1] == "compared"
    assert res["metrics"]["sim_cycles_per_s"]["value"] > 0


@pytest.mark.parametrize("fault", ["state_unchanged", "answer_altered"])
def test_fault_is_not_correct(fault):
    assert run_cell(WORKLOAD, 5, CYCLES, fault)["correct"] is False


@contextlib.contextmanager
def teardown_replaced(fake):
    """The runner's segment program built with `fake` in place of
    `apply_membership_change` (fake(real, cfg, dp, state, change))."""
    from repro.sim import runner
    real = runner.apply_membership_change
    runner._compiled_seg_run.cache_clear()
    runner.apply_membership_change = \
        lambda cfg, dp, state, change: fake(real, cfg, dp, state, change)
    try:
        yield
    finally:
        runner.apply_membership_change = real
        runner._compiled_seg_run.cache_clear()


FAKES = {
    # the departing tenant's state is handed to its successor as it is
    "teardown_skipped": lambda real, cfg, dp, state, change: state,
    # the whole teardown, but the slot's ASID generation is not bumped
    "generation_reused": lambda real, cfg, dp, state, change:
        real(cfg, dp, state, change)._replace(
            asid_of_app=state.asid_of_app),
}


@pytest.mark.parametrize("fault", sorted(FAKES))
def test_teardown_fault_is_not_correct(fault):
    with teardown_replaced(FAKES[fault]):
        res = run_cell(WORKLOAD, 2**31 + 77, CYCLES)
    assert res["failed"] == 0 and res["correct"] is False
