"""The control of `correct` on the CPU at a tiny size: the plain reference
one precision below the configuration's (bfloat16 accumulators, float32
statistics) put in the program's place reads a gap above the limit, while
the program itself reads none."""
import pytest

from perfbench import compare, control
from perfbench.tests.faults import own_cache


@pytest.mark.parametrize("workload,cycles", [
    ("pair.mask.60k", 300), ("oracle.16x300", 200),
    ("sweep8.pairs2.8k", 100)])
def test_control_is_not_correct(workload, cycles):
    with own_cache():
        (line,) = control.main(
            ["--workload", workload, "--seconds", "0.01", "--seeds",
             str(2**31 + 9)], allow_cpu=True,
            spec_overrides={"cycles": cycles})
    assert line["correct"] and line["program_gap"] == 0.0
    assert line["control_gap"] > compare.LIMIT
