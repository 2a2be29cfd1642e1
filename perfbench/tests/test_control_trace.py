"""The control of `correct` in the churn cell on the CPU at a tiny size:
the churn reference one precision below the configuration's (bfloat16
accumulators, float32 statistics) put in the program's place reads a gap
above the limit, while the program itself reads none."""
from perfbench import compare, control_trace
from perfbench.tests.faults import own_cache


def test_control_is_not_correct():
    with own_cache():
        (line,) = control_trace.main(
            ["--workload", "churn.mask.k8", "--seconds", "0.01", "--seeds",
             str(2**31 + 9)], allow_cpu=True, spec_overrides={"cycles": 800})
    assert line["correct"] and line["program_gap"] == 0.0
    assert line["answers"] == 8 and line["control_gap"] > compare.LIMIT
