"""The `sweep8.pairs8.8k.4chip` cell on four virtual CPU devices at a tiny
size: whole, its answers equal the plain reference's; with a fault planted
in the exchange (every chip's rows come back as the first chip's), `correct`
comes out false.

The device count is fixed when JAX starts, so both runs are made in one
child process with `--xla_force_host_platform_device_count=4`; this process
stays on its single device."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
WORKLOAD = "sweep8.pairs8.8k.4chip"
CYCLES = 60

_CHILD = r"""
import json
import sys

import jax
import numpy as np

from perfbench import run
from perfbench.tests import faults
from repro.sim import runner

assert jax.device_count() == 4, jax.device_count()
workload, cycles = sys.argv[1], int(sys.argv[2])


def first_chip_for_all(compiled_grid_run):
    def grid(ccfg):
        fn = compiled_grid_run(ccfg)

        def broken(dp, pm):
            def first(x):
                shards = x.addressable_shards
                block = np.asarray(shards[0].data)
                return np.concatenate([block] * len(shards))
            return jax.tree_util.tree_map(first, fn(dp, pm))
        return broken
    return grid


out = {}
for fault in (None, "exchange"):
    with faults.own_cache(), faults.planted(None):
        if fault:
            runner._compiled_grid_run = first_chip_for_all(
                runner._compiled_grid_run)
        out[str(fault)] = run.run_cell(
            ["--workload", workload, "--seed", str(2**31 + 4242),
             "--seconds", "0.01", "--trace", "0"],
            allow_cpu=True, spec_overrides={"cycles": cycles})[0]
print("RESULTS " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def results():
    env = dict(os.environ, PYTHONPATH=os.pathsep.join([ROOT, os.path.join(
        ROOT, "src")]), JAX_PLATFORMS="cpu",
        XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                   + " --xla_force_host_platform_device_count=4"))
    proc = subprocess.run([sys.executable, "-c", _CHILD, WORKLOAD,
                           str(CYCLES)], cwd=ROOT, env=env,
                          capture_output=True, text=True, timeout=1200)
    line = [x for x in proc.stdout.splitlines() if x.startswith("RESULTS ")]
    assert proc.returncode == 0 and line, proc.stderr[-4000:]
    return json.loads(line[-1][len("RESULTS "):])


def test_whole_run_is_correct(results):
    res = results["None"]
    assert res["correct"] and res["failed"] == 0 and res["attempted"] >= 1
    assert res["compared"]["worst_rel_gap"]["value"] == 0.0
    assert res["device"]["count"] == 4
    assert res["metrics"]["sim_cycles_per_s"]["value"] > 0


def test_exchange_fault_is_not_correct(results):
    res = results["exchange"]
    assert res["correct"] is False and res["failed"] == 0
    assert res["compared"]["worst_rel_gap"]["value"] > 0
