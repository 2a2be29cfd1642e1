"""The program against the plain reference, exactly.

`perfbench/reference.py` is an independent simulator written from the
model's definition; it imports nothing of `repro`. Every built-in design
at 1-4 application slots must give the same statistics, float-hex on
every key, on a small GPU (6 cores x 8 warps, 300 cycles) whose epochs
(100 cycles) are crossed twice, so the adaptive token, bypass and DRAM
paths run too.
"""
import functools
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference
from repro.core.design import PAPER_DESIGNS, get_design
from repro.sim import runner as R
from repro.sim.config import SimConfig

BENCHES = ("3DS", "BLK", "MUM", "HISTO")
SMALL = dict(n_cores=6, warps_per_core=8, epoch_cycles=100)
CYCLES = 300
CONFIG = os.path.join(os.path.dirname(__file__), os.pardir, "perfbench",
                      "configs", "table1.2app.json")


@functools.lru_cache(maxsize=None)
def _table1():
    with open(CONFIG) as f:
        return json.load(f)


def _program(name: str, n_apps: int):
    cfg = SimConfig(n_cores=SMALL["n_cores"],
                    warps_per_core=SMALL["warps_per_core"], n_apps=n_apps,
                    sim_cycles=CYCLES,
                    design=get_design(name).with_(
                        epoch_cycles=SMALL["epoch_cycles"]))
    pm = jnp.asarray(R._mix_matrix(list(BENCHES[:n_apps])))
    return R._stats(cfg, R._compiled_run(cfg)(pm))


def _reference(name: str, n_apps: int):
    cfg = dict(_table1(), n_apps=n_apps, **SMALL)
    rows = reference.app_rows(cfg, BENCHES[:n_apps])[None]
    return reference.stats(cfg, reference.simulate(cfg, name, rows, CYCLES),
                           0)


@pytest.mark.parametrize("n_apps", [1, 2, 3, 4])
@pytest.mark.parametrize("name", PAPER_DESIGNS)
def test_program_equals_reference_float_hex(name, n_apps):
    """program == plain reference, float-hex, 8 designs x 1-4 slots."""
    got = _program(name, n_apps)
    want = _reference(name, n_apps)
    assert set(got) == set(want)
    for k in got:
        hg = [float(v).hex() for v in np.atleast_1d(got[k]).ravel()]
        hw = [float(v).hex() for v in np.atleast_1d(want[k]).ravel()]
        assert hg == hw, (name, n_apps, k)
