"""Design-registry coverage: hashability, jit-compilability over n_apps,
custom-design registration, and compile-cache isolation.

The jit grid below (every registered design x n_apps in {1, 2, 3}) uses a
small SimConfig: compile time is graph-size bound, not array-size bound,
so the small config proves the same pipeline specialization cheaply.
"""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.design import (PAPER_DESIGNS, Design, TokenSpec, as_design,
                               get_design, list_designs, register_design)
from repro.sim import runner
from repro.sim.config import SimConfig
from repro.sim.runner import Experiment
from repro.sim.workloads import app_matrix

SMALL = dict(n_cores=6, warps_per_core=2, sim_cycles=64)
BENCHES3 = ["3DS", "BLK", "MUM"]


def _small_run(design: Design, n_apps: int):
    cfg = SimConfig(n_apps=n_apps, design=design, **SMALL)
    pm = jnp.asarray(app_matrix(BENCHES3[:n_apps]))
    return runner._stats(cfg, runner._compiled_run(cfg)(pm))


# ---------------------------------------------------------------- registry

def test_builtins_registered():
    names = list_designs()
    for n in PAPER_DESIGNS:
        assert n in names
        assert get_design(n).name == n
    with pytest.raises(KeyError):
        get_design("no-such-design")


def test_designs_hashable_frozen_distinct():
    ds = [get_design(n) for n in PAPER_DESIGNS]
    assert len({hash(d) for d in ds}) >= 2     # hashable at all
    assert len(set(ds)) == len(ds)             # all distinct by value
    with pytest.raises(dataclasses.FrozenInstanceError):
        ds[0].name = "nope"
    with pytest.raises(dataclasses.FrozenInstanceError):
        ds[0].tokens.enabled = True


def test_with_nested_merge():
    mask = get_design("mask")
    mine = mask.with_(name="t-lean", tokens=dict(initial_frac=0.1),
                      bypass=dict(enabled=False))
    assert mine.tokens == TokenSpec(enabled=True, initial_frac=0.1,
                                    step_frac=0.5, bypass_cache_entries=32)
    assert not mine.bypass.enabled
    assert mine.dram == mask.dram              # untouched layers carry over
    assert mask.tokens.initial_frac == 0.25    # original untouched
    # replace is an alias; spec instances are accepted too
    assert mine.replace(tokens=TokenSpec()) == mine.with_(tokens=TokenSpec())
    with pytest.raises(TypeError):
        mask.with_(no_such_layer=dict())


def test_register_collision_semantics():
    d1 = get_design("mask").with_(name="t-collide")
    d2 = d1.with_(tokens=dict(initial_frac=0.9))
    register_design(d1)
    register_design(d1)                        # identical re-register: ok
    with pytest.raises(ValueError):
        register_design(d2)                    # same name, different specs
    register_design(d2, overwrite=True)
    assert get_design("t-collide") == d2


def test_as_design_accepts_names_and_designs():
    """A registered name resolves to the registered design, a Design
    passes through as it is, and anything else is refused."""
    for n in PAPER_DESIGNS:
        assert as_design(n) == get_design(n)
        assert as_design(get_design(n)) is get_design(n)
    with pytest.raises(TypeError):
        as_design(42)


# ------------------------------------------------------------- jit grid

@pytest.mark.parametrize("name", PAPER_DESIGNS)
def test_every_design_compiles_and_is_finite(name):
    """Each registered design compiles under jit for n_apps in {1, 2, 3}
    and yields finite stats."""
    d = get_design(name)
    for n_apps in (1, 2, 3):
        s = _small_run(d, n_apps)
        assert s["ipc"].shape == (n_apps,)
        for k, v in s.items():
            arr = np.asarray(v, np.float64)
            assert np.all(np.isfinite(arr)), (name, n_apps, k)


# ------------------------------------------------- compile-cache isolation

def test_same_name_designs_do_not_collide_in_compile_cache():
    """Two distinct designs sharing a name must key separate run
    callables (the cache hashes every spec field, not the name). Since
    the static/traced split they may SHARE the underlying executable —
    their differing knobs ride in the traced DesignParams — so the
    observable check below (distinct token budgets) is the load-bearing
    one."""
    a = get_design("mask").with_(name="t-dup", tokens=dict(initial_frac=0.25))
    b = get_design("mask").with_(name="t-dup", tokens=dict(initial_frac=0.75))
    assert a != b and hash(SimConfig(design=a)) != hash(SimConfig(design=b))
    cfg_a = SimConfig(n_apps=2, design=a, **SMALL)
    cfg_b = SimConfig(n_apps=2, design=b, **SMALL)
    assert runner._compiled_run(cfg_a) is runner._compiled_run(cfg_a)
    assert runner._compiled_run(cfg_a) is not runner._compiled_run(cfg_b)
    # observable separation: initial token budgets differ (no epoch at 64
    # cycles), so a stale shared executable would be caught here
    sa, sb = _small_run(a, 2), _small_run(b, 2)
    warps = SMALL["n_cores"] // 2 * SMALL["warps_per_core"]
    assert sa["tokens"].tolist() == [int(warps * 0.25)] * 2
    assert sb["tokens"].tolist() == [int(warps * 0.75)] * 2


# ------------------------------------------- custom design via Experiment

def test_custom_design_through_experiment():
    """Acceptance: a user-defined design (MASK with a different
    initial_token_frac and bypass disabled) registers and runs through
    Experiment without touching repro.sim/repro.core internals."""
    custom = register_design(
        get_design("mask").with_(name="t-mask-custom",
                                 tokens=dict(initial_frac=0.5),
                                 bypass=dict(enabled=False)))
    res = Experiment("t-mask-custom", [("3DS", "BLK")], cycles=64).run()
    r = res[0]
    assert r.design == custom
    assert np.isfinite(r.weighted_speedup())
    assert np.isfinite(r.unfairness())
    a = r.app("3DS")
    assert a.ipc_alone is not None and a.ipc > 0
    # full-size config: 30 cores / 2 apps -> 480 warps/app; frac 0.5 and
    # no epoch boundary before cycle 64 means tokens stay at 240
    assert a.tokens == 240
