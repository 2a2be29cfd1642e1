"""Drive a benchmark cell on the CPU at a tiny size, whole or with the
timed path broken underneath, and return its result line.

The faults are planted in the program's runner, where the timed path
runs, and each should make `correct` come out false:

  state_unchanged  the simulated cycle returns its state unchanged
  half_batch       the grid computes the first half of its rows; the rest
                   repeat them
  answer_altered   every statistics dict is altered by one unit in the
                   last place of its first app's IPC
"""
from __future__ import annotations

import contextlib
import tempfile

import jax
import numpy as np

PROGRAM_CACHES = ("_compiled_sig_run", "_compiled_sig_batch_run",
                  "_compiled_grid_run", "_compiled_seg_run", "_compiled_run",
                  "_compiled_batch_run")


@contextlib.contextmanager
def planted(fault):
    """Plant `fault` (or nothing, for None) in the runner; every compiled
    program is dropped before and after, so the fault is traced in."""
    from repro.sim import runner
    saved = {n: getattr(runner, n) for n in ("step", "_compiled_grid_run",
                                              "_stats")}

    def drop():
        for name in PROGRAM_CACHES:
            getattr(runner, name).cache_clear()
        saved["_compiled_grid_run"].cache_clear()

    drop()
    if fault == "state_unchanged":
        runner.step = lambda cfg, dp, params, state: state
    elif fault == "half_batch":
        real = saved["_compiled_grid_run"]

        def grid(ccfg):
            fn = real(ccfg)

            def broken(dp, pm):
                rows = pm.shape[0]
                half = max(rows // 2, 1)
                out = jax.device_get(fn(*jax.tree_util.tree_map(
                    lambda x: x[:half], (dp, pm))))
                return jax.tree_util.tree_map(
                    lambda x: np.concatenate([x, x[:rows - half]]), out)
            return broken
        runner._compiled_grid_run = grid
    elif fault == "answer_altered":
        real_stats = saved["_stats"]

        def stats(cfg, st, audit=None):
            s = real_stats(cfg, st, audit)
            s["ipc"] = s["ipc"].copy()
            s["ipc"][0] = np.nextafter(s["ipc"][0], np.inf)
            return s
        runner._stats = stats
    elif fault is not None:
        raise ValueError(fault)
    try:
        yield
    finally:
        for name, value in saved.items():
            setattr(runner, name, value)
        drop()


@contextlib.contextmanager
def own_cache():
    """Point the harness's persistent compile cache at a directory of this
    process's own, and leave JAX's cache as it was found afterwards."""
    from jax.experimental.compilation_cache import compilation_cache

    from perfbench import run
    saved = run.CACHE_DIR
    with tempfile.TemporaryDirectory() as tmp:
        run.CACHE_DIR = tmp
        try:
            yield
        finally:
            run.CACHE_DIR = saved
            jax.config.update("jax_compilation_cache_dir", None)
            compilation_cache.reset_cache()


def run_cell(workload, seed, cycles, fault=None):
    """The result line of one tiny CPU run of `workload` with `fault`."""
    from perfbench import run
    with own_cache(), planted(fault):
        return run.run_cell(
            ["--workload", workload, "--seed", str(seed), "--seconds",
             "0.01", "--trace", "0"],
            allow_cpu=True, spec_overrides={"cycles": cycles})[0]
