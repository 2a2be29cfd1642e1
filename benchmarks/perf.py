"""The persistent JAX compilation cache for this repository's scripts
(`benchmarks/run.py`, `chip_smoke.py`).

Simulator throughput on the chip is measured by the benchmark in
`perfbench/` (`python3 perfbench/run.py`, see PERF.md).
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_ROOT = Path(__file__).resolve().parent.parent
CACHE_DIR = REPO_ROOT / ".jax_cache"


def enable_compilation_cache(cache_dir: Path = CACHE_DIR) -> str:
    """Enable JAX's persistent compilation cache so repeated invocations
    skip recompiles (opt out with --no-compile-cache; see README
    "Persistent compilation cache"); returns the directory in use.

    Where `JAX_COMPILATION_CACHE_DIR` is set, JAX has taken its directory
    from it and that setting is left alone. Otherwise the cache goes to
    the fixed `cache_dir`: never a path made from a temporary name, a
    process id or the time, or later runs would never hit it."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env_dir:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    # cache every entry, however small/fast — sim compiles are the cost
    # (0, not the default 1s: CI-smoke-scale programs compile sub-second)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return env_dir or str(cache_dir)
