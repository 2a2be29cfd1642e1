"""Benchmark harness rules: where the compile cache goes, and that a
failed figure fails the run."""
import sys
from pathlib import Path

import jax
import pytest

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))

from benchmarks import perf, run  # noqa: E402

_CACHE_KEYS = ("jax_compilation_cache_dir",
               "jax_persistent_cache_min_entry_size_bytes",
               "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def jax_cache_config():
    """Restore the process's cache settings after the test."""
    saved = {k: getattr(jax.config, k) for k in _CACHE_KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_compile_cache_fixed_dir_without_env(monkeypatch, jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    used = perf.enable_compilation_cache()
    assert used == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == used


def test_compile_cache_env_setting_left_alone(monkeypatch, tmp_path,
                                              jax_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    used = perf.enable_compilation_cache()
    assert used == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_failed_figure_fails_the_run(monkeypatch, capsys):
    def boom(force=False):
        raise RuntimeError("figure exploded")

    monkeypatch.setattr(run.paper_repro, "ALL",
                        {"ok": lambda force=False: {"x": 1}, "boom": boom})
    monkeypatch.setattr(sys, "argv", ["run.py", "--no-compile-cache"])
    with pytest.raises(SystemExit) as e:
        run.main()
    assert e.value.code not in (0, None)
    assert "boom" in str(e.value.code)
    assert "# ok" in capsys.readouterr().out
