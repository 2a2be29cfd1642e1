"""`chip_smoke.py`: refuses to run without a TPU, and its phases pass
when rehearsed on the CPU at a tiny size.

The smoke runs as a child process on the CPU (`JAX_PLATFORMS=cpu`), so it
never competes with this process for an accelerator.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _run(args, cwd=REPO, timeout=900, **env_extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_extra)
    return subprocess.run([sys.executable, str(cwd / "chip_smoke.py"),
                           *args], cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def _result(out: str):
    """The parsed last stdout line when it is the smoke's result, else
    None."""
    lines = out.strip().splitlines()
    if not lines or not lines[-1].startswith("{"):
        return None
    return json.loads(lines[-1])


def test_smoke_fails_without_accelerator():
    p = _run([])
    assert p.returncode != 0
    assert _result(p.stdout) is None, p.stdout[-2000:]
    assert "no TPU" in p.stderr, p.stderr[-2000:]


def test_smoke_fails_outside_the_repo(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    p = _run([], cwd=tmp_path)
    assert p.returncode != 0
    assert _result(p.stdout) is None, p.stdout[-2000:]


@pytest.mark.multi_device
@pytest.mark.parametrize("chips", [1, 4])
def test_smoke_cpu_rehearsal(chips, tmp_path):
    """Every phase at a tiny size on the CPU (four forced host devices for
    the sharded path); the compile cache lands where
    JAX_COMPILATION_CACHE_DIR says."""
    cache = tmp_path / "jax_cache"
    extra = {"JAX_COMPILATION_CACHE_DIR": str(cache)}
    if chips > 1:
        extra["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    p = _run(["--cpu-rehearsal", "--chips", str(chips)], **extra)
    assert p.returncode == 0, (p.stdout[-3000:], p.stderr[-3000:])
    assert _result(p.stdout) == {"ok": True, "device": {
        "platform": "cpu", "kind": "cpu", "count": chips}}
    phases = [ln.split("]")[0] + "]" for ln in p.stdout.splitlines()
              if ln.startswith("[")]
    want = ["[1 device]", "[sharded]"] if chips > 1 else [
        "[1 device]", "[2 goldens]", "[3 paper]", "[4 churn]", "[5 serving]"]
    assert sorted(set(phases)) == want
    assert f"# compile cache: {cache}" in p.stdout
    assert any(cache.iterdir())
