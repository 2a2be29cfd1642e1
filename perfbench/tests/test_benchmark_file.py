"""BENCHMARK.json against the benchmark's contract, and the harness's
refusal to run without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "perfbench/run.py"]
    assert BENCH["paths"] == ["perfbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_check_fits_with_24_cells():
    runs = 2 + 14 * 24
    budget = runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert budget <= 43200


def test_configs_and_cells():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("perfbench/")
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert NAME.match(key) and not key.endswith(("_dim", "_rank"))
    used = set()
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in configs
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (w["config"], w["traffic"]) not in used
        used.add((w["config"], w["traffic"]))
        with open(os.path.join(ROOT, "perfbench", "traffic",
                               w["traffic"] + ".json")) as f:
            spec = json.load(f)
        assert os.path.exists(os.path.join(ROOT, "perfbench", "entries",
                                           spec["entry"] + ".py"))
    assert {w["config"] for w in BENCH["workloads"]} == set(configs)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(len(BENCH["workloads"]) // 2, 1)


def test_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in (
            "host_clock", "device_trace")
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert os.path.exists(os.path.join(ROOT, "perfbench", "metrics",
                                           m["name"] + ".py"))


def test_no_tpu_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "pair.mask.60k",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0 and proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_reports_its_metrics(name):
    e2e = [m for m in BENCH["end_to_end"] if name in m.get("workloads", [name])]
    per = [m for m in BENCH["per_layer"] if name in m.get("workloads", [name])]
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert per


def test_harness_names_no_cell_config_or_layer_metric():
    """Cells, configurations and per-layer metrics are files found by the
    names in BENCHMARK.json: the harness code names none of them."""
    code = ""
    for name in ("run.py", "traffic.py", "compare.py", "control.py",
                 "reference.py", "trace.py"):
        with open(os.path.join(ROOT, "perfbench", name)) as f:
            code += f.read()
    names = ([w["name"] for w in BENCH["workloads"]]
             + [w["traffic"] for w in BENCH["workloads"]]
             + [c["name"] for c in BENCH["configs"]]
             + [m["name"] for m in BENCH["per_layer"]])
    assert [n for n in names if n in code] == []
