"""Simulator throughput microbenchmark -> BENCH_sim.json.

Measures steps/sec of the compiled one-cycle pipeline in four shapes:

  2app    — one 2-app mix (the paper's pair setting)
  4app    — one 4-app mix (N-way sharing)
  batch8  — eight 2-app mixes vmapped through one executable
  churn   — the same 2-app mix run through the SEGMENTED runner
            (`run_trace`, K=4 epoch-aligned segments, constant
            membership) so the scenario's work is identical to a
            monolithic run of the same total cycles: its rate vs
            `2app` — and its `--compare` ratio against a
            pre-segmentation baseline tree, which falls back to the
            monolithic `run_mix` of the same workload — isolates the
            segmentation overhead (per-boundary state round-trip +
            host-side snapshot), honestly, rather than timing a
            different workload
  grid    — the full 8-design x 2-mix ablation sweep at the sweep-
            iteration scale (min(--cycles, GRID_CYCLES) cycles): one
            compiled, vmapped grid execution per static-signature group
            (two for the paper designs); on trees without the grid path
            it falls back to the per-design loop. Under `--compare`
            this scenario is timed END-TO-END from cold — compile +
            execute at a fresh cycle count per round — because the
            sweep's dominant cost at this scale is its XLA compiles (8
            programs pre-vectorization vs one per signature group)

With `--devices N` (N > 1) a fifth scenario rides along:

  grid_sharded — the grid sweep with its stacked rows sharded over N
            devices (runner `_row_sharding`/`_pad_rows`); if fewer
            devices are visible on the CPU the benchmark re-executes
            itself with `--xla_force_host_platform_device_count=N`, and
            on an accelerator it stops with an error. Under
            `--compare` it is timed cold like `grid`, new-side sharded
            vs old-side single-device, at a disjoint cycle count so
            neither side reuses the `grid` round's compiles.

`--tlb-backend {xla,pallas,pallas-interpret}` selects the fused
shared-round backend for the current tree (SimConfig.tlb_backend; all
backends are bit-for-bit identical, see tests/test_tlb_backends.py).

The scenarios are interleaved round-robin inside ONE process and
the median per-scenario rate is reported: this box's absolute throughput
drifts with neighbor load, so sequential before/after blocks are not
comparable — interleaving keeps the scenarios under the same drift, and
the recorded JSON gives future PRs a perf trajectory (compare ratios
between scenarios / versions, not absolute steps/sec across days).

`--compare <git-ref>` is the honest A/B protocol for the same reason:
the baseline tree is materialized from git into a renamed `repro_base`
package, both versions are compiled into THIS process, and each round
times them back-to-back (pair-by-pair) so neighbor drift hits both
sides equally; the reported number is the median new/old speedup per
scenario, never a cross-run absolute.

Compiles are cached persistently in `$JAX_COMPILATION_CACHE_DIR` when
that is set, else under `.jax_cache/` (repo root), so repeated
invocations skip XLA recompiles; disable with `--no-compile-cache`.
`--compare` removes its materialized baseline tree on exit unless
`--keep-baseline`.

Run:  PYTHONPATH=src python -m benchmarks.perf [--cycles N] [--rounds R]
      PYTHONPATH=src python -m benchmarks.perf --compare HEAD
"""
from __future__ import annotations

import argparse
import atexit
import dataclasses
import importlib
import json
import os
import platform
import re
import shutil
import subprocess
import sys
import tarfile
import time
from io import BytesIO
from pathlib import Path

import jax
import numpy as np

REPO_ROOT = Path(__file__).resolve().parent.parent
OUT_PATH = REPO_ROOT / "BENCH_sim.json"
COMPARE_DIR = REPO_ROOT / ".bench_compare"
CACHE_DIR = REPO_ROOT / ".jax_cache"
_IMPORT_RE = re.compile(r"^(\s*(?:from|import)\s+)repro(?=[.\s])",
                        re.MULTILINE)
GRID_N_MIXES = 2     # grid scenario: all 8 paper designs x this many pairs
# The grid scenario runs at min(--cycles, GRID_CYCLES): it benchmarks the
# sweep-harness shape that design-vectorization targets — short iterative
# sweeps (CI smoke, test goldens, dev loops) where the 8-vs-2 XLA compiles
# dominate wall time. At paper scale (60K cycles) a sweep is
# execution-bound and the vmapped grid is execution-neutral on this box
# (flat per-sim batch scaling, measured G=2..14; see README), so the
# saving there is the fixed compile time, not a proportional factor.
GRID_CYCLES = 2_000
CHURN_SEGMENTS = 4   # churn scenario: K segments of cycles/K each
# Subprocess guard rails: a wedged `git` (e.g. a lock held by another
# process) or a hung re-exec child must fail the benchmark loudly, not
# hang CI forever. Generous on purpose — these bound pathology, they are
# not performance budgets.
GIT_TIMEOUT_S = 120
REEXEC_TIMEOUT_S = 4 * 3600


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


def _mk_cfg(config_mod, **kw):
    """SimConfig for `config_mod`, dropping kwargs the tree predates
    (e.g. `tlb_backend` does not exist on pre-PR-6 baseline copies)."""
    fields = {f.name for f in dataclasses.fields(config_mod.SimConfig)}
    return config_mod.SimConfig(**{k: v for k, v in kw.items()
                                   if k in fields})


def _signature_groups(pkg: str = "repro"):
    """Count of static-signature groups over the paper's 8 designs, or
    None for trees that predate the static/traced design split."""
    design_mod = importlib.import_module(pkg + ".core.design")
    mask_mod = importlib.import_module(pkg + ".core.mask")
    if not hasattr(design_mod, "static_signature"):
        return None
    return len({design_mod.static_signature(design_mod.get_design(n))
                for n in mask_mod.ALL_DESIGNS})


def _scenarios(design: str, cycles: int, pkg: str = "repro",
               include_grid: bool = True, tlb_backend: str = "xla",
               devices: int = 0):
    """name -> (zero-arg compiled call, sim-steps per call).

    `pkg` selects the simulator package ("repro" or a baseline copy such
    as "repro_base") so two versions can be timed in one process.
    `include_grid=False` skips building the grid scenarios (the compare
    harness times grid sweeps cold via `_grid_sweep` instead).
    `tlb_backend` selects the fused-round backend on trees that have the
    knob (silently dropped on older baseline copies, which ARE the xla
    path). `devices > 1` adds a `grid_sharded` scenario: the same sweep
    with its rows sharded over that many devices.
    """
    import jax.numpy as jnp
    config_mod = importlib.import_module(pkg + ".sim.config")
    runner_mod = importlib.import_module(pkg + ".sim.runner")
    workloads_mod = importlib.import_module(pkg + ".sim.workloads")
    design_mod = importlib.import_module(pkg + ".core.design")
    d = design_mod.get_design(design)

    def single(benches):
        cfg = _mk_cfg(config_mod, n_apps=len(benches), sim_cycles=cycles,
                      design=d, tlb_backend=tlb_backend)
        pm = jnp.asarray(runner_mod._mix_matrix(benches))
        fn = runner_mod._compiled_run(cfg)
        return (lambda: jax.block_until_ready(fn(pm))), cycles

    def batch(mixes):
        cfg = _mk_cfg(config_mod, n_apps=len(mixes[0]), sim_cycles=cycles,
                      design=d, tlb_backend=tlb_backend)
        pm = jnp.asarray(np.stack([runner_mod._mix_matrix(m)
                                   for m in mixes]))
        fn = runner_mod._compiled_batch_run(cfg)
        return (lambda: jax.block_until_ready(fn(pm))), cycles * len(mixes)

    def churn():
        """Segmented runner over the 2app workload (constant membership,
        K = CHURN_SEGMENTS segments). On trees that predate `run_trace`
        the MONOLITHIC `run_mix` of the same total cycles stands in, so
        a --compare ratio measures segmentation overhead on identical
        work. Runs the tree's default TLB backend (run_trace owns its
        SimConfig)."""
        segc = max(1, cycles // CHURN_SEGMENTS)
        total = segc * CHURN_SEGMENTS
        mix = ("3DS", "BLK")
        if hasattr(runner_mod, "run_trace"):
            call = (lambda: runner_mod.run_trace(
                design, [mix] * CHURN_SEGMENTS, seg_cycles=segc,
                collect_segments=False))
        else:
            call = (lambda: runner_mod.run_mix(design, list(mix),
                                               cycles=total))
        return call, total

    mix4 = workloads_mod.mix_workloads(seed=7, n_mixes=1, n_apps=4)[0]
    scen = {
        "2app": single(["3DS", "BLK"]),
        "4app": single(list(mix4)),
        "batch8": batch(workloads_mod.pair_workloads()[:8]),
        "churn": churn(),
    }
    if include_grid:
        scen["grid"] = _grid_sweep(pkg, min(cycles, GRID_CYCLES),
                                   tlb_backend)
        if devices and devices > 1:
            scen["grid_sharded"] = _grid_sweep(pkg, min(cycles, GRID_CYCLES),
                                               tlb_backend, devices)
    return scen


def _grid_sweep(pkg: str, cycles: int, tlb_backend: str = "xla",
                devices: int = 0):
    """The paper's 8-design ablation sweep over GRID_N_MIXES pairs:
    (zero-arg call, sim-steps). The call compiles lazily on first use,
    so timing a FRESH `cycles` value measures the sweep end-to-end
    (compile + execute) — the compare harness exploits this.

    On grid-capable trees: one vmapped execution per signature group.
    On older trees: the per-design loop (one vmapped mix batch per
    design) — the honest pre-vectorization sweep shape. Both run the
    identical designs x mixes work. `devices > 1` shards each group's
    rows over that many devices (runner `_row_sharding`/`_pad_rows`;
    requires a sharding-capable tree)."""
    import jax.numpy as jnp
    config_mod = importlib.import_module(pkg + ".sim.config")
    runner_mod = importlib.import_module(pkg + ".sim.runner")
    workloads_mod = importlib.import_module(pkg + ".sim.workloads")
    design_mod = importlib.import_module(pkg + ".core.design")
    mask_mod = importlib.import_module(pkg + ".core.mask")
    if devices and devices > 1 and not hasattr(runner_mod, "_row_sharding"):
        raise ValueError(f"{pkg} tree has no sharded grid support")

    names = list(mask_mod.ALL_DESIGNS)
    mixes = workloads_mod.pair_workloads()[:GRID_N_MIXES]
    steps = cycles * len(names) * len(mixes)
    pms = np.stack([runner_mod._mix_matrix(list(m)) for m in mixes])
    calls = []
    if hasattr(runner_mod, "_compiled_grid_run"):
        groups = {}
        for n in names:
            dd = design_mod.get_design(n)
            groups.setdefault(design_mod.static_signature(dd),
                              []).append(dd)
        for sig, gds in groups.items():
            ccfg = _mk_cfg(config_mod, n_apps=2, sim_cycles=cycles,
                           design=design_mod.canonical_design(sig),
                           tlb_backend=tlb_backend)
            dp_stack = jax.tree_util.tree_map(
                lambda *leaves: jnp.repeat(jnp.stack(leaves),
                                           len(mixes), axis=0),
                *[design_mod.design_params(dd) for dd in gds])
            pm_stack = jnp.asarray(np.tile(pms, (len(gds), 1, 1)))
            if devices and devices > 1:
                sharding = runner_mod._row_sharding(devices)
                (dp_stack, pm_stack), _ = runner_mod._pad_rows(
                    (dp_stack, pm_stack), devices)
                dp_stack, pm_stack = jax.device_put(
                    (dp_stack, pm_stack), sharding)
            fn = runner_mod._compiled_grid_run(ccfg)
            calls.append((fn, (dp_stack, pm_stack)))
    else:
        for n in names:
            cfg = _mk_cfg(config_mod, n_apps=2, sim_cycles=cycles,
                          design=design_mod.get_design(n),
                          tlb_backend=tlb_backend)
            calls.append((runner_mod._compiled_batch_run(cfg),
                          (jnp.asarray(pms),)))
    return (lambda: [jax.block_until_ready(fn(*args))
                     for fn, args in calls]), steps


# ---------------------------------------------------------------------------
# baseline materialization for --compare
# ---------------------------------------------------------------------------

def _materialize_baseline(ref: str) -> str:
    """Extract src/repro at `ref` into .bench_compare/<sha>/src/repro_base
    (imports rewritten), put it on sys.path, and return the resolved sha."""
    sha = subprocess.run(["git", "rev-parse", ref], cwd=REPO_ROOT,
                         capture_output=True, text=True,
                         check=True, timeout=GIT_TIMEOUT_S).stdout.strip()
    dest = COMPARE_DIR / sha[:12]
    pkg_dir = dest / "src" / "repro_base"
    if not pkg_dir.exists():
        # stage into a temp dir and rename into place only when fully
        # rewritten — a half-rewritten cached baseline would silently
        # import the CURRENT `repro` modules and fake a ~1.0x ratio
        shutil.rmtree(dest, ignore_errors=True)
        tmp = COMPARE_DIR / (dest.name + ".tmp")
        shutil.rmtree(tmp, ignore_errors=True)
        tar_bytes = subprocess.run(
            ["git", "archive", "--format=tar", sha, "src/repro"],
            cwd=REPO_ROOT, capture_output=True, check=True,
            timeout=GIT_TIMEOUT_S).stdout
        with tarfile.open(fileobj=BytesIO(tar_bytes)) as tf:
            tf.extractall(tmp, filter="data")
        (tmp / "src" / "repro").rename(tmp / "src" / "repro_base")
        for py in (tmp / "src" / "repro_base").rglob("*.py"):
            py.write_text(_IMPORT_RE.sub(r"\1repro_base", py.read_text()))
        tmp.rename(dest)
    path = str(dest / "src")
    if path not in sys.path:
        sys.path.insert(0, path)
    mod = importlib.import_module("repro_base.sim.runner")
    assert mod.__file__.startswith(str(dest)), mod.__file__
    return sha


def run_compare(ref: str, design: str = "mask", cycles: int = 8_000,
                rounds: int = 5, out_path: Path = OUT_PATH,
                keep_baseline: bool = False, tlb_backend: str = "xla",
                devices: int = 0) -> dict:
    """Interleaved A/B: current tree vs the committed tree at `ref`.

    Each round times (new, old) back-to-back per scenario; the headline
    number is the median over rounds of old_time / new_time (>1 means
    the working tree is faster).

    The warm scenarios (2app/4app/batch8) time pre-compiled execution.
    The `grid` scenario instead times the 8-design sweep END-TO-END —
    compile + execute, at a fresh cycle count every round so neither
    side can reuse a compiled program — because the sweep's real cost
    includes its XLA compiles (8 programs pre-vectorization, one per
    signature group after). With `devices > 1` a `grid_sharded` round
    rides along: the NEW side shards the sweep's rows over the devices,
    the OLD side runs its plain single-device sweep, both cold at a
    cycle count distinct from the `grid` round's (so neither side can
    reuse those compiles). The persistent compilation cache is disabled
    for the whole compare run for the same reason. The materialized
    baseline tree under `.bench_compare/` is removed on exit unless
    `keep_baseline` — guaranteed even on a crash: removal is registered
    with atexit BEFORE the baseline is materialized, so an unhandled
    exception (or plain sys.exit) anywhere in the run still cleans up;
    the `finally` below only makes it prompt."""
    if not keep_baseline:
        atexit.register(shutil.rmtree, COMPARE_DIR, ignore_errors=True)
    try:
        sha = _materialize_baseline(ref)
        jax.config.update("jax_compilation_cache_dir", None)
        print("# persistent compilation cache disabled for --compare "
              "(grid rounds time cold compiles)", flush=True)
        scen_new = _scenarios(design, cycles, "repro", include_grid=False,
                              tlb_backend=tlb_backend)
        scen_old = _scenarios(design, cycles, "repro_base",
                              include_grid=False)
        warm_names = list(scen_new)
        for name in warm_names:            # compile + warm both sides
            for tag, scen in (("new", scen_new), ("old", scen_old)):
                t0 = time.perf_counter()
                scen[name][0]()
                print(f"# warm {name}/{tag}: "
                      f"{time.perf_counter() - t0:.1f}s", flush=True)

        names = warm_names + ["grid"]
        if devices and devices > 1:
            names.append("grid_sharded")
        ratios = {name: [] for name in names}
        rates = {name: {"new": [], "old": []} for name in names}
        for r in range(rounds):
            for name in warm_names:
                call_new, steps = scen_new[name]
                call_old, _ = scen_old[name]
                t0 = time.perf_counter()
                call_new()
                t_new = time.perf_counter() - t0
                t0 = time.perf_counter()
                call_old()
                t_old = time.perf_counter() - t0
                ratios[name].append(t_old / t_new)
                rates[name]["new"].append(steps / t_new)
                rates[name]["old"].append(steps / t_old)
            # grid: cold end-to-end sweep, fresh cycles -> fresh compiles
            gc = min(cycles, GRID_CYCLES) + r + 1
            call_new, gsteps = _grid_sweep("repro", gc, tlb_backend)
            call_old, _ = _grid_sweep("repro_base", gc)
            t0 = time.perf_counter()
            call_new()
            t_new = time.perf_counter() - t0
            t0 = time.perf_counter()
            call_old()
            t_old = time.perf_counter() - t0
            ratios["grid"].append(t_old / t_new)
            rates["grid"]["new"].append(gsteps / t_new)
            rates["grid"]["old"].append(gsteps / t_old)
            print(f"# compare round {r + 1}/{rounds} done "
                  f"(grid cold: new {t_new:.1f}s old {t_old:.1f}s)",
                  flush=True)
            if devices and devices > 1:
                # sharded pair at a cycle count disjoint from the grid
                # round's range, so neither side reuses those compiles:
                # new = rows sharded over `devices`, old = the baseline
                # tree's single-device vmapped sweep
                gs = min(cycles, GRID_CYCLES) + 1_000 + r
                call_new, ssteps = _grid_sweep("repro", gs, tlb_backend,
                                               devices)
                call_old, _ = _grid_sweep("repro_base", gs)
                t0 = time.perf_counter()
                call_new()
                t_new = time.perf_counter() - t0
                t0 = time.perf_counter()
                call_old()
                t_old = time.perf_counter() - t0
                ratios["grid_sharded"].append(t_old / t_new)
                rates["grid_sharded"]["new"].append(ssteps / t_new)
                rates["grid_sharded"]["old"].append(ssteps / t_old)
                print(f"# compare round {r + 1}/{rounds} sharded "
                      f"(cold: new {t_new:.1f}s old {t_old:.1f}s)",
                      flush=True)

        result = _measure_report(design, cycles, rounds,
                                 {n: rates[n]["new"] for n in rates},
                                 tlb_backend=tlb_backend, devices=devices)
        result["compare"] = {
            "ref": ref,
            "sha": sha,
            "speedup": {n: float(np.median(v)) for n, v in ratios.items()},
            "ratio_samples": {n: [float(x) for x in v]
                              for n, v in ratios.items()},
            "baseline_steps_per_sec": {n: float(np.median(rates[n]["old"]))
                                       for n in rates},
            "baseline_signature_groups": _signature_groups("repro_base"),
            "grid_timing": "cold end-to-end sweep (compile + execute, "
                           "fresh cycle count per round)",
        }
        out_path.write_text(json.dumps(result, indent=2) + "\n")
        print(json.dumps(
            {"design": design, "cycles": cycles,
             "steps_per_sec": result["steps_per_sec"],
             "speedup_vs_" + sha[:8]: result["compare"]["speedup"]},
            indent=2))
        print(f"# wrote {out_path}")
        return result
    finally:
        if not keep_baseline:
            shutil.rmtree(COMPARE_DIR, ignore_errors=True)
            print(f"# removed {COMPARE_DIR} (use --keep-baseline to keep)",
                  flush=True)


def _measure_report(design, cycles, rounds, samples, tlb_backend="xla",
                    devices=0) -> dict:
    return {
        "design": design,
        "cycles": cycles,
        "rounds": rounds,
        "steps_per_sec": {n: float(np.median(v)) for n, v in samples.items()},
        "samples": {n: [float(x) for x in v] for n, v in samples.items()},
        "meta": {
            "jax": jax.__version__,
            "jax_version": jax.__version__,
            "platform": platform.platform(),
            "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "device_count": jax.device_count(),
            "tlb_backend": tlb_backend,
            "devices": devices if devices and devices > 1 else 1,
            # compiled programs for the grid scenario's 8-design sweep
            "signature_groups": _signature_groups("repro"),
        },
    }


def run_bench(design: str = "mask", cycles: int = 8_000, rounds: int = 5,
              out_path: Path = OUT_PATH, tlb_backend: str = "xla",
              devices: int = 0) -> dict:
    scen = _scenarios(design, cycles, tlb_backend=tlb_backend,
                      devices=devices)
    for name, (call, _) in scen.items():   # compile + warm
        t0 = time.perf_counter()
        call()
        print(f"# warm {name}: {time.perf_counter() - t0:.1f}s", flush=True)

    samples = {name: [] for name in scen}
    for r in range(rounds):                # interleaved measurement
        for name, (call, steps) in scen.items():
            t0 = time.perf_counter()
            call()
            dt = time.perf_counter() - t0
            samples[name].append(steps / dt)
        print(f"# round {r + 1}/{rounds} done", flush=True)

    result = _measure_report(design, cycles, rounds, samples,
                             tlb_backend=tlb_backend, devices=devices)
    out_path.write_text(json.dumps(result, indent=2) + "\n")
    print(json.dumps({k: result[k] for k in ("design", "cycles",
                                             "steps_per_sec")}, indent=2))
    print(f"# wrote {out_path}")
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--design", default="mask")
    ap.add_argument("--cycles", type=int, default=8_000)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--out", type=Path, default=OUT_PATH)
    ap.add_argument("--compare", metavar="GIT_REF", default=None,
                    help="interleave against the committed tree at GIT_REF "
                         "and report median new/old speedups")
    ap.add_argument("--keep-baseline", action="store_true",
                    help="keep the materialized .bench_compare/ baseline "
                         "tree after --compare (default: removed on exit)")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent JAX compilation cache "
                         "(default: $JAX_COMPILATION_CACHE_DIR, else "
                         ".jax_cache/)")
    ap.add_argument("--devices", type=int, default=0,
                    help="shard the grid sweep's rows over N devices "
                         "(adds the grid_sharded scenario); on a CPU host "
                         "with fewer visible devices the benchmark "
                         "re-executes itself with "
                         "--xla_force_host_platform_device_count=N, on "
                         "an accelerator it fails")
    ap.add_argument("--tlb-backend", default="xla",
                    choices=["xla", "pallas", "pallas-interpret"],
                    help="fused shared-round backend for the current tree "
                         "(baseline copies under --compare always run "
                         "their own default path)")
    args = ap.parse_args()
    if args.devices > 1 and jax.device_count() < args.devices:
        if jax.default_backend() != "cpu":
            # this process holds the accelerator now: a child could not
            # get it, and forced host devices exist only on the CPU
            raise SystemExit(
                f"--devices {args.devices}: only {jax.device_count()} "
                f"{jax.default_backend()} devices visible (fewer devices "
                "than --devices)")
        # the device-count flag must be set before the backend exists, so
        # re-exec into a child that sees the forced host devices
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}"
        ).strip()
        print(f"# re-executing with {args.devices} forced host devices",
              flush=True)
        try:
            raise SystemExit(subprocess.call(
                [sys.executable, "-m", "benchmarks.perf", *sys.argv[1:]],
                env=env, cwd=REPO_ROOT, timeout=REEXEC_TIMEOUT_S))
        except subprocess.TimeoutExpired:
            # subprocess.call kills the child on expiry; surface it as
            # the conventional timeout exit code instead of hanging CI
            print(f"# re-executed benchmark exceeded {REEXEC_TIMEOUT_S}s "
                  "and was killed", file=sys.stderr, flush=True)
            raise SystemExit(124)
    if not args.no_compile_cache:
        enable_compilation_cache()
    if args.compare:
        run_compare(args.compare, args.design, args.cycles, args.rounds,
                    args.out, keep_baseline=args.keep_baseline,
                    tlb_backend=args.tlb_backend, devices=args.devices)
    else:
        run_bench(args.design, args.cycles, args.rounds, args.out,
                  tlb_backend=args.tlb_backend, devices=args.devices)


if __name__ == "__main__":
    main()
