"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (`BENCHMARK.json` "workloads") names a configuration file and a
traffic file; the traffic file names the entry module that calls the
simulator. Set-up imports the program, fills what the traffic needs and
makes one call of the timed shape, so every program is compiled (or
loaded from the persistent cache at `<checkout>/.jax_cache`). The window
then calls back to back until `--seconds` have passed and finishes the
call it is in. Afterwards a seeded sample of the window's answers is
compared with the plain reference (`perfbench/compare.py`).

`--trace 0` prints the cell's end-to-end metrics, `--trace 1` its
per-layer metrics, read by `perfbench/metrics/<name>.py` from a profiler
trace of a slice of the window. The last stdout line is one JSON object;
its last key, "compared", holds each compared number beside its limit,
which also end standard error. No TPU, or fewer chips than the cell asks
for, exits non-zero with no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")   # fixed: the path keys the cache
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
TRACE_START_S = 1.0      # the traced slice starts this far into the window


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (cell,) = [w for w in bench["workloads"] if w["name"] == workload]
    (conf,) = [c for c in bench["configs"] if c["name"] == cell["config"]]
    with open(os.path.join(ROOT, conf["file"])) as f:
        cfg = json.load(f)
    with open(os.path.join(HERE, "traffic", cell["traffic"] + ".json")) as f:
        spec = json.load(f)
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return dict(bench=bench, cell=cell, cfg=cfg, spec=spec,
                per_layer=per_layer, end_to_end=end_to_end)


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling (or loading from
    the persistent cache), and how many programs it compiled or loaded."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_misses = 0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
            self.programs += event == COMPILE_EVENTS[-1]

    def count(self, event: str, **kwargs) -> None:
        self.cache_misses += event == "/jax/compilation_cache/cache_misses"


def init_jax(chips: int, allow_cpu: bool):
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    # no eviction: an environment's size cap makes every write read each
    # entry's access-time file, and one entry without it fails all writes
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX found platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return jax, devs


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks))


def traced_window(jax, window, trace_s: float):
    """Run `window` in a worker thread while this thread traces a slice of
    it; returns (window's result, reduced trace context)."""
    out, err = {}, []

    def work():
        try:
            out["r"] = window()
        except BaseException as e:  # handed back to the main thread
            err.append(e)

    from perfbench import trace as tr
    worker = threading.Thread(target=work)
    tmp = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        worker.start()
        time.sleep(TRACE_START_S)
        jax.profiler.start_trace(tmp)
        with jax.profiler.TraceAnnotation(tr.SLICE):
            time.sleep(trace_s)
        jax.profiler.stop_trace()
        worker.join()
        if err:
            raise err[0]
        paths = [os.path.join(d, f) for d, _, fs in os.walk(tmp) for f in fs
                 if f.endswith(".xplane.pb")]
        events = tr.load(paths[0])
    finally:
        worker.join()
        shutil.rmtree(tmp, ignore_errors=True)
    (lo, hi), = tr.spans(events, tr.SLICE)
    return out["r"], dict(events=events, lo=lo, hi=hi,
                          reduced=tr.reduce(events, lo, hi))


def run_cell(argv=None, allow_cpu: bool = False, spec_overrides=None):
    """Run one cell: (the result dict that is printed, the compared
    answers, the cell's configuration). The keyword arguments are for the
    CPU tests: `allow_cpu` skips the look for a chip and `spec_overrides`
    shrinks the traffic."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    spec = dict(cell["spec"], **(spec_overrides or {}))
    chips = cell["cell"]["chips"]
    jax, devs = init_jax(chips, allow_cpu)
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    jax.monitoring.register_event_listener(clock.count)

    import numpy as np
    sys.path[:0] = [p for p in (os.path.join(ROOT, "src"),) if p not in
                    sys.path]
    from repro.sim import runner
    from perfbench import compare
    from perfbench.traffic import Traffic
    entry = load_module(os.path.join(HERE, "entries", spec["entry"] + ".py"),
                        "perfbench_entry_" + spec["entry"])
    ctx = types.SimpleNamespace(cfg=cell["cfg"], spec=spec, runner=runner,
                                state={})
    traffic = Traffic(spec, cell["cfg"], args.seed)

    # ---- set-up: what the traffic needs, then one call of the timed shape.
    # A program that fails here fails again in the window, where it counts.
    try:
        entry.setup(ctx)
        entry.call(ctx, traffic.draw())
    except Exception as e:  # noqa: BLE001
        print(f"# set-up failed: {type(e).__name__}: {e}", file=sys.stderr)
    setup_compile_s = clock.seconds

    # ---- the window
    records, failed = [], [0]

    def window():
        span = (jax.profiler.TraceAnnotation if args.trace
                else contextlib.nullcontext)
        t0 = time.perf_counter()
        while True:
            mixes = traffic.draw()
            try:
                with span("perfbench.call"):
                    res = entry.call(ctx, mixes)
            except Exception as e:  # noqa: BLE001 — a failed call is counted
                print(f"# call failed: {type(e).__name__}: {e}",
                      file=sys.stderr)
                failed[0] += 1
                res = None
            records.append((mixes, res))
            if time.perf_counter() - t0 >= args.seconds:
                return time.perf_counter() - t0

    programs0 = clock.programs
    setup_s = time.perf_counter() - PROCESS_START
    print(f"# set-up {setup_s:.3f} s, of it {setup_compile_s:.3f} s compiling "
          f"or loading {programs0} programs, {clock.cache_misses} of them "
          f"not in the persistent cache", file=sys.stderr)
    traced = None
    if args.trace:
        window_s, traced = traced_window(jax, window, spec["trace_seconds"])
    else:
        window_s = window()
    window_programs = clock.programs - programs0
    used = devs[:chips]
    peak = memory_peak(used)
    done = [(m, r) for m, r in records if r is not None]
    cycles = sum(entry.work(ctx, m, r) for m, r in done)
    print(f"# {len(records)} calls in {window_s:.3f} s, programs compiled "
          f"or loaded inside the window: {window_programs}", file=sys.stderr)

    # ---- correctness: a seeded sample of the window's answers
    rng = np.random.default_rng([args.seed, 1])
    n_check = spec["check"].get("calls", 1)
    picks = (range(len(done)) if n_check == "all" else
             sorted(rng.choice(len(done), min(n_check, len(done)),
                               replace=False)) if done else [])
    answers = [a for i in picks for a in entry.answers(ctx, *done[i], rng)]
    t_ref = time.perf_counter()
    worst, n_values = (compare.check(cell["cfg"], answers, spec["cycles"])
                       if answers else (compare.MISSING, 0))
    print(f"# reference: {time.perf_counter() - t_ref:.3f} s for "
          f"{len(answers)} answers", file=sys.stderr)
    correct = failed[0] == 0 and n_values > 0 and worst <= compare.LIMIT

    # ---- metrics
    if args.trace:
        run = types.SimpleNamespace(trace=traced,
                                    setup_compile_s=setup_compile_s)
        metrics = {}
        for m in cell["per_layer"]:
            reader = load_module(os.path.join(HERE, "metrics",
                                              m["name"] + ".py"),
                                 "perfbench_metric_" + m["name"])
            value = reader.read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = {"sim_cycles_per_s": cycles / window_s, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    result = {"correct": bool(correct), "attempted": len(records),
              "failed": failed[0], "metrics": metrics, "device": device}
    if traced and traced["reduced"]:
        red = traced["reduced"]
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    result["compared"] = {
        "worst_rel_gap": {"value": worst, "limit": compare.LIMIT,
                          "values": n_values}}
    print(f"compared worst_rel_gap {worst!r} limit {compare.LIMIT!r} over "
          f"{n_values} values from {len(answers)} answers", file=sys.stderr)
    return result, answers, cell["cfg"]


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    try:
        result = run_cell()[0]
    except NoChip as e:
        print(f"perfbench: {e}", file=sys.stderr)
        sys.exit(2)
    print(json.dumps(result), flush=True)
