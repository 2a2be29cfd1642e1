"""Bring-up smoke: the simulator and the serving loop on one TPU chip.

Drives both hot paths once through the entry points a user calls, at the
paper's Table 1 GPU size (30 cores x 32 warps, 2 MB 16-way L2$, 512-entry
shared L2 TLB, 8x8 DRAM banks):

  1 device   a TPU must be present; nothing falls back to the CPU
  2 goldens  the nine float-hex goldens (`repro.sim.golden`) on the chip
  3 paper    `run_mix` at 60 000 cycles, then the 8-design sweep over two
             pairs through the grid path; compile and run timed apart
  4 churn    `run_trace` with K=4 constant-membership segments equals the
             monolithic `run_mix` float-hex; one seeded `FaultPlan` under
             the state auditor
  5 serving  the oracle-placed engine on the `flood_vs_trickle` trace with
             real reduced-model forwards; the decoded tokens of a finished
             request are checked against a plain greedy decode

Each phase prints one line. The last line of stdout is one JSON object,
`{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`; any
failed phase raises, so the process exits non-zero and prints no result.
Everything runs in this one process, which starts no child.

  python chip_smoke.py              # one chip, phases 1-5
  python chip_smoke.py --chips 4    # only the sweep sharded over 4 chips,
                                    # compared float-hex with one device

The compile cache is `$JAX_COMPILATION_CACHE_DIR` when set, else the
fixed `<repo>/.jax_cache/`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent
MIX = ("3DS", "BLK")
FULL_CYCLES = 60_000     # the paper's run length (Table 1 setup)
SWEEP_CYCLES = 8_000     # one token/DRAM epoch; keeps the smoke short
SWEEP_PAIRS = 2
TRACE_SEGMENTS = 4
TRACE_SEG_CYCLES = 2_500  # 10 000 cycles in all: crosses an epoch
SERVE_STEPS = 24
ORACLE_CYCLES = 300      # the serving launcher's default
# --cpu-rehearsal: the same phases at a tiny size on the CPU
REHEARSAL = dict(full=600, sweep=200, seg=100, steps=12)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def stats_hex(stats) -> dict:
    """Bit-exact form of a runner stats dict."""
    import numpy as np
    return {k: [float(x).hex() for x in np.atleast_1d(
        np.asarray(v, np.float64)).ravel()] for k, v in sorted(stats.items())}


def all_finite(stats) -> bool:
    import numpy as np
    return all(np.isfinite(np.asarray(v, np.float64)).all()
               for v in stats.values())


# --------------------------------------------------------------- phases
def phase_device(rehearsal: bool, chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say("1 device", f"jax {jax.__version__}, platform {d.platform}, "
        f"device_kind {d.device_kind!r}, count {len(devs)}")
    if d.platform != "tpu" and not rehearsal:
        raise SmokeFailure(f"no TPU: JAX found platform {d.platform!r}")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} devices, JAX sees {len(devs)}")
    return d


def phase_goldens():
    from repro.sim.golden import GOLDEN, golden_mismatches
    bad = {}
    t0 = time.perf_counter()
    for entry in sorted(GOLDEN):
        diff = golden_mismatches(entry)
        if diff:
            bad[entry] = diff
    dt = time.perf_counter() - t0
    say("2 goldens", f"{len(GOLDEN) - len(bad)}/{len(GOLDEN)} entries match "
        f"float-hex ({dt:.1f} s)")
    for entry, diff in bad.items():
        for key, (got, want) in diff.items():
            say("2 goldens", f"MISMATCH {entry}:{key} got {got} want {want}")
    check(not bad, f"golden mismatch in {sorted(bad)}")


def _sweep(cycles: int, devices=None):
    from repro.core.design import BUILTIN_DESIGNS
    from repro.sim import runner
    from repro.sim.workloads import pair_workloads
    return runner.sweep([d.name for d in BUILTIN_DESIGNS],
                        pair_workloads()[:SWEEP_PAIRS],
                        cycles=cycles, devices=devices)


# JAX's own monitoring events for tracing, lowering and compiling
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class CompileClock:
    """Seconds JAX spent tracing, lowering and compiling, summed from its
    monitoring events (a persistent-cache hit skips the backend compile)."""

    def __init__(self):
        self.total = 0.0

    def __call__(self, event: str, duration: float, **kwargs) -> None:
        if event in COMPILE_EVENTS:
            self.total += duration


CLOCK = CompileClock()


def _timed(fn):
    """(fn(), wall seconds, of which compile seconds). The entry points
    timed here return host arrays, so the wall time ends with the device
    done."""
    c0, t0 = CLOCK.total, time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0, CLOCK.total - c0


def _timed_sweep(cycles: int, devices=None):
    from repro.sim import runner
    t0 = runner.TRACE_COUNT
    res, wall, comp = _timed(lambda: _sweep(cycles, devices))
    check(all(all_finite(m.raw) for e in res.values() for m in e),
          "non-finite sweep stats")
    return res, runner.TRACE_COUNT - t0, wall, comp


def phase_paper(full: int, sweep_cycles: int):
    from repro.sim import runner

    t0 = runner.TRACE_COUNT
    stats, wall, comp = _timed(lambda: runner.run_mix("mask", list(MIX),
                                                      cycles=full))
    check(all_finite(stats), "non-finite run_mix stats")
    run = wall - comp
    say("3 paper", f"run_mix mask {'+'.join(MIX)} {full} cycles: compile "
        f"{comp:.3f} s, run {run:.3f} s ({full / run:.0f} cycles/s), ipc "
        f"{stats['ipc'].tolist()}, traces {runner.TRACE_COUNT - t0}")

    res, traces, wall, comp = _timed_sweep(sweep_cycles)
    ws = {n: round(r.mean_weighted_speedup(), 4) for n, r in res.items()}
    say("3 paper", f"sweep 8 designs x {SWEEP_PAIRS} pairs (+solos) "
        f"{sweep_cycles} cycles: compile {comp:.3f} s, run "
        f"{wall - comp:.3f} s, traces {traces}, mean weighted speedup {ws}")


def phase_churn(seg: int):
    from repro.sim.faults import random_plan
    from repro.sim.runner import run_mix, run_trace

    total = seg * TRACE_SEGMENTS
    (tr, mono), dt, _ = _timed(lambda: (
        run_trace("mask", [MIX] * TRACE_SEGMENTS, seg_cycles=seg,
                  collect_segments=False),
        run_mix("mask", list(MIX), cycles=total)))
    same = stats_hex(tr.stats) == stats_hex(mono)
    say("4 churn", f"run_trace K={TRACE_SEGMENTS} x {seg} cycles vs run_mix "
        f"{total}: float-hex {'equal' if same else 'DIFFERENT'} ({dt:.1f} s)")
    check(same, "segmented run differs from the monolithic run")

    plan = random_plan(seed=3, n_segments=TRACE_SEGMENTS, n_apps=len(MIX),
                       rate=1.0)
    chaos, dt, _ = _timed(lambda: run_trace(
        "mask", [MIX] * TRACE_SEGMENTS, seg_cycles=seg, fault_plan=plan,
        audit=True))
    check(all(all_finite(s) for s in chaos.segments),
          "non-finite stats under faults")
    say("4 churn", f"FaultPlan seed 3 {[f.kind for f in plan.faults]}: "
        f"audit clean over {len(chaos.segments)} snapshots, ipc "
        f"{chaos.stats['ipc'].tolist()} ({dt:.1f} s)")


def _greedy_reference(eng, req):
    """Plain greedy decode of `req`'s prompt with the engine's model:
    (tokens, every logits row finite)."""
    import jax.numpy as jnp
    from repro.models import model as M
    max_len = eng.pool_cfg.pages_per_seq * eng.pool_cfg.page_size
    logits, caches = M.forward_prefill(
        eng.cfg, eng.run, eng.params,
        {"tokens": jnp.asarray(req.prompt, jnp.int32)[None]}, max_len=max_len)
    finite = bool(jnp.isfinite(logits).all())
    toks = [int(jnp.argmax(logits[0, -1]))]
    for _ in range(len(req.out) - 1):
        logits, caches = M.forward_decode(
            eng.cfg, eng.run, eng.params,
            {"tokens": jnp.asarray([[toks[-1]]], jnp.int32)}, caches)
        finite &= bool(jnp.isfinite(logits).all())
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks, finite


def phase_serving(steps: int):
    from repro.launch.serve import build_engine
    from repro.serving import metrics as smet
    from repro.serving import stream as strm
    from repro.serving.engine import EngineConfig

    trace = strm.make_trace("flood_vs_trickle", seed=0, steps=steps)
    eng = build_engine("qwen3-4b", policy="oracle",
                       profiles=trace.profiles(), epoch_steps=8,
                       ecfg=EngineConfig(max_batch=8, backoff_seed=0),
                       cycles=ORACLE_CYCLES)
    finished, wall, comp = _timed(lambda: strm.drive(eng, trace))
    oracle = eng.placement.oracle
    check(oracle.failures == [], f"oracle failures: {oracle.failures}")
    n_pred = sum(1 for d in eng.decisions if d.predictions)
    check(n_pred >= 1, "no placement decision carried a prediction")
    cons = smet.conservation_report(eng)
    check(cons["lost"] == 0 and cons["duplicated"] == 0,
          f"conservation broken: {cons}")
    check(len(finished) >= 1, "no request finished")
    req = finished[0]
    ref, finite = _greedy_reference(eng, req)
    check(finite, "non-finite logits")
    check(ref == req.out, f"request {req.rid} decoded {req.out}, plain "
          f"greedy decode gives {ref}")
    tokens = sum(len(r.out) for r in finished)
    say("5 serving", f"flood_vs_trickle {steps} steps (+drain to "
        f"{eng.step_count}): {len(finished)}/{cons['submitted']} requests "
        f"finished, {tokens} tokens, {wall:.1f} s wall ({comp:.1f} s "
        f"compiling), "
        f"{len(eng.decisions)} decisions ({n_pred} with predictions), "
        f"oracle grid calls {oracle.grid_calls}, failures 0, lost "
        f"{cons['lost']}, duplicated {cons['duplicated']}, request "
        f"{req.rid} matches greedy reference")


def phase_sharded(chips: int, sweep_cycles: int):
    one, tr1, w1, c1 = _timed_sweep(sweep_cycles)
    many, trn, wn, cn = _timed_sweep(sweep_cycles, devices=chips)
    same = all(
        [stats_hex(r.raw) for r in one[n]] == [stats_hex(r.raw)
                                                for r in many[n]]
        and one[n].solo_ipc == many[n].solo_ipc for n in one)
    say("sharded", f"sweep 8 designs x {SWEEP_PAIRS} pairs {sweep_cycles} "
        f"cycles: devices=1 compile {c1:.3f} s run {w1 - c1:.3f} s; "
        f"devices={chips} compile {cn:.3f} s run {wn - cn:.3f} s; traces "
        f"{tr1}+{trn}; float-hex {'equal' if same else 'DIFFERENT'}")
    check(same, f"sweep sharded over {chips} devices differs from one device")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sweep sharded over 4 chips and "
                         "its single-device comparison")
    # test-only: accept the CPU and shrink cycle counts, for rehearsing
    # the whole smoke under JAX_PLATFORMS=cpu
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    sys.path[:0] = [str(REPO / "src"), str(REPO)]
    from benchmarks.perf import enable_compilation_cache
    cache_dir = enable_compilation_cache()
    print(f"# compile cache: {cache_dir}", flush=True)

    r = args.cpu_rehearsal
    full = REHEARSAL["full"] if r else FULL_CYCLES
    sweep_cycles = REHEARSAL["sweep"] if r else SWEEP_CYCLES
    seg = REHEARSAL["seg"] if r else TRACE_SEG_CYCLES

    import jax
    jax.monitoring.register_event_duration_secs_listener(CLOCK)
    dev = phase_device(r, args.chips)
    if args.chips > 1:
        phase_sharded(args.chips, sweep_cycles)
    else:
        phase_goldens()
        phase_paper(full, sweep_cycles)
        phase_churn(seg)
        phase_serving(REHEARSAL["steps"] if r else SERVE_STEPS)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
