"""Benchmark harness: one entry per paper table/figure + kernel micro-bench
+ roofline summary. Prints ``name,value,paper_value`` rows / JSON blocks.

  PYTHONPATH=src python -m benchmarks.run                 # paper repro suite
  PYTHONPATH=src python -m benchmarks.run --quick         # subset (CI)
  PYTHONPATH=src python -m benchmarks.run --kernels       # kernel micro-bench
  PYTHONPATH=src python -m benchmarks.run --roofline      # dry-run summary
  PYTHONPATH=src python -m benchmarks.run --list-designs  # design registry
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from benchmarks import paper_repro  # noqa: E402


def run_paper(which=None, force=False):
    """Run the selected figures; returns the names of those that failed.
    A failing figure does not stop the others, but it is reported."""
    failed = []
    for name, fn in paper_repro.ALL.items():
        if which and name not in which:
            continue
        t0 = time.time()
        try:
            res = fn(force=force)
        except Exception:  # noqa: BLE001 — keep running the other figures
            traceback.print_exc()
            print(f"# {name} FAILED")
            failed.append(name)
            continue
        print(f"# {name} ({time.time()-t0:.0f}s)")
        print(json.dumps(res, indent=2, default=float))
    return failed


def run_kernels():
    """Micro-bench the Pallas kernels. Each row says whether the kernel
    ran compiled or in interpret mode: interpret-mode times show
    correctness and relative shape scaling, never device speed.
    flash/paged attention compile on a TPU; ssd_scan does not lower for
    TPU yet (tests/test_chip_compile.py), so it always runs interpreted."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.paged_attention.ops import paged_attention
    from repro.kernels.ssd_scan.ops import ssd_scan

    compiled = jax.default_backend() == "tpu"
    mode = "compiled" if compiled else "interpret"
    print("name,mode,us_per_call,flops_est")
    rng = np.random.RandomState(0)
    B, S, H, KV, dh = 1, 512, 4, 2, 128
    q = jnp.asarray(rng.randn(B, S, H, dh), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, KV, dh), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, KV, dh), jnp.float32)
    f = lambda: flash_attention(q, k, v, block_q=128, block_k=128,  # noqa
                                interpret=not compiled)
    f().block_until_ready()
    t0 = time.time()
    for _ in range(3):
        f().block_until_ready()
    print(f"flash_attention_512,{mode},{(time.time()-t0)/3*1e6:.0f},"
          f"{4*B*H*S*S*dh/2:.3g}")

    qd = jnp.asarray(rng.randn(4, H, dh), jnp.float32)
    kp = jnp.asarray(rng.randn(32, 16, KV, dh), jnp.float32)
    vp = jnp.asarray(rng.randn(32, 16, KV, dh), jnp.float32)
    bt = jnp.asarray(rng.choice(32, (4, 8)), jnp.int32)
    sl = jnp.asarray([128, 64, 90, 16], jnp.int32)
    g = lambda: paged_attention(qd, kp, vp, bt, sl,  # noqa
                                interpret=not compiled)
    g().block_until_ready()
    t0 = time.time()
    for _ in range(3):
        g().block_until_ready()
    print(f"paged_attention_b4,{mode},{(time.time()-t0)/3*1e6:.0f},"
          f"{4*4*H*128*dh:.3g}")

    x = jnp.asarray(rng.randn(1, 256, 8, 32) * .3, jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(1, 256, 8)) * .1 + .02, jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(8)) * .5 - .1, jnp.float32)
    Bm = jnp.asarray(rng.randn(1, 256, 16) * .3, jnp.float32)
    Cm = jnp.asarray(rng.randn(1, 256, 16) * .3, jnp.float32)
    h = lambda: ssd_scan(x, dt, A, Bm, Cm, chunk=64,  # noqa
                         interpret=True)[0]
    h().block_until_ready()
    t0 = time.time()
    for _ in range(3):
        h().block_until_ready()
    print(f"ssd_scan_256,interpret,{(time.time()-t0)/3*1e6:.0f},n/a")


def run_roofline_summary():
    """Summarize reports/dryrun into the §Roofline table (CSV)."""
    rep_dir = Path(__file__).resolve().parent.parent / "reports" / "dryrun"
    rows = sorted(rep_dir.glob("*.json"))
    print("cell,mesh,dominant,compute_s,memory_s,collective_s,"
          "roofline_frac,useful_ratio,hbm_gb")
    for f in rows:
        r = json.loads(f.read_text())
        if "error" in r:
            print(f"{f.stem},ERROR,,,,,,,")
            continue
        rf = r.get("roofline", {})
        print(f"{r['arch']}__{r['shape']},{r['mesh']},{rf.get('dominant')},"
              f"{rf.get('compute_s', 0):.3e},{rf.get('memory_s', 0):.3e},"
              f"{rf.get('collective_s', 0):.3e},"
              f"{rf.get('roofline_fraction', 0):.3f},"
              f"{rf.get('useful_flops_ratio', 0):.3f},"
              f"{r.get('hbm_per_device_bytes', 0)/1e9:.2f}")


def list_designs():
    """Print the design registry: every named point `benchmarks` can run."""
    from repro.core.design import get_design, list_designs as _names
    for name in _names():
        d = get_design(name)
        mechs = [m for m, on in (("tokens", d.tokens.enabled),
                                 ("bypass", d.bypass.enabled),
                                 ("dram", d.dram.enabled)) if on]
        print(f"{name:12s} translation={d.translation.kind:13s} "
              f"partition={d.partition.kind:6s} "
              f"mechanisms={'+'.join(mechs) or '-'}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--kernels", action="store_true")
    ap.add_argument("--roofline", action="store_true")
    ap.add_argument("--list-designs", action="store_true")
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--no-compile-cache", action="store_true",
                    help="disable the persistent JAX compilation cache "
                         "(default: $JAX_COMPILATION_CACHE_DIR, else "
                         ".jax_cache/, so re-runs skip recompiles; see "
                         "README)")
    args = ap.parse_args()

    if not args.no_compile_cache:
        from benchmarks.perf import enable_compilation_cache
        enable_compilation_cache()

    if args.kernels:
        run_kernels()
        return
    if args.roofline:
        run_roofline_summary()
        return
    if args.list_designs:
        list_designs()
        return
    which = args.only
    if args.quick and not which:
        which = ["fig16", "tab3"]
    failed = run_paper(which, force=args.force)
    if failed:
        raise SystemExit(f"failed figures: {', '.join(failed)}")


if __name__ == "__main__":
    main()
