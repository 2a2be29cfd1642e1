"""Compile rehearsals for one described TPU v5e chip.

Each test compiles a program of the main path at its real size with the
TPU compiler, for a chip that is described, not attached: what the chip's
compiler would refuse (tiling, VMEM, memory) fails here, at no chip time.
Nothing runs, so these say nothing about results or speed.

The topology is described inside a module-scoped fixture, never while a
module is imported: only one process at a time may load the TPU library,
and every test worker imports every test file. Keep all such tests in
this one file, so that only the worker that is given it loads the
library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.design import as_design, design_params
from repro.sim import faults as faults_mod
from repro.sim import runner
from repro.sim.config import SimConfig
from repro.sim.memsys import init_state

# Qwen3-4B attention widths: 32 query heads, 8 KV heads, head_dim 128
Q_HEADS, KV_HEADS, HEAD_DIM = 32, 8, 128
GRID_ROWS = 16


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _spec(tree, sharding):
    """Shapes (with `sharding`) of every leaf of `tree`."""
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _table1(cycles: int, n_apps: int = 2) -> SimConfig:
    """Canonical Table 1 config of the `mask` signature group."""
    return runner._canonical(SimConfig(n_apps=n_apps, sim_cycles=cycles,
                                       design=as_design("mask")))


def _sim_args(cfg, sharding, rows=None):
    dp = design_params(as_design("mask"))
    pm = runner._mix_matrix(("3DS", "BLK"))
    if rows is not None:
        dp = jax.tree_util.tree_map(
            lambda x: np.repeat(np.asarray(x)[None], rows, 0), dp)
        pm = np.repeat(pm[None], rows, 0)
    return _spec(dp, sharding), _spec(pm, sharding)


def test_sim_scan_table1(one_chip):
    """The main path: `_run_fn` under one lax.scan, 60 000 cycles."""
    cfg = _table1(60_000)
    compiled = jax.jit(runner._run_fn(cfg)).lower(
        *_sim_args(cfg, one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.output_size_in_bytes > 0


def test_sim_grid_vmap(one_chip):
    """The grid program of `run_grid`: 16 vmapped (design, mix) rows."""
    cfg = _table1(8_000)
    prog = jax.jit(jax.vmap(runner._run_fn(cfg), in_axes=(0, 0)))
    compiled = prog.lower(*_sim_args(cfg, one_chip, GRID_ROWS)).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_sim_trace_segment(one_chip):
    """The `run_trace` segment program: teardown + faults + scan."""
    cfg = _table1(2_500)
    dp, pm = _sim_args(cfg, one_chip)
    state = jax.eval_shape(lambda d: init_state(cfg, d), dp)
    fops = jax.tree_util.tree_map(
        lambda x: x[0], faults_mod.empty_operands(cfg, 1))
    compiled = runner._compiled_seg_run.__wrapped__(cfg).lower(
        dp, pm, _spec(state, one_chip),
        jax.ShapeDtypeStruct((cfg.n_apps,), jnp.bool_, sharding=one_chip),
        _spec(fops, one_chip)).compile()
    assert compiled.memory_analysis().output_size_in_bytes > 0


def test_flash_attention_qwen3_4b(one_chip):
    from repro.kernels.flash_attention.ops import flash_attention
    S = 2048
    q = jax.ShapeDtypeStruct((1, S, Q_HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, S, KV_HEADS, HEAD_DIM), jnp.bfloat16,
                              sharding=one_chip)
    compiled = flash_attention.lower(q, kv, kv, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_paged_attention_qwen3_4b(one_chip):
    from repro.kernels.paged_attention.ops import paged_attention
    B, pages, page, per_seq = 8, 256, 16, 16
    pg = jax.ShapeDtypeStruct((pages, page, KV_HEADS, HEAD_DIM),
                              jnp.bfloat16, sharding=one_chip)
    compiled = paged_attention.lower(
        jax.ShapeDtypeStruct((B, Q_HEADS, HEAD_DIM), jnp.bfloat16,
                             sharding=one_chip), pg, pg,
        jax.ShapeDtypeStruct((B, per_seq), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((B,), jnp.int32, sharding=one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "the (1, 1, chunk, head_tile) block at kernels/ssd_scan/kernel.py:86 "
    "breaks the Pallas TPU rule that a block's last two dims divide by "
    "(8, 128) or equal the array's"))
def test_ssd_scan_mamba2_1_3b(one_chip):
    """Mamba2-1.3B SSD widths: 64 heads of 64, state 128, chunk 256."""
    from repro.kernels.ssd_scan.ops import ssd_scan
    b, S, nh, hd, ds = 1, 1024, 64, 64, 128
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32,  # noqa: E731
                                          sharding=one_chip)
    ssd_scan.lower(f32(b, S, nh, hd), f32(b, S, nh), f32(nh), f32(b, S, ds),
                   f32(b, S, ds), chunk=256, interpret=False).compile()
