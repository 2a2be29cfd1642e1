"""Data pipeline determinism, sharder rules, HLO parser correctness."""
import json
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_run_config, reduced_model
from repro.data.pipeline import DataConfig, DataPipeline
from repro.models.losses import cross_entropy
from repro.models.params import Param
from repro.roofline.hlo_parse import analyze_hlo


# ------------------------------------------------------------------- data

def test_pipeline_deterministic_and_sharded():
    cfg = reduced_model(ARCHS["llama3-8b"])
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("t", seq_len=16, global_batch=8, kind="train")
    full = DataPipeline(cfg, shape).batch_at(3)
    again = DataPipeline(cfg, shape).batch_at(3)
    np.testing.assert_array_equal(full["tokens"], again["tokens"])
    # host shards partition the global batch rows exactly
    h0 = DataPipeline(cfg, shape, host_index=0, host_count=2).batch_at(3)
    h1 = DataPipeline(cfg, shape, host_index=1, host_count=2).batch_at(3)
    np.testing.assert_array_equal(
        np.concatenate([h0["tokens"], h1["tokens"]]), full["tokens"])


def test_pipeline_iterator_skip_ahead():
    cfg = reduced_model(ARCHS["qwen3-4b"])
    from repro.configs.base import ShapeConfig
    shape = ShapeConfig("t", seq_len=16, global_batch=4, kind="train")
    pipe = DataPipeline(cfg, shape)
    seq = list(pipe.iterate(start_step=5, stop_step=8))
    assert [s for s, _ in seq] == [5, 6, 7]
    np.testing.assert_array_equal(seq[1][1]["tokens"],
                                  pipe.batch_at(6)["tokens"])


# ----------------------------------------------------------------- losses

def test_cross_entropy_padded_vocab_masked():
    logits = jnp.zeros((1, 2, 8))
    # make a padded column irresistible — masking must ignore it
    logits = logits.at[..., 7].set(100.0)
    labels = jnp.asarray([[0, 1]])
    loss_masked, m = cross_entropy(logits, labels, real_vocab=7)
    assert abs(float(loss_masked) - np.log(7)) < 1e-4
    loss_unmasked, _ = cross_entropy(logits, labels)
    assert float(loss_unmasked) > 50


# --------------------------------------------------------------- HLO parse

def test_hlo_parser_counts_scan_trips():
    """A scanned matmul must be counted trip-count times."""
    n, m, k, trips = 64, 64, 64, 7

    def f(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None
        y, _ = jax.lax.scan(body, x, None, length=trips)
        return y

    x = jnp.zeros((n, k))
    w = jnp.zeros((k, m))
    hlo = jax.jit(f).lower(x, w).compile().as_text()
    t = analyze_hlo(hlo)
    expect = 2 * n * m * k * trips
    assert abs(t["dot_flops"] - expect) / expect < 0.05, t["dot_flops"]


def test_hlo_parser_collectives_smoke():
    hlo = """
HloModule test

ENTRY %main (a: f32[64]) -> f32[64] {
  %a = f32[64]{0} parameter(0)
  ROOT %ar = f32[64]{0} all-reduce(%a), replica_groups={}, to_apply=%add
}
"""
    t = analyze_hlo(hlo)
    assert t["coll_by_op"].get("all-reduce", 0) == 256


# ----------------------------------------------------------------- sharder

class _FakeRun:
    def __init__(self):
        from repro.configs import get_run_config
        self.__dict__.update(get_run_config("llama3-8b", "train_4k").__dict__)


@pytest.mark.slow
def test_sharder_specs_subprocess():
    """Lower a reduced model on an 8-device mesh in a subprocess (the only
    way to get >1 host device under pytest)."""
    code = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np
import jax.numpy as jnp
from repro.configs import ARCHS, reduced_model, get_run_config
from repro.configs.base import RunConfig, ShapeConfig
from repro.distributed.sharding import Sharder
from repro.models import model as M
from repro.train.step import build_train_step
from repro.train import optimizer as opt_mod

cfg = reduced_model(ARCHS["llama3-8b"])
shape = ShapeConfig("t", seq_len=32, global_batch=4, kind="train")
run = RunConfig(model=cfg, shape=shape, remat=False, fsdp=True,
                attn_block_q=16, attn_block_k=16)
mesh = jax.make_mesh((2, 4), ("data", "model"))
sh = Sharder(mesh, run)
with mesh:
    params = M.abstract_params(cfg, sh.param_sharding)
    batch = M.input_specs(cfg, shape, sh.act_sharding)
    ocfg = opt_mod.OptConfig()
    opt = opt_mod.abstract_state(M.param_specs(cfg), ocfg, sh.param_sharding)
    step = build_train_step(cfg, run, ocfg, sh.constrain)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(
        params, opt, batch).compile()
print("OK", compiled.cost_analysis()["flops"] > 0)
"""
    # the 8-fake-device CPU compile takes several minutes on slow hosts
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert "OK True" in out.stdout, out.stderr[-2000:]


def test_param_spec_no_duplicate_axes():
    from jax.sharding import Mesh
    import jax
    from repro.distributed.sharding import Sharder
    run = get_run_config("jamba-1.5-large-398b", "train_4k")
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1), ("data", "model"))
    sh = Sharder(mesh, run)
    p = Param((16, 8192, 24576), ("experts", "embed", "ffn"))
    spec = sh.param_spec(p)
    flat = [e for entry in spec if entry for e in
            (entry if isinstance(entry, tuple) else (entry,))]
    assert len(flat) == len(set(flat))
