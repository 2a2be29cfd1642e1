"""Per-kernel shape/dtype sweeps vs pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.paged_attention.ops import paged_attention
from repro.kernels.paged_attention.ref import paged_attention_ref
from repro.kernels.ssd_scan.ops import ssd_scan
from repro.kernels.ssd_scan.ref import ssd_recurrence_ref


@pytest.mark.parametrize("S,H,KV,dh,bq,bk", [
    (128, 4, 4, 64, 64, 64),      # MHA
    (256, 8, 2, 64, 64, 128),     # GQA 4:1
    (128, 4, 1, 128, 32, 64),     # MQA
])
@pytest.mark.parametrize("causal,window", [(True, None), (False, None),
                                           (True, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_sweep(S, H, KV, dh, bq, bk, causal, window, dtype):
    rng = np.random.RandomState(S + H)
    q = jnp.asarray(rng.randn(2, S, H, dh), dtype)
    k = jnp.asarray(rng.randn(2, S, KV, dh), dtype)
    v = jnp.asarray(rng.randn(2, S, KV, dh), dtype)
    out = flash_attention(q, k, v, causal=causal, window=window,
                          block_q=bq, block_k=bk, interpret=True)
    ref = attention_ref(jnp.swapaxes(q, 1, 2), jnp.swapaxes(k, 1, 2),
                        jnp.swapaxes(v, 1, 2), causal=causal, window=window)
    ref = jnp.swapaxes(ref, 1, 2)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("B,H,KV,dh,page,npp", [
    (4, 8, 4, 64, 16, 6),
    (2, 4, 4, 128, 32, 4),        # MHA-ish
    (3, 16, 2, 64, 8, 10),        # GQA 8:1
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(B, H, KV, dh, page, npp, dtype):
    rng = np.random.RandomState(B * H)
    P = npp * B + 4
    q = jnp.asarray(rng.randn(B, H, dh), dtype)
    kp = jnp.asarray(rng.randn(P, page, KV, dh), dtype)
    vp = jnp.asarray(rng.randn(P, page, KV, dh), dtype)
    bt = jnp.asarray(rng.choice(P, (B, npp), replace=False), jnp.int32)
    sl = jnp.asarray(rng.randint(1, npp * page + 1, B), jnp.int32)
    out = paged_attention(q, kp, vp, bt, sl, interpret=True)
    ref = paged_attention_ref(q, kp, vp, bt, sl)
    tol = 3e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("S,nh,hd,ds,chunk", [
    (64, 4, 16, 16, 16),
    (128, 8, 32, 16, 32),
    (96, 2, 64, 32, 32),
])
def test_ssd_scan_sweep(S, nh, hd, ds, chunk):
    rng = np.random.RandomState(S + nh)
    x = jnp.asarray(rng.randn(2, S, nh, hd) * .5, jnp.float32)
    dt = jnp.asarray(np.abs(rng.randn(2, S, nh)) * .1 + .02, jnp.float32)
    A = jnp.asarray(-np.abs(rng.randn(nh)) * .5 - .1, jnp.float32)
    B = jnp.asarray(rng.randn(2, S, ds) * .5, jnp.float32)
    C = jnp.asarray(rng.randn(2, S, ds) * .5, jnp.float32)
    y, h = ssd_scan(x, dt, A, B, C, chunk=chunk, interpret=True)
    y_ref, h_ref = ssd_recurrence_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref),
                               atol=1e-4, rtol=1e-4)


def _tiny_kernel_call(kernel):
    """One minimal call of each attention/SSD wrapper with interpret=None."""
    f32 = jnp.float32
    if kernel == "flash_attention":
        x = jnp.zeros((1, 64, 1, 64), f32)
        return flash_attention(x, x, x, block_q=64, block_k=64)
    if kernel == "paged_attention":
        pages = jnp.zeros((2, 8, 1, 64), f32)
        return paged_attention(jnp.zeros((1, 1, 64), f32), pages, pages,
                               jnp.zeros((1, 2), jnp.int32),
                               jnp.ones((1,), jnp.int32))
    x = jnp.zeros((1, 16, 1, 8), f32)
    bc = jnp.zeros((1, 16, 8), f32)
    return ssd_scan(x, jnp.ones((1, 16, 1), f32), -jnp.ones((1,), f32),
                    bc, bc, chunk=16)


@pytest.mark.parametrize("kernel", ["flash_attention", "paged_attention",
                                    "ssd_scan"])
def test_kernels_raise_without_tpu_lowering(kernel):
    """interpret=None lowers for real or raises; interpret mode is never
    chosen quietly off the TPU."""
    if jax.default_backend() == "tpu":
        pytest.skip("real Pallas lowering available")
    with pytest.raises(RuntimeError, match="no Pallas lowering"):
        _tiny_kernel_call(kernel)
