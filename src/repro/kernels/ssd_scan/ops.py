"""jit'd SSD wrapper: Pallas intra-chunk kernel + jnp inter-chunk scan."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.ssd_scan.kernel import ssd_intra_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, A, B, C, *, chunk: int = 256,
             interpret: Optional[bool] = None):
    """Full SSD: y (b, S, nh, hd) and final state (b, nh, hd, ds).

    x: (b, S, nh, hd); dt: (b, S, nh) positive; A: (nh,) negative;
    B, C: (b, S, ds).

    `interpret=None` lowers the intra-chunk kernel for a TPU and raises
    elsewhere; `interpret=True` runs the Pallas interpreter. The TPU
    compiler refuses this kernel today: its (1, 1, chunk, hd) blocks break
    the last-two-dims tiling rule (tests/test_chip_compile.py keeps that
    refusal as a strict xfail).
    """
    interpret = resolve_interpret(interpret, "ssd_scan")
    b, S, nh, hd = x.shape
    ds = B.shape[-1]
    assert S % chunk == 0
    nc = S // chunk

    xf = (x * dt[..., None]).astype(jnp.float32)
    dA = (dt * A[None, None, :]).astype(jnp.float32)
    xc = xf.reshape(b, nc, chunk, nh, hd)
    dAc = dA.reshape(b, nc, chunk, nh)
    Bc = B.reshape(b, nc, chunk, ds).astype(jnp.float32)
    Cc = C.reshape(b, nc, chunk, ds).astype(jnp.float32)

    y_intra, s_chunk, decay = ssd_intra_chunk(
        xc, dAc, Bc, Cc, interpret=interpret)

    # ---- inter-chunk recurrence (tiny, stays in XLA) ----
    h0 = jnp.zeros((b, nh, hd, ds), jnp.float32)

    def step(h, inp):
        s_c, d_c = inp
        h_out = h
        return h * d_c[..., None, None] + s_c, h_out

    h_final, h_enter = jax.lax.scan(
        step, h0, (jnp.moveaxis(s_chunk, 1, 0), jnp.moveaxis(decay, 1, 0)))
    h_enter = jnp.moveaxis(h_enter, 0, 1)              # (b, nc, nh, hd, ds)

    dA_cum = jnp.cumsum(dAc, axis=2)
    y_inter = jnp.einsum("bnqd,bnqh,bnhpd->bnqhp",
                         Cc, jnp.exp(dA_cum), h_enter)
    y = (y_intra + y_inter).reshape(b, S, nh, hd)
    return y, h_final
