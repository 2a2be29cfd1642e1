"""jit'd public wrapper: layout handling, GQA, explicit interpret mode."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import resolve_interpret
from repro.kernels.flash_attention.kernel import flash_attention_bhsd


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window: Optional[int] = None,
                    block_q: int = 512, block_k: int = 512,
                    interpret: Optional[bool] = None):
    """q: (B, S, H, dh); k, v: (B, S, KV, dh) — model-native layout.

    Returns (B, S, H, dh). `interpret=None` compiles to Mosaic on a TPU
    and raises elsewhere; `interpret=True` runs the kernel body in the
    Pallas interpreter (the correctness path on the CPU).
    """
    interpret = resolve_interpret(interpret, "flash_attention")
    qt = jnp.swapaxes(q, 1, 2)
    kt = jnp.swapaxes(k, 1, 2)
    vt = jnp.swapaxes(v, 1, 2)
    out = flash_attention_bhsd(qt, kt, vt, causal=causal, window=window,
                               block_q=block_q, block_k=block_k,
                               interpret=interpret)
    return jnp.swapaxes(out, 1, 2)
