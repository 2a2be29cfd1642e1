"""jit'd wrapper for paged decode attention."""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import resolve_interpret
from repro.kernels.paged_attention.kernel import paged_attention as _kernel_call


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, k_pages, v_pages, block_table, seq_lens,
                    interpret: Optional[bool] = None):
    """`interpret=None` compiles to Mosaic on a TPU and raises elsewhere;
    `interpret=True` runs the Pallas interpreter."""
    interpret = resolve_interpret(interpret, "paged_attention")
    return _kernel_call(q, k_pages, v_pages, block_table, seq_lens,
                        interpret=interpret)
