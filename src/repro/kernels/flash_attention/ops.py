"""Jit'd public wrapper for the flash-attention kernel.

``flash_attention(q, k, v)`` takes model-layout (b, s, h, d) tensors,
flattens (b, h) into the grid's leading axis, and dispatches to the
Pallas kernel, compiled for the TPU.  ``interpret=True`` runs the
kernel in the Pallas interpreter instead (the CPU tests do).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.kernel import flash_attention_fwd


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool = False):
    """q/k/v: (b, s, h, d) -> (b, s, h, d)."""
    b, s, h, d = q.shape
    skv = k.shape[1]

    def to_bh(x, sl):
        return x.transpose(0, 2, 1, 3).reshape(b * h, sl, d)

    o = flash_attention_fwd(
        to_bh(q, s), to_bh(k, skv), to_bh(v, skv),
        causal=causal, window=window, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
