"""Jit'd wrapper for the int8 block-quantize kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.quantize.kernel import quantize_fwd


@functools.partial(jax.jit, static_argnames=("block", "block_rows",
                                             "interpret"))
def quantize(x, *, block: int = 256, block_rows: int = 64,
             interpret: bool = False):
    """x: any-shape f32 -> (q (nb, block) int8, scales (nb,), pad)."""
    flat = x.astype(jnp.float32).reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    nb = blocks.shape[0]
    br = block_rows
    while nb % br:
        br //= 2
    q, s = quantize_fwd(blocks, block_rows=max(br, 1), interpret=interpret)
    return q, s, pad
