"""Int8 block-quantize kernel (pl.pallas_call + BlockSpec).

One grid step loads a (Bn, block) tile of gradient blocks into VMEM,
computes per-row absmax -> scale, and writes the rounded int8 tile plus
the f32 scales.  Pure VPU work; the point of the kernel is bandwidth:
gradients are read exactly once and written at 1/4 the bytes (+scales),
which is the compression step of the cross-pod gradient all-reduce.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _quantize_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                     # (Bn, block)
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=1) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(x / scale[:, None]), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale[:, None]


def quantize_fwd(x, *, block_rows: int = 64, interpret: bool = False):
    """x: (nb, block) f32 -> (q (nb, block) int8, scales (nb,) f32).

    The kernel writes the scales as an (nb, 1) column: a rank-1 block
    of ``block_rows`` breaks the TPU rule that a rank-1 block is a
    multiple of 128 or the whole array.
    """
    nb, block = x.shape
    block_rows = min(block_rows, nb)
    assert nb % block_rows == 0
    grid = (nb // block_rows,)
    q, scales = pl.pallas_call(
        _quantize_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block_rows, block), lambda i: (i, 0))],
        out_specs=[
            pl.BlockSpec((block_rows, block), lambda i: (i, 0)),
            pl.BlockSpec((block_rows, 1), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nb, block), jnp.int8),
            jax.ShapeDtypeStruct((nb, 1), jnp.float32),
        ],
        interpret=interpret,
    )(x)
    return q, scales[:, 0]
