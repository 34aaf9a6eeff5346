"""Jit'd wrapper: (G, E, C, D) capacity blocks -> fused expert MLP."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.moe_mlp.kernel import expert_mlp_fwd


@functools.partial(jax.jit, static_argnames=("block_c", "block_f",
                                             "interpret"))
def expert_mlp(x, wi, wg, wo, *, block_c: int = 128, block_f: int = 256,
               interpret: bool = False):
    """x: (G, E, C, D); wi/wg: (E, D, F); wo: (E, F, D) -> (G, E, C, D)."""
    g, e, c, d = x.shape
    out = expert_mlp_fwd(x.reshape(g * e, c, d), wi, wg, wo,
                         block_c=block_c, block_f=block_f,
                         interpret=interpret)
    return out.reshape(g, e, c, d)
