"""Jit'd wrapper: model layout (b, s, h, n) -> WKV6 Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6_wkv.kernel import wkv6_fwd


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(r, k, v, w, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/w: (b, s, h, n) with w = decay in (0,1); u: (h, n)."""
    b, s, h, n = r.shape
    lw = jnp.log(jnp.maximum(w.astype(jnp.float32), 1e-38))

    def to_bh(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, s, n)

    u_bh = jnp.broadcast_to(u[None], (b, h, n)).reshape(b * h, n)
    y = wkv6_fwd(to_bh(r), to_bh(k), to_bh(v), to_bh(lw), u_bh,
                 chunk=chunk, interpret=interpret)
    return y.reshape(b, h, s, n).transpose(0, 2, 1, 3)
