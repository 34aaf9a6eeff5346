"""Chunked WKV6 kernel (pl.pallas_call + BlockSpec).

Grid: (batch*heads, seq_chunks) with the chunk axis marked
"arbitrary"-ordered sequential — the (n, n) state matrix lives in a
VMEM scratch accumulator carried across chunk steps (grid iteration on
TPU is sequential over the last axis, the standard Pallas carry
pattern).

Per chunk of length L (default 64) with head size n (= 64 for RWKV6):
  load r/k/v/logw tiles (L, n) -> VMEM,
  cum = cumsum(logw) along L, as a lower-triangular (L, L) matmul
  (Mosaic has no cumsum),
  pairwise decay D[l, m] = exp(cum[l-1] - cum[m]) masked to m < l
  (every exponent <= 0: numerically safe by construction),
  intra = (r*exp(cum_prev)) @ state  +  ((r (x) k (x) D) @ v  + diag-u,
  state = exp(cum_L) * state + (k * exp(cum_L - cum))^T @ v.

Working set: 4 tiles (L, n) + state (n, n) f32 + the (L, L) score tile
~ 64KB << VMEM.  The MXU sees (L, n) x (n, n) and (L, L) x (L, n)
matmuls; the decay-weighted scores are summed one head channel at a
time as (L, L) VPU work, so the (L, L, n) pairwise-decay tensor is
never built.

The HBM win vs the jnp path: r/k/v/w are read once and y written once
per chunk — the pairwise-decay tensor never reaches HBM (it dominates
the jnp path's memory traffic at rwkv6-7b scale).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_HIGHEST = jax.lax.Precision.HIGHEST


def _wkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, y_ref, s_ref, *,
                 chunk: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    f32 = jnp.float32
    r = r_ref[0].astype(f32)           # (L, n)
    k = k_ref[0].astype(f32)
    v = v_ref[0].astype(f32)
    lw = lw_ref[0].astype(f32)         # log decay, <= 0
    u = u_ref[0].astype(f32)           # (1, n) bonus for this head
    n = r.shape[1]

    def mm(a, b, contract=((1,), (0,))):
        return jax.lax.dot_general(a, b, (contract, ((), ())),
                                   precision=_HIGHEST)

    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    # inclusive cumsum along L as a lower-triangular matmul
    cum = mm((col <= row).astype(f32), lw)                 # (L, n)
    cum_prev = cum - lw
    S = s_ref[...]                     # (n, n)

    # inter-chunk: r_t * a_{t-1} applied to the carried state
    inter = mm(r * jnp.exp(cum_prev), S)

    # intra-chunk pairwise scores, one head channel at a time:
    # scores[l,m] = sum_c r[l,c] k[m,c] exp(cum_prev[l,c] - cum[m,c]), m < l
    # (static slices only: Mosaic implements no dynamic_slice)
    causal = col < row
    k_t = k.T                          # (n, L)
    cum_t = cum.T
    scores = jnp.zeros((chunk, chunk), f32)
    for c in range(n):
        d = cum_prev[:, c:c + 1] - cum_t[c:c + 1, :]        # (L, L)
        d = jnp.where(causal, d, -jnp.inf)
        scores = scores + (r[:, c:c + 1] * k_t[c:c + 1, :]) * jnp.exp(d)
    intra = mm(scores, v)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)       # (L, 1)
    y = inter + intra + diag * v

    # state update (all multipliers <= 1); total == cum[L-1]
    total = jnp.sum(lw, axis=0, keepdims=True)             # (1, n)
    k_tail = k * jnp.exp(total - cum)                       # (L, n)
    s_ref[...] = jnp.exp(total).T * S + mm(k_tail, v, ((0,), (0,)))
    y_ref[0] = y.astype(y_ref.dtype)


def wkv6_fwd(r, k, v, lw, u, *, chunk: int = 64, interpret: bool = False):
    """r/k/v/lw: (bh, s, n); u: (bh, n).  Returns y (bh, s, n).

    lw = log(decay) (<= 0).  bh = batch*heads; u is per-head, callers
    broadcast it to (bh, n).  u enters the kernel as (bh, 1, n) so its
    block's last two dims equal the array's (the TPU tiling rule).
    """
    bh, s, n = r.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    grid = (bh, s // chunk)
    kernel = functools.partial(_wkv6_kernel, chunk=chunk)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, 1, n), lambda b, c: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, n), lambda b, c: (b, c, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, n), r.dtype),
        scratch_shapes=[pltpu.VMEM((n, n), jnp.float32)],
        interpret=interpret,
    )(r, k, v, lw, u[:, None, :])
