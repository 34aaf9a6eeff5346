"""Mamba-2 (SSD) mixer [arXiv:2405.21060], the state-space layer of the
Granite-4.0-H hybrids.

One layer: ``in_proj`` maps d_model to z (d_inner), xBC (d_inner + 2 *
d_state) and dt (one per head).  xBC goes through a causal depthwise
conv (``d_conv`` taps, with bias) and SiLU, then splits into x (heads x
head_dim), B and C (d_state each, one group shared by every head).  Per
head, ``dt = softplus(dt + dt_bias)`` and ``A = -exp(A_log)`` give a
scalar decay:

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T     (heads, head_dim, d_state)
    y_t = h_t C_t + D x_t

and ``y * silu(z)`` goes through an RMSNorm over all d_inner channels
(one group) and ``out_proj``.  The state is float32.

Two paths compute the recurrence:

- ``ssd_chunked`` (prefill and train): the chunked SSD form.  Inside each
  chunk of ``ssm_chunk`` tokens, matrix products over the chunk's (query,
  key) pairs; across chunks, the state at each chunk's end carried by a
  scan over chunks.  Profiles show it as ``mamba2.ssd``.
- ``ssd_step`` (decode): one step of the recurrence on the carried
  state, ``mamba2.step``.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import IDENTITY_SHARDER, Sharder, param, split_key
from repro.models.mamba import causal_conv


def conv_dim(cfg) -> int:
    """Channels of the conv: x, B and C."""
    return cfg.d_inner + 2 * cfg.ssm_groups * cfg.d_state


def _check(cfg) -> None:
    if cfg.ssm_groups != 1:
        raise NotImplementedError(
            f"Mamba-2 with {cfg.ssm_groups} B/C groups (only 1 is built)")
    if cfg.ssm_heads * cfg.ssm_head_dim != cfg.d_inner:
        raise ValueError(f"{cfg.ssm_heads} heads x {cfg.ssm_head_dim} != "
                         f"d_inner {cfg.d_inner}")


def init_mamba2_block(key, cfg) -> Dict:
    """Published init: A uniform in [1, 16]; dt log-uniform in [0.001,
    0.1], stored as softplus^-1(dt) in ``dt_bias``; D ones."""
    _check(cfg)
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    cd = conv_dim(cfg)
    ks = split_key(key, 7)
    dt = jnp.exp(jax.random.uniform(ks[3], (h,), minval=math.log(1e-3),
                                    maxval=math.log(1e-1)))
    return {
        "in_proj": param(ks[0], (d, di + cd + h), ("embed", "mlp")),
        "conv_w": param(ks[1], (cfg.d_conv, cd), (None, "mlp"), scale=0.5),
        "conv_b": param(ks[2], (cd,), ("mlp",), init="zeros"),
        "dt_bias": {"v": dt + jnp.log(-jnp.expm1(-dt)), "axes": (None,)},
        "A_log": {"v": jnp.log(jax.random.uniform(ks[4], (h,), minval=1.0,
                                                  maxval=16.0)),
                  "axes": (None,)},
        "D": param(ks[5], (h,), (None,), init="ones"),
        "norm": {"scale": param(ks[5], (di,), ("mlp",), init="ones")},
        "out_proj": param(ks[6], (di, d), ("mlp", "embed")),
    }


def ssd_chunked(x, dt, A, B, C, chunk: int, h0=None) -> Tuple:
    """The recurrence over a sequence, chunk by chunk (float32).

    x (b, s, heads, p); dt (b, s, heads), after the softplus; A (heads,);
    B, C (b, s, n); h0 (b, heads, p, n) or None for zeros.  A length that
    is not a multiple of ``chunk`` is padded with dt = 0, which leaves
    the state as it is.  Returns y (b, s, heads, p) without the D term,
    and the state after the last token.
    """
    b, s, nh, p = x.shape
    n = B.shape[-1]
    L = min(chunk, s)
    pad = -s % L
    if pad:
        x, dt, B, C = (jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
                       for t in (x, dt, B, C))
    c = (s + pad) // L
    x = x.reshape(b, c, L, nh, p)
    B = B.reshape(b, c, L, n)
    C = C.reshape(b, c, L, n)
    dt = dt.reshape(b, c, L, nh).swapaxes(2, 3)         # (b, c, heads, L)
    acum = jnp.cumsum(dt * A[:, None], axis=-1)        # log decay to t
    # decay from key s to query t inside a chunk, 0 above the diagonal
    seg = acum[..., :, None] - acum[..., None, :]      # (b,c,heads,t,s)
    decay = jnp.exp(jnp.where(jnp.tril(jnp.ones((L, L), bool)), seg,
                              -jnp.inf))
    w = jnp.einsum("bctn,bcsn->bcts", C, B)[:, :, None] * decay \
        * dt[..., None, :]
    y = jnp.einsum("bchts,bcshp->bcthp", w, x)
    # each chunk's own part of the state at its end, then the scan
    to_end = (jnp.exp(acum[..., -1:] - acum) * dt).swapaxes(2, 3)
    own = jnp.einsum("bcshp,bcsn->bchpn", x * to_end[..., None], B)
    if h0 is None:
        h0 = jnp.zeros((b, nh, p, n), jnp.float32)

    def carry(h, inp):
        d, st = inp
        return d[:, :, None, None] * h + st, h

    h_last, h_in = jax.lax.scan(
        carry, h0, (jnp.exp(acum[..., -1]).swapaxes(0, 1),
                    own.swapaxes(0, 1)))
    h_in = h_in.swapaxes(0, 1)                         # state entering chunk
    y = y + jnp.einsum("bctn,bchpn->bcthp", C, h_in) \
        * jnp.exp(acum).swapaxes(2, 3)[..., None]
    return y.reshape(b, c * L, nh, p)[:, :s], h_last


def ssd_step(x, dt, A, B, C, h) -> Tuple:
    """One token: x (b, heads, p), dt (b, heads), B, C (b, n), h (b, heads,
    p, n) -> (y (b, heads, p) without the D term, new h)."""
    h = jnp.exp(dt * A)[:, :, None, None] * h \
        + (dt[..., None] * x)[..., None] * B[:, None, None, :]
    return jnp.sum(h * C[:, None, None, :], axis=-1), h


def apply_mamba2(p: Dict, x, cfg, sharder: Sharder = IDENTITY_SHARDER,
                 conv_state=None, ssm_state=None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (b, s, d) -> (out, new_conv_state, new_ssm_state).  One token
    with a carried state steps the recurrence; anything else runs the
    chunked form from ``ssm_state`` (zeros where None)."""
    b, s, _ = x.shape
    nh, hp, n, di = cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state, cfg.d_inner
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    zxbcdt = sharder.ac(zxbcdt, ("batch", None, "mlp"))
    z, xbc, dt = jnp.split(zxbcdt, [di, di + conv_dim(cfg)], axis=-1)
    xbc, new_conv = causal_conv(p, xbc, conv_state)
    xs, B, C = jnp.split(jax.nn.silu(xbc).astype(jnp.float32),
                         [di, di + n], axis=-1)
    xs = xs.reshape(b, s, nh, hp)
    dt = jax.nn.softplus(dt.astype(jnp.float32)
                         + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    if s == 1 and ssm_state is not None:
        with jax.named_scope("mamba2.step"):
            y, new_ssm = ssd_step(xs[:, 0], dt[:, 0], A, B[:, 0], C[:, 0],
                                  ssm_state)
            y = y[:, None]
    else:
        with jax.named_scope("mamba2.ssd"):
            y, new_ssm = ssd_chunked(xs, dt, A, B, C, cfg.ssm_chunk,
                                     ssm_state)
    y = y + p["D"].astype(jnp.float32)[:, None] * xs
    # gated RMSNorm over all d_inner channels (one group)
    y = y.reshape(b, s, di) * jax.nn.silu(z.astype(jnp.float32))
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.norm_eps) * p["norm"]["scale"]
    y = y.astype(x.dtype)
    if s > 1:
        y = sharder.ac(y, ("batch", "seq", None))
    return jnp.einsum("bsd,de->bse", y, p["out_proj"]), new_conv, new_ssm
