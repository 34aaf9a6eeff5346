"""Mamba (S6) selective-state-space block [arXiv:2312.00752], used by the
Jamba hybrid architecture [arXiv:2403.19887].

Training path: chunked parallel scan (outer ``lax.scan`` over chunks
carrying the (d_inner, d_state) state, inner ``associative_scan`` over
the chunk).  Decode path: O(1) single-step recurrence with a carried
(conv_state, ssm_state) — what makes ``long_500k`` runnable for the
hybrid family.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Sharder, IDENTITY_SHARDER, param, split_key


def dt_rank(cfg) -> int:
    return max(1, math.ceil(cfg.d_model / 16))


def init_mamba_block(key, cfg) -> Dict:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.d_state
    r = dt_rank(cfg)
    ks = split_key(key, 8)
    return {
        "in_proj": param(ks[0], (d, 2 * di), ("embed", "mlp")),
        "conv_w": param(ks[1], (cfg.d_conv, di), (None, "mlp"), scale=0.5),
        "conv_b": param(ks[2], (di,), ("mlp",), init="zeros"),
        "x_proj": param(ks[3], (di, r + 2 * n), ("mlp", None)),
        "dt_proj": param(ks[4], (r, di), (None, "mlp"), scale=0.1),
        "dt_bias": param(ks[5], (di,), ("mlp",), init="zeros"),
        # S4D-real init: A = -(1..n) per channel
        "A_log": {"v": jnp.broadcast_to(
            jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n)).copy(),
            "axes": ("mlp", None)},
        "D": param(ks[6], (di,), ("mlp",), init="ones"),
        "out_proj": param(ks[7], (di, d), ("mlp", "embed")),
    }


def causal_conv(p: Dict, x, conv_state: Optional[jnp.ndarray] = None):
    """Depthwise causal conv via shifted adds.  x: (b, s, di).

    conv_state: (b, d_conv-1, di) trailing inputs from the previous
    segment (decode); returns (y, new_conv_state).
    """
    taps = p["conv_w"].shape[0]
    b, s, di = x.shape
    if conv_state is None:
        conv_state = jnp.zeros((b, taps - 1, di), x.dtype)
    ext = jnp.concatenate([conv_state, x], axis=1)     # (b, s+taps-1, di)
    y = jnp.zeros_like(x)
    for i in range(taps):
        y = y + ext[:, i:i + s] * p["conv_w"][i]
    y = y + p["conv_b"]
    new_state = ext[:, -(taps - 1):] if taps > 1 else conv_state
    return y, new_state


def _ssm_params(p: Dict, xc, cfg):
    """xc: (b, s, di) post-conv.  Returns decay, drive, C."""
    r = dt_rank(cfg)
    n = cfg.d_state
    proj = jnp.einsum("bsd,dk->bsk", xc, p["x_proj"])
    dt_r, B, C = jnp.split(proj, [r, r + n], axis=-1)
    dt = jnp.einsum("bsr,rd->bsd", dt_r, p["dt_proj"]) + p["dt_bias"]
    dt = jax.nn.softplus(dt.astype(jnp.float32))       # (b,s,di)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))        # (di,n)
    decay = jnp.exp(dt[..., None] * A)                  # (b,s,di,n)
    drive = (dt * xc.astype(jnp.float32))[..., None] \
        * B[:, :, None, :].astype(jnp.float32)          # (b,s,di,n)
    return decay, drive, C.astype(jnp.float32)


def selective_scan_chunked(p: Dict, xc, cfg, h0=None, chunk: int = 256,
                           remat: bool = True):
    """Chunked selective scan computing SSM params per chunk.

    xc: (b, s, di) post-conv activations.  The (b, s, di, n) decay/drive
    tensors are 2*d_state times larger than the activations, so they are
    built INSIDE the chunk loop (and rematerialized in the backward
    pass) — materializing them for the whole sequence would dominate
    training memory (measured: ~17 GB/layer at jamba train_4k scale).

    Returns (y (b, s, di) f32  = sum_n h * C, h_last (b, di, n)).
    """
    b, s, di = xc.shape
    n = cfg.d_state
    if s % chunk:
        chunk = s
    nc = s // chunk
    if h0 is None:
        h0 = jnp.zeros((b, di, n), jnp.float32)

    def combine(e1, e2):
        a1, b1 = e1
        a2, b2 = e2
        return a2 * a1, a2 * b1 + b2

    # slice chunks in-body (a staged (nc, b, chunk, di) transpose copy
    # of xc per mamba sublayer dominated prefill_32k memory) and emit
    # bf16 chunk outputs (f32 kept only for the recurrence itself).
    def body(carry, _):
        h, i = carry
        xck = jax.lax.dynamic_slice_in_dim(xc, i * chunk, chunk, axis=1)
        decay, drive, C = _ssm_params(p, xck, cfg)     # (b,chunk,di,n)
        ca, cb = jax.lax.associative_scan(combine, (decay, drive), axis=1)
        h_all = ca * h[:, None] + cb
        y = jnp.einsum("bsdn,bsn->bsd", h_all, C)
        return (h_all[:, -1], i + 1), y.astype(xc.dtype)

    scan_body = jax.checkpoint(body) if remat else body
    (h_last, _), ys = jax.lax.scan(scan_body, (h0, 0), None, length=nc)
    y = ys.transpose(1, 0, 2, 3).reshape(b, s, di).astype(jnp.float32)
    return y, h_last


def apply_mamba(p: Dict, x, cfg, sharder: Sharder = IDENTITY_SHARDER,
                conv_state=None, ssm_state=None, chunk: int = 256,
                remat: bool = True
                ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (b, s, d) -> (out, new_conv_state, new_ssm_state)."""
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xz = sharder.ac(xz, ("batch", None, "mlp"))
    xin, z = jnp.split(xz, 2, axis=-1)
    xc, new_conv = causal_conv(p, xin, conv_state)
    xc = jax.nn.silu(xc)

    if x.shape[1] == 1 and ssm_state is not None:
        decay, drive, C = _ssm_params(p, xc, cfg)
        h = decay[:, 0] * ssm_state + drive[:, 0]       # (b,di,n)
        new_ssm = h
        y = jnp.einsum("bdn,bn->bd", h, C[:, 0])[:, None]
    else:
        y, new_ssm = selective_scan_chunked(p, xc, cfg, ssm_state, chunk,
                                            remat=remat)

    y = y + p["D"].astype(jnp.float32) * xc.astype(jnp.float32)
    y = y.astype(x.dtype) * jax.nn.silu(z)
    # sequence-parallel out-projection: reshard (seq <- model, di full)
    # BEFORE contracting over di.  Keeping di sharded here makes XLA
    # materialize a full-sequence f32 partial-sum of (b, s, d_model) per
    # sublayer and all-reduce it — measured ~2 GB/sublayer at
    # prefill_32k; the all-to-all reshard moves bf16 and the contraction
    # becomes local.
    if x.shape[1] > 1:
        y = sharder.ac(y, ("batch", "seq", None))
    out = jnp.einsum("bsd,de->bse", y, p["out_proj"])
    return out, new_conv, new_ssm
