"""Plain float32 building blocks that the reference families share.

Written from the published descriptions, with nothing imported from the
program.  Every matrix product goes through ``mm(rnd, ...)``: ``rnd`` is
``exact`` in the reference and ``fp8`` in its control, which rounds both
operands to float8 (e4m3, one scale per tensor), the step below the
bfloat16 that the configurations state.  Call the reference under
``jax.default_matmul_precision("highest")``: without it a TPU multiplies
float32 in bfloat16.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, Sequence

import jax
import jax.numpy as jnp

F8_MAX = 448.0   # largest float8_e4m3fn


def exact(x):
    return x


@jax.custom_vjp
def fp8(x):
    """``x`` rounded to float8 e4m3 under one scale for the tensor.  The
    gradient passes straight through, in float32: a backward pass
    multiplies the rounded operands saved by the forward pass."""
    x = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / F8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


fp8.defvjp(lambda x: (fp8(x), None), lambda _, g: (g,))


PRECISIONS: Dict[str, Callable] = {"float32": exact, "fp8": fp8}


def mm(rnd: Callable, eq: str, a, b):
    return jnp.einsum(eq, rnd(a), rnd(b))


def layer_norm(x, p: Dict, eps: float):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def rope(x, positions, rot: int, theta: float):
    """Rotary embedding on the first ``rot`` dims of each head, halves
    rotated (GPT-NeoX style); x: (t, heads, head_dim)."""
    if rot == 0:
        return x
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv      # (t, rot/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def attention(rnd: Callable, q, k, v, causal: bool):
    """Softmax attention of one sequence; q (tq, h, d), k/v (tk, h, d)."""
    s = mm(rnd, "qhd,khd->hqk", q, k) / math.sqrt(q.shape[-1])
    if causal:
        tq, tk = q.shape[0], k.shape[0]
        mask = jnp.arange(tk)[None, :] <= jnp.arange(tq)[:, None]
        s = jnp.where(mask[None], s, -jnp.inf)
    return mm(rnd, "hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


def nll(logits, labels):
    """Negative log-likelihood of each label; logits (..., vocab)."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]


def adamw(params, grads, m, v, step, opt: Dict):
    """One AdamW step with global-norm clipping and the learning rate of
    a linear warm-up from 0 then a cosine to a tenth of the peak.
    ``step`` counts from 0.  Weight decay applies to every leaf."""
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                        for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    lr = lr_at(step, opt)
    t = jnp.asarray(step, jnp.float32) + 1
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)

    def upd(p, m_, v_):
        mh = m_ / (1 - b1 ** t)
        vh = v_ / (1 - b2 ** t)
        return p - lr * (mh / (jnp.sqrt(vh) + eps)
                         + opt["weight_decay"] * p)

    return jax.tree.map(upd, params, m, v), m, v


def lr_at(step, opt: Dict):
    peak, warm, total = opt["peak_lr"], opt["warmup"], opt["total_steps"]
    s = jnp.asarray(step, jnp.float32)
    frac = jnp.clip((s - warm) / max(total - warm, 1), 0.0, 1.0)
    cos = peak * (0.1 + 0.9 * 0.5 * (1 + jnp.cos(jnp.pi * frac)))
    return jnp.where(s < warm, peak * s / max(warm, 1), cos)


def count_attention_flops(heads: int, head_dim: int,
                          attended: float) -> float:
    """Scores and weighted sum: 4 * heads * head_dim per (query, key)."""
    return 4.0 * heads * head_dim * attended


def fan_in_std(path: Sequence[str], shape, attn_parents: Sequence[str]):
    """Normal with std 1/sqrt(fan-in), contraction axes from the leaf's
    role: q/k/v (.., d, heads, hd) contract d; an attention output
    (.., heads, hd, d) contracts heads*hd; any other matrix (.., in, out)
    contracts in."""
    name, parent = path[-1], path[-2] if len(path) > 1 else ""
    if name in ("wq", "wk", "wv"):
        fan = shape[-3]
    elif name == "wo" and parent in attn_parents:
        fan = shape[-3] * shape[-2]
    else:
        fan = shape[-2]
    return "normal", 1.0 / math.sqrt(fan)
