"""Reference for a hybrid decoder of Mamba-2 and attention layers, of the
Granite-4.0-H kind [hf ibm-granite/granite-4.0-h-micro; Mamba-2:
arXiv:2405.21060].

Token embedding times ``embed_scale``; ``n_layers`` pre-RMSNorm blocks,
attention where ``layer % attn_every == attn_offset`` and Mamba-2
elsewhere, each branch added times ``residual_scale``:

    x += residual_scale * mixer(rmsnorm(x))
    x += residual_scale * mlp(rmsnorm(x))

- attention: grouped-query, causal softmax of the scores times
  ``attn_multiplier``, no positional encoding;
- Mamba-2, as its definition reads, one token after another: in_proj to
  z, xBC and dt; a causal depthwise conv over xBC with bias, then SiLU,
  split into x (heads x head_dim), B and C (one group); per head
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``,
  ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t + D x_t``
  in float32; then RMSNorm(y * silu(z)) * w over all channels and
  out_proj;
- MLP: SwiGLU.

A final RMSNorm, and logits from the tied embedding table over
``logits_scaling``.

Weights live in the tree the harness hands the program: ``embed/table``,
``layers`` (a tuple with one tree per position of the period, each
stacked over the periods: ``norm1/scale``, ``mixer`` (attention ``wq, wk,
wv, wo``; Mamba-2 ``in_proj, conv_w, conv_b, dt_bias, A_log, D,
norm/scale, out_proj``), ``norm2/scale``, ``ffn/{wi, wg, wo}``),
``final_norm/scale``.

Also the operation and byte counts of the family, from the sizes alone.
"""

from __future__ import annotations

import math
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from chipbench.refs import common as C

ATTN = ("mixer",)
# The tied table's std times sqrt(d_model).  The head reads the current
# token's own row, scaled by 12 at the embedding, so its logit stands
# about 12 * sqrt(d_model) * std / rms(residual) standard deviations above
# the others: at 0.1 / sqrt(d_model) about one, where a std of 1 (or the
# published family's 0.02) puts every row's largest logit on the current
# token whatever the layers compute.
TABLE_STD_SQRT_D = 0.1
CONV_STD = 0.5 / math.sqrt(3.0)   # PyTorch's U(-1/2, 1/2) for 4 taps


def leaf_init(path: Sequence[str], shape):
    """A_log and dt_bias are normal (std 1): the harness draws normals or
    constants only, where Mamba-2 draws A uniform in [1, 16] and dt
    log-uniform in [0.001, 0.1]; this gives decays per step from near 1
    to near 0 all the same (the configuration lists it under
    ``assumed``)."""
    name = path[-1]
    if name in ("scale", "D"):
        return "ones", 0.0
    if name == "table":
        return "normal", TABLE_STD_SQRT_D / math.sqrt(shape[-1])
    if name in ("A_log", "dt_bias"):
        return "normal", 1.0
    if name in ("conv_w", "conv_b"):
        return "normal", CONV_STD
    return C.fan_in_std(path, shape, ATTN)


def _rms(x, w, eps: float):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * w


def is_attention(s: Dict, layer: int) -> bool:
    return layer % s["attn_every"] == s["attn_offset"]


def _attention(rnd, x, a, s: Dict, block: int = 512):
    """Causal GQA attention of one sequence, queries taken ``block`` at a
    time so that the scores of a long sequence fit."""
    t = x.shape[0]
    rep = s["n_heads"] // s["n_kv_heads"]
    q = C.mm(rnd, "td,dhk->thk", x, a["wq"])
    k = jnp.repeat(C.mm(rnd, "td,dhk->thk", x, a["wk"]), rep, 1)
    v = jnp.repeat(C.mm(rnd, "td,dhk->thk", x, a["wv"]), rep, 1)
    block = block if t % block == 0 else t

    def rows(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        sc = C.mm(rnd, "qhd,khd->hqk", qb, k) * s["attn_multiplier"]
        mask = jnp.arange(t)[None, :] <= i * block + jnp.arange(block)[:, None]
        sc = jnp.where(mask[None], sc, -jnp.inf)
        return C.mm(rnd, "hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v)

    o = jax.lax.map(rows, jnp.arange(t // block)).reshape(q.shape)
    return C.mm(rnd, "thk,hkd->td", o, a["wo"])


def _mamba2(rnd, x, m, s: Dict):
    """The Mamba-2 mixer of one sequence, as the recurrence."""
    t = x.shape[0]
    nh, hp, n = s["ssm_heads"], s["ssm_head_dim"], s["d_state"]
    di = nh * hp
    zxbcdt = C.mm(rnd, "td,de->te", x, m["in_proj"])
    z, xbc, dt = jnp.split(zxbcdt, [di, 2 * di + 2 * n], axis=-1)
    taps = m["conv_w"].shape[0]
    ext = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1])), xbc])
    xbc = jax.nn.silu(sum(ext[j:j + t] * m["conv_w"][j] for j in range(taps))
                      + m["conv_b"])
    xs, B, Cm = jnp.split(xbc, [di, di + n], axis=-1)
    xs = xs.reshape(t, nh, hp)
    dt = jax.nn.softplus(dt + m["dt_bias"])
    A = -jnp.exp(m["A_log"])

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t * A)[:, None, None] * h \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t
        return h, jnp.sum(h * c_t, axis=-1)

    # 16 steps to a loop iteration: the same recurrence, step by step,
    # with fewer trips round the loop
    _, y = jax.lax.scan(step, jnp.zeros((nh, hp, n)), (xs, dt, B, Cm),
                        unroll=16)
    y = (y + m["D"][:, None] * xs).reshape(t, di) * jax.nn.silu(z)
    y = _rms(y, m["norm"]["scale"], s["norm_eps"])
    return C.mm(rnd, "te,ed->td", y, m["out_proj"])


def _layer(rnd, x, lp, s: Dict, attention: bool):
    eps, rs = s["norm_eps"], s["residual_scale"]
    h = _rms(x, lp["norm1"]["scale"], eps)
    mix = (_attention(rnd, h, lp["mixer"], s) if attention
           else _mamba2(rnd, h, lp["mixer"], s))
    x = x + rs * mix
    h = _rms(x, lp["norm2"]["scale"], eps)
    f = lp["ffn"]
    g = jax.nn.silu(C.mm(rnd, "td,df->tf", h, f["wi"])) * C.mm(
        rnd, "td,df->tf", h, f["wg"])
    return x + rs * C.mm(rnd, "tf,fd->td", g, f["wo"])


def logits(params, tokens, s: Dict, rnd=C.exact):
    """Logits (t, vocab_size) of one sequence ``tokens`` (t,)."""
    table = params["embed"]["table"].astype(jnp.float32)
    x = table[tokens] * s["embed_scale"]
    period = s["attn_every"]

    def body(x, lps):
        for pos in range(period):
            x = _layer(rnd, x, lps[pos], s, is_attention(s, pos))
        return x, None

    layers = jax.tree.map(lambda w: w.astype(jnp.float32), params["layers"])
    x, _ = jax.lax.scan(body, x, layers)
    x = _rms(x, params["final_norm"]["scale"], s["norm_eps"])
    return C.mm(rnd, "td,vd->tv", x, table[:s["vocab_size"]]) \
        / s["logits_scaling"]


# ---------------------------------------------------------------------------
# counts from shapes
# ---------------------------------------------------------------------------

def n_attention(s: Dict) -> int:
    return sum(is_attention(s, li) for li in range(s["n_layers"]))


def _conv_dim(s: Dict) -> int:
    return s["ssm_heads"] * s["ssm_head_dim"] + 2 * s["d_state"]


def _attn_params(s: Dict) -> int:
    return s["d_model"] * s["d_head"] * (2 * s["n_heads"]
                                         + 2 * s["n_kv_heads"])


def _mamba_matrices(s: Dict) -> int:
    di = s["ssm_heads"] * s["ssm_head_dim"]
    return s["d_model"] * (2 * di + _conv_dim(s) + s["ssm_heads"])


def _mamba_params(s: Dict) -> int:
    """in_proj and out_proj; conv taps and bias; dt_bias, A_log, D; the
    gated norm's scale."""
    di = s["ssm_heads"] * s["ssm_head_dim"]
    return (_mamba_matrices(s) + (s["d_conv"] + 1) * _conv_dim(s)
            + 3 * s["ssm_heads"] + di)


def matmul_params(s: Dict) -> int:
    """Parameters: the tied table once, attention, the Mamba-2 mixers
    (their vectors too) and the MLPs.  The blocks' norm scales are left
    out."""
    na = n_attention(s)
    return (s["vocab_size"] * s["d_model"] + na * _attn_params(s)
            + (s["n_layers"] - na) * _mamba_params(s)
            + s["n_layers"] * 3 * s["d_model"] * s["d_ff"])


def weight_bytes(s: Dict, dtype_bytes: int = 2) -> int:
    """Bytes a forward step reads: every parameter once, the tied table
    as the head (the embedding reads only its tokens' rows)."""
    return matmul_params(s) * dtype_bytes


def kv_bytes_per_token(s: Dict, dtype_bytes: int = 2) -> int:
    return 2 * n_attention(s) * s["n_kv_heads"] * s["d_head"] * dtype_bytes


def state_bytes_per_seq(s: Dict, conv_bytes: int = 2) -> int:
    """One sequence's recurrent state: per Mamba-2 layer the float32 SSM
    state (heads x head_dim x d_state) and the conv's last d_conv - 1
    inputs (bf16)."""
    ssm = 4 * s["ssm_heads"] * s["ssm_head_dim"] * s["d_state"]
    conv = conv_bytes * (s["d_conv"] - 1) * _conv_dim(s)
    return (s["n_layers"] - n_attention(s)) * (ssm + conv)


def ssm_token_flops(s: Dict) -> float:
    """The recurrence's own operations per token in one Mamba-2 layer,
    whatever algorithm runs: 5 per state element (decay, drive, their
    sum, the multiply-add of the read-out) and the conv's multiply-adds."""
    return (5.0 * s["ssm_heads"] * s["ssm_head_dim"] * s["d_state"]
            + 2.0 * s["d_conv"] * _conv_dim(s))


def forward_flops(s: Dict, tokens: float, attended: float,
                  heads: Optional[float] = None) -> float:
    """Multiply-adds x 2 of ``tokens`` tokens through the projections and
    MLPs, of the head for ``heads`` of them (all by default), of
    attention over ``attended`` (query, key) pairs in each attention
    layer, and the recurrence of each Mamba-2 layer."""
    heads = tokens if heads is None else heads
    na = n_attention(s)
    ns = s["n_layers"] - na
    proj = (na * _attn_params(s) + ns * _mamba_matrices(s)
            + s["n_layers"] * 3 * s["d_model"] * s["d_ff"])
    return (2.0 * proj * tokens
            + 2.0 * s["vocab_size"] * s["d_model"] * heads
            + na * C.count_attention_flops(s["n_heads"], s["d_head"],
                                           attended)
            + ns * ssm_token_flops(s) * tokens)
