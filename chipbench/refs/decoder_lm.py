"""Reference for a dense decoder-only LM of the StableLM-2 kind: token
embedding, pre-LayerNorm blocks of multi-head attention with rotary
embedding on a share of each head (GPT-NeoX halves), a SwiGLU MLP, a final
LayerNorm and an untied output head.  Biases: none on the projections
(StableLM-2-1.6B has them on q/k/v; the configuration states the
departure).

Weights live in the tree the harness hands the program:
``embed/{table, head}``, ``layers/{norm1, mixer/{wq, wk, wv, wo}, norm2,
ffn/{wi, wg, wo}}`` stacked over layers, ``final_norm``.  Vocabulary rows
past ``vocab_size`` (padding) are never read.

Also the operation and byte counts of the family, from the sizes alone.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from chipbench.refs import common as C

ATTN = ("mixer",)


def leaf_init(path: Sequence[str], shape):
    name = path[-1]
    if name == "scale":
        return "ones", 0.0
    if name == "bias":
        return "zeros", 0.0
    if name == "table":
        return "normal", 1.0
    return C.fan_in_std(path, shape, ATTN)


def _rot(s: Dict) -> int:
    rot = int(s["d_head"] * s["rope_pct"])
    return rot - rot % 2


def _layer(x, lp, s: Dict, rnd, positions):
    eps = s["norm_eps"]
    h = C.layer_norm(x, lp["norm1"], eps)
    a = lp["mixer"]
    q = C.mm(rnd, "td,dhk->thk", h, a["wq"])
    k = C.mm(rnd, "td,dhk->thk", h, a["wk"])
    v = C.mm(rnd, "td,dhk->thk", h, a["wv"])
    rep = s["n_heads"] // s["n_kv_heads"]
    q = C.rope(q, positions, _rot(s), s["rope_theta"])
    k = jnp.repeat(C.rope(k, positions, _rot(s), s["rope_theta"]), rep, 1)
    v = jnp.repeat(v, rep, 1)
    x = x + C.mm(rnd, "thk,hkd->td", C.attention(rnd, q, k, v, True),
                 a["wo"])
    h = C.layer_norm(x, lp["norm2"], eps)
    f = lp["ffn"]
    g = jax.nn.silu(C.mm(rnd, "td,df->tf", h, f["wi"])) * C.mm(
        rnd, "td,df->tf", h, f["wg"])
    return x + C.mm(rnd, "tf,fd->td", g, f["wo"])


def logits(params, tokens, s: Dict, rnd=C.exact):
    """Logits (t, vocab_size) of one sequence ``tokens`` (t,)."""
    x = params["embed"]["table"][tokens].astype(jnp.float32)
    positions = jnp.arange(tokens.shape[0])

    def body(x, lp):
        return _layer(x, lp, s, rnd, positions), None

    layers = jax.tree.map(lambda w: w.astype(jnp.float32), params["layers"])
    x, _ = jax.lax.scan(body, x, layers)
    x = C.layer_norm(x, params["final_norm"], s["norm_eps"])
    head = params["embed"]["head"][:, :s["vocab_size"]].astype(jnp.float32)
    return C.mm(rnd, "td,dv->tv", x, head)


# ---------------------------------------------------------------------------
# counts from shapes
# ---------------------------------------------------------------------------

def layer_matmul_params(s: Dict) -> int:
    d, hd = s["d_model"], s["d_head"]
    attn = d * hd * (2 * s["n_heads"] + 2 * s["n_kv_heads"])
    return attn + 3 * d * s["d_ff"]


def matmul_params(s: Dict) -> int:
    """Parameters of the matrices: embedding table, head, projections.
    Norm scales and biases are left out."""
    return (2 * s["vocab_size"] * s["d_model"]
            + s["n_layers"] * layer_matmul_params(s))


def kv_bytes_per_token(s: Dict, dtype_bytes: int = 2) -> int:
    return 2 * s["n_layers"] * s["n_kv_heads"] * s["d_head"] * dtype_bytes


def weight_bytes(s: Dict, dtype_bytes: int = 2) -> int:
    """Bytes a forward step must read: every matrix but the embedding
    table, of which a step reads only its tokens' rows."""
    return (matmul_params(s) - s["vocab_size"] * s["d_model"]) * dtype_bytes


def forward_flops(s: Dict, tokens: float, attended: float,
                  heads: Optional[float] = None) -> float:
    """Multiply-adds x 2 of ``tokens`` tokens through the projections,
    of the head for ``heads`` of them (all by default), and of attention
    over ``attended`` (query, key) pairs in all."""
    heads = tokens if heads is None else heads
    return (2.0 * s["n_layers"] * layer_matmul_params(s) * tokens
            + 2.0 * s["vocab_size"] * s["d_model"] * heads
            + s["n_layers"] * C.count_attention_flops(
                s["n_heads"], s["d_head"], attended))


def train_flops(s: Dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward), no recomputation, causal."""
    return 3.0 * forward_flops(s, batch * seq, batch * seq * (seq + 1) / 2)
