"""Reference for a Whisper-style encoder-decoder transformer
[arXiv:2212.04356]: the encoder takes frame embeddings (the stub frontend
in place of the two convolutions over the log-mel spectrogram), adds
learned positions and runs pre-LayerNorm bidirectional blocks; the
decoder embeds tokens with learned positions and runs pre-LayerNorm
blocks of causal self-attention, cross-attention to the encoder output
and a GELU MLP; a final LayerNorm and an output head.  As configured
(stated departures from the paper): no biases on the projections, GELU
in its tanh form, an untied head.

Weights live in the tree the harness hands the program: ``embed/{table,
head, pos_table}``, ``enc_pos``, ``enc_layers/{norm1, attn, norm2, ffn}``,
``enc_norm``, ``dec_layers/{norm1, self_attn, norm_x, cross_attn, norm2,
ffn}``, ``final_norm``; layers stacked.
"""

from __future__ import annotations

from typing import Dict, Sequence

import jax
import jax.numpy as jnp

from chipbench.refs import common as C

ATTN = ("attn", "self_attn", "cross_attn")


def leaf_init(path: Sequence[str], shape):
    name = path[-1]
    if name == "scale":
        return "ones", 0.0
    if name == "bias":
        return "zeros", 0.0
    if name in ("pos_table", "enc_pos"):
        return "normal", 0.02
    if name == "table":
        return "normal", 1.0
    return C.fan_in_std(path, shape, ATTN)


def _heads(rnd, x, w):
    return C.mm(rnd, "td,dhk->thk", x, w)


def _mlp(rnd, x, f):
    return C.mm(rnd, "tf,fd->td",
                jax.nn.gelu(C.mm(rnd, "td,df->tf", x, f["wi"]),
                            approximate=True), f["wo"])


def _attend(rnd, a, xq, xkv, causal):
    o = C.attention(rnd, _heads(rnd, xq, a["wq"]), _heads(rnd, xkv, a["wk"]),
                    _heads(rnd, xkv, a["wv"]), causal)
    return C.mm(rnd, "thk,hkd->td", o, a["wo"])


def encode(params, frames, s: Dict, rnd=C.exact):
    eps = s["norm_eps"]

    @jax.checkpoint
    def body(x, lp):
        h = C.layer_norm(x, lp["norm1"], eps)
        x = x + _attend(rnd, lp["attn"], h, h, False)
        return x + _mlp(rnd, C.layer_norm(x, lp["norm2"], eps), lp["ffn"]), \
            None

    x, _ = jax.lax.scan(body, frames + params["enc_pos"],
                        params["enc_layers"])
    return C.layer_norm(x, params["enc_norm"], eps)


def logits(params, tokens, frames, s: Dict, rnd=C.exact):
    """Logits (t, vocab_size) of one sequence: ``tokens`` (t,), ``frames``
    (enc_seq, d_model)."""
    eps = s["norm_eps"]
    enc = encode(params, frames, s, rnd)
    emb = params["embed"]
    x = emb["table"][tokens] + emb["pos_table"][jnp.arange(tokens.shape[0])]

    @jax.checkpoint
    def body(x, lp):
        h = C.layer_norm(x, lp["norm1"], eps)
        x = x + _attend(rnd, lp["self_attn"], h, h, True)
        h = C.layer_norm(x, lp["norm_x"], eps)
        x = x + _attend(rnd, lp["cross_attn"], h, enc, False)
        return x + _mlp(rnd, C.layer_norm(x, lp["norm2"], eps), lp["ffn"]), \
            None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    x = C.layer_norm(x, params["final_norm"], eps)
    return C.mm(rnd, "td,dv->tv", x, emb["head"][:, :s["vocab_size"]])


def loss_sum(params, batch, s: Dict, rnd=C.exact):
    """Sum over the rows of ``batch`` of the masked next-token NLL."""
    def row(tokens, labels, mask, frames):
        return jnp.sum(C.nll(logits(params, tokens, frames, s, rnd), labels)
                       * mask)
    return jnp.sum(jax.vmap(row)(batch["tokens"], batch["labels"],
                                 batch["mask"], batch["enc_embeds"]))


# ---------------------------------------------------------------------------
# counts from shapes
# ---------------------------------------------------------------------------

def _sq(s):
    return s["d_model"] * s["n_heads"] * s["d_head"]


def matmul_params(s: Dict) -> int:
    d, f = s["d_model"], s["d_ff"]
    enc = s["enc_layers"] * (4 * _sq(s) + 2 * d * f)
    dec = s["n_layers"] * (8 * _sq(s) + 2 * d * f)
    return enc + dec + 2 * s["vocab_size"] * d


def forward_flops_sample(s: Dict, seq: int) -> float:
    """One sample: ``enc_seq`` frames through the encoder, ``seq``
    decoder tokens (causal), cross-attention to every frame."""
    d, f, t_enc = s["d_model"], s["d_ff"], s["enc_seq"]
    hd, h = s["d_head"], s["n_heads"]
    enc = s["enc_layers"] * (2.0 * (4 * _sq(s) + 2 * d * f) * t_enc
                             + C.count_attention_flops(h, hd, t_enc * t_enc))
    dec = s["n_layers"] * (
        2.0 * (6 * _sq(s) + 2 * d * f) * seq        # self q/k/v/o, cross q/o, mlp
        + 2.0 * 2 * _sq(s) * t_enc                  # cross k/v of the frames
        + C.count_attention_flops(h, hd, seq * (seq + 1) / 2 + seq * t_enc))
    head = 2.0 * d * s["vocab_size"] * seq
    return enc + dec + head


def train_flops(s: Dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward), no recomputation."""
    return 3.0 * batch * forward_flops_sample(s, seq)
