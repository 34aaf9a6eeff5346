"""Whisper-style encoder-decoder [arXiv:2212.04356].

The conv/mel frontend is a STUB per the assignment: the encoder input
is precomputed frame embeddings (batch, enc_seq, d_model) provided by
``input_specs()``.  The encoder is a bidirectional transformer; the
decoder adds causal self-attention plus cross-attention whose K/V come
from the encoder output (cached at prefill for decode).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as ll
from repro.models.common import IDENTITY_SHARDER, Sharder, split_key, stack_inits
from repro.models.transformer import scan_layers


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_enc_layer(key, cfg) -> Dict:
    ks = split_key(key, 4)
    return {
        "norm1": ll.init_norm(ks[0], cfg, cfg.d_model),
        "attn": ll.init_attention(ks[1], cfg),
        "norm2": ll.init_norm(ks[2], cfg, cfg.d_model),
        "ffn": ll.init_mlp(ks[3], cfg),
    }


def _init_dec_layer(key, cfg) -> Dict:
    ks = split_key(key, 6)
    return {
        "norm1": ll.init_norm(ks[0], cfg, cfg.d_model),
        "self_attn": ll.init_attention(ks[1], cfg),
        "norm_x": ll.init_norm(ks[2], cfg, cfg.d_model),
        "cross_attn": ll.init_attention(ks[3], cfg),
        "norm2": ll.init_norm(ks[4], cfg, cfg.d_model),
        "ffn": ll.init_mlp(ks[5], cfg),
    }


def init_encdec(key, cfg) -> Dict:
    ks = split_key(key, 6)
    return {
        "embed": ll.init_embedding(ks[0], cfg),
        "enc_pos": {"v": 0.02 * jax.random.normal(
            ks[1], (cfg.enc_seq, cfg.d_model), jnp.float32),
            "axes": (None, "embed")},
        "enc_layers": stack_inits(lambda k: _init_enc_layer(k, cfg), ks[2],
                                  cfg.enc_layers),
        "enc_norm": ll.init_norm(ks[3], cfg, cfg.d_model),
        "dec_layers": stack_inits(lambda k: _init_dec_layer(k, cfg), ks[4],
                                  cfg.n_layers),
        "final_norm": ll.init_norm(ks[5], cfg, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# encoder
# ---------------------------------------------------------------------------

def encode(params: Dict, enc_embeds, cfg, sharder: Sharder,
           chunk: int = 2048):
    """enc_embeds: (b, enc_seq, d) stub frontend output."""
    x = enc_embeds + params["enc_pos"]
    x = sharder.ac(x, ("batch", "seq", None))
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s), (b, s))

    def body(x, lp):
        def fn(x, lp):
            h = ll.apply_norm(lp["norm1"], x, cfg)
            # bidirectional: reuse attention_train with cross=True trick
            k = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, lp["attn"]["wv"])
            k = ll._repeat_kv(k, cfg.n_heads)
            v = ll._repeat_kv(v, cfg.n_heads)
            a = ll.attention_train(lp["attn"], h, cfg, positions, sharder,
                                   kv=(k, v, positions), chunk=chunk)
            x = x + a
            h2 = ll.apply_norm(lp["norm2"], x, cfg)
            x = x + ll.apply_mlp(lp["ffn"], h2, cfg, sharder)
            return sharder.ac(x, ("batch", "seq", None))
        return jax.checkpoint(fn)(x, lp), 0.0

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return ll.apply_norm(params["enc_norm"], x, cfg)


# ---------------------------------------------------------------------------
# decoder
# ---------------------------------------------------------------------------

def _cross_kv(lp, enc_out, cfg):
    k = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, lp["cross_attn"]["wv"])
    return k, v


def _decode_cross(lp, h, cfg, cross_cache, sharder):
    """Cross-attention read during decode (cache: (b, h, enc_seq, hd))."""
    b = h.shape[0]
    hd = cfg.head_dim
    q = jnp.einsum("bsd,dhk->bshk", h, lp["cross_attn"]["wq"])
    kvh = cfg.n_kv_heads
    g = cfg.n_heads // kvh
    qg = q.reshape(b, kvh, g, hd)
    sc = jnp.einsum("bkgd,bksd->bkgs", qg, cross_cache["k"])
    sc = (sc / jnp.sqrt(jnp.asarray(hd, jnp.float32))).astype(jnp.float32)
    probs = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(h.dtype),
                     cross_cache["v"])
    out = out.reshape(b, 1, cfg.n_heads, hd)
    return jnp.einsum("bshk,hkd->bsd", out, lp["cross_attn"]["wo"])


def dec_forward(params: Dict, x, enc_out, cfg, sharder: Sharder, positions,
                mode: str, cache: Any = None, cur_len=None,
                chunk: int = 2048, seq_capacity: int = 0):
    """Decoder stack.  cache per layer:
    {"self": {k,v}, "cross": {k,v (b, kvh, enc_seq, hd)}}."""
    seq_capacity = seq_capacity or x.shape[1]

    def layer(x, lp, lc):
        h = ll.apply_norm(lp["norm1"], x, cfg)
        a, new_self = ll.attention(
            lp["self_attn"], h, cfg, mode, sharder, positions=positions,
            cache=None if lc is None else lc["self"], cur_len=cur_len,
            chunk=chunk, seq_capacity=seq_capacity)
        x = x + a
        hx = ll.apply_norm(lp["norm_x"], x, cfg)
        if mode == "decode":
            c = _decode_cross(lp, hx, cfg, lc["cross"], sharder)
            new_cross = lc["cross"]
        else:
            ck, cv = _cross_kv(lp, enc_out, cfg)
            enc_pos = jnp.broadcast_to(
                jnp.arange(enc_out.shape[1]), enc_out.shape[:2])
            c = ll.attention_train(
                lp["cross_attn"], hx, cfg, positions, sharder,
                kv=(ll._repeat_kv(ck, cfg.n_heads),
                    ll._repeat_kv(cv, cfg.n_heads), enc_pos),
                chunk=chunk)
            new_cross = {"k": ck.transpose(0, 2, 1, 3),
                         "v": cv.transpose(0, 2, 1, 3)}
        x = x + c
        h2 = ll.apply_norm(lp["norm2"], x, cfg)
        x = x + ll.apply_mlp(lp["ffn"], h2, cfg, sharder)
        x = sharder.ac(x, ("batch", "seq", None))
        new_cache = None
        if mode != "train":
            new_cache = {"self": new_self, "cross": new_cross}
        return x, new_cache, None

    x, cache, _ = scan_layers(layer, params["dec_layers"], x, mode, cache)
    return x, cache


def encdec_apply(params: Dict, batch: Dict, cfg,
                 sharder: Sharder = IDENTITY_SHARDER, mode: str = "train",
                 cache: Any = None, cur_len=None, chunk: int = 2048,
                 seq_capacity: int = 0
                 ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Returns (logits, cache, aux), computing in the dtype of ``params``.
    batch: tokens + enc_embeds (stub)."""
    tokens = batch["tokens"]
    b = tokens.shape[0]
    if mode == "decode":
        enc_out = None
        positions = None
        embed_pos = jnp.broadcast_to(
            jnp.reshape(jnp.asarray(cur_len, jnp.int32), (-1, 1)), (b, 1))
    else:
        enc_out = encode(params,
                         batch["enc_embeds"].astype(params["enc_pos"].dtype),
                         cfg, sharder, chunk=chunk)
        s = tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        embed_pos = positions
    x = ll.embed_tokens(params["embed"], tokens, cfg, positions=embed_pos)
    x = sharder.ac(x, ("batch", "seq", None))
    x, new_cache = dec_forward(params, x, enc_out, cfg, sharder, positions,
                               mode, cache=cache, cur_len=cur_len,
                               chunk=chunk, seq_capacity=seq_capacity)
    if mode != "train":
        x = x[:, -1:]
    x = ll.apply_norm(params["final_norm"], x, cfg)
    logits = ll.unembed(params["embed"], x, cfg, sharder)
    return logits, new_cache, jnp.zeros((), jnp.float32)


def encdec_cache_spec(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16):
    S = ll.kv_capacity(cfg, seq_len)
    self_shp = (cfg.n_layers, batch, cfg.n_kv_heads, S, cfg.head_dim)
    cross_shp = (cfg.n_layers, batch, cfg.n_kv_heads, cfg.enc_seq,
                 cfg.head_dim)
    return {
        "self": {"k": jax.ShapeDtypeStruct(self_shp, dtype),
                 "v": jax.ShapeDtypeStruct(self_shp, dtype)},
        "cross": {"k": jax.ShapeDtypeStruct(cross_shp, dtype),
                  "v": jax.ShapeDtypeStruct(cross_shp, dtype)},
    }
