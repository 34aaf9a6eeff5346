"""Layer library: norms, rotary (RoPE / M-RoPE), GQA attention
(blockwise-online-softmax train path + KV-cache decode path), MLPs,
embeddings and the cross-entropy loss.

Everything is a pure function over parameter pytrees created with
``repro.models.common.param`` (which carries logical sharding axes).
Attention over long sequences uses a pure-jnp blockwise online-softmax
(the oracle for ``repro.kernels.flash_attention``); the naive path is
kept for short sequences and as a test reference.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.common import Sharder, IDENTITY_SHARDER, param, split_key

NEG_INF = -1e9


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(key, cfg, d: int) -> Dict:
    p = {"scale": param(key, (d,), (None,), init="ones")}
    if cfg.norm == "layernorm":
        p["bias"] = param(key, (d,), (None,), init="zeros")
    return p


def apply_norm(p: Dict, x, cfg):
    xf = x.astype(jnp.float32)
    if cfg.norm == "rmsnorm":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + cfg.norm_eps) * p["scale"]
    else:
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + cfg.norm_eps)
        out = out * p["scale"] + p["bias"]
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE, partial RoPE, M-RoPE)
# ---------------------------------------------------------------------------

def _rot_dims(cfg) -> int:
    rot = int(cfg.head_dim * cfg.rope_pct)
    return rot - rot % 2


def _inv_freq(rot: int, theta: float):
    return theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)


def rope_angles(cfg, positions):
    """positions (..., ) or (..., 3) for mrope -> angles (..., rot//2)."""
    rot = _rot_dims(cfg)
    inv = _inv_freq(rot, cfg.rope_theta)          # (rot//2,)
    if cfg.pos_scheme == "mrope":
        # split the frequency dims into t/h/w sections (2:3:3, Qwen2-VL)
        nf = rot // 2
        s1 = nf // 4
        s2 = (nf - s1) // 2
        sections = (s1, s2, nf - s1 - s2)
        pos = positions.astype(jnp.float32)       # (..., 3)
        parts = []
        start = 0
        for i, sec in enumerate(sections):
            parts.append(pos[..., i:i + 1] * inv[start:start + sec])
            start += sec
        return jnp.concatenate(parts, axis=-1)    # (..., nf)
    pos = positions.astype(jnp.float32)
    return pos[..., None] * inv


def apply_rope(cfg, x, positions):
    """x: (b, s, h, hd); positions: (b, s) or (b, s, 3)."""
    if cfg.pos_scheme in ("learned", "none"):
        return x
    rot = _rot_dims(cfg)
    if rot == 0:
        return x
    ang = rope_angles(cfg, positions)             # (b, s, rot//2)
    cos = jnp.cos(ang)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, :, None, :].astype(x.dtype)
    xr, xp = x[..., :rot], x[..., rot:]
    x1, x2 = xr[..., : rot // 2], xr[..., rot // 2:]
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    if xp.shape[-1]:
        out = jnp.concatenate([out, xp], axis=-1)
    return out


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def init_attention(key, cfg) -> Dict:
    d, hd = cfg.d_model, cfg.head_dim
    ks = split_key(key, 6)
    p = {
        "wq": param(ks[0], (d, cfg.n_heads, hd), ("embed", "heads", None)),
        "wk": param(ks[1], (d, cfg.n_kv_heads, hd),
                    ("embed", "kv_heads", None)),
        "wv": param(ks[2], (d, cfg.n_kv_heads, hd),
                    ("embed", "kv_heads", None)),
        "wo": param(ks[3], (cfg.n_heads, hd, d), ("heads", None, "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = param(ks[4], (hd,), (None,), init="ones")
        p["k_norm"] = param(ks[5], (hd,), (None,), init="ones")
    return p


def _qk_norm(x, scale, eps):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return (xf * jax.lax.rsqrt(var + eps) * scale).astype(x.dtype)


def _repeat_kv(k, n_heads: int):
    """(b, s, kvh, hd) -> (b, s, h, hd) by repeating each kv head."""
    b, s, kvh, hd = k.shape
    if kvh == n_heads:
        return k
    rep = n_heads // kvh
    k = jnp.broadcast_to(k[:, :, :, None, :], (b, s, kvh, rep, hd))
    return k.reshape(b, s, n_heads, hd)


def qkv_project(p: Dict, x, cfg, positions, sharder: Sharder):
    """Returns q (b,s,h,hd), k/v (b,s,h,hd) (kv repeated), post-RoPE."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
    q = apply_rope(cfg, q, positions)
    k = apply_rope(cfg, k, positions)
    k = _repeat_kv(k, cfg.n_heads)
    v = _repeat_kv(v, cfg.n_heads)
    q = sharder.ac(q, ("batch", None, "heads", None))
    k = sharder.ac(k, ("batch", None, "heads", None))
    v = sharder.ac(v, ("batch", None, "heads", None))
    return q, k, v


def scale_scores(scores, hd: int, multiplier: float = 0.0):
    """Attention scores times ``multiplier``, or over sqrt(hd) where it
    is 0 (``ArchConfig.attn_multiplier``)."""
    if multiplier:
        return scores * multiplier
    return scores / math.sqrt(hd)


def naive_causal_attention(q, k, v, q_pos, kv_pos, window: int = 0,
                           cross: bool = False, multiplier: float = 0.0):
    """Reference attention.  q/k/v: (b, s, h, hd); positions (b, s)."""
    hd = q.shape[-1]
    scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32)
    scores = scale_scores(scores, hd, multiplier)
    if not cross:
        mask = kv_pos[:, None, None, :] <= q_pos[:, None, :, None]
        if window:
            mask &= kv_pos[:, None, None, :] > (
                q_pos[:, None, :, None] - window)
        scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqs,bshk->bqhk", probs.astype(q.dtype), v)
    return out


def blockwise_attention(q, k, v, q_pos, kv_pos, window: int = 0,
                        chunk: int = 1024, cross: bool = False,
                        multiplier: float = 0.0):
    """Online-softmax attention, scanning KV chunks (flash-style).

    Pure jnp (runs everywhere); the oracle for the Pallas kernel.
    q: (b, sq, h, hd); k/v: (b, skv, h, hd).
    """
    b, sq, h, hd = q.shape
    skv = k.shape[1]
    if skv % chunk:
        chunk = skv          # fall back to single chunk
    n_chunks = skv // chunk
    scale = multiplier or 1.0 / math.sqrt(hd)
    qf = q * jnp.asarray(scale, q.dtype)

    kc = k.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, hd).transpose(1, 0, 2, 3, 4)
    pc = kv_pos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(carry, xs):
        acc, m, l = carry                       # (b,h,sq,hd) f32, (b,h,sq)
        kci, vci, pci = xs
        s = jnp.einsum("bqhk,bshk->bhqs", qf, kci).astype(jnp.float32)
        if not cross:
            mask = pci[:, None, None, :] <= q_pos[:, None, :, None]
            if window:
                mask &= pci[:, None, None, :] > (
                    q_pos[:, None, :, None] - window)
            s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqs,bshk->bhqk", p.astype(q.dtype), vci).astype(jnp.float32)
        return (acc, m_new, l_new), None

    acc0 = jnp.zeros((b, h, sq, hd), jnp.float32)
    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    # checkpoint the chunk body: the backward pass recomputes the f32
    # score/probability tensors per chunk instead of saving them across
    # the whole KV axis (flash-attention-backward memory behavior;
    # saving them costs ~4 GB/layer at deepseek train_4k scale).
    (acc, m, l), _ = jax.lax.scan(jax.checkpoint(body), (acc0, m0, l0),
                                  (kc, vc, pc))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)   # (b, sq, h, hd)


def attention_train(p: Dict, x, cfg, positions, sharder: Sharder,
                    kv: Optional[Tuple] = None, chunk: int = 2048,
                    return_kv: bool = False):
    """Full training/prefill attention with output projection.

    ``kv``: optional externally-computed (k, v, kv_pos) for
    cross-attention (whisper decoder); positions then only drive q RoPE.
    ``return_kv``: also return the pre-repeat (b, kvh, s, hd) cache
    tensors (prefill).
    """
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
    q = apply_rope(cfg, q, positions)
    kv_raw = None
    if kv is None:
        k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
        if cfg.qk_norm:
            k = _qk_norm(k, p["k_norm"], cfg.norm_eps)
        k = apply_rope(cfg, k, positions)
        if return_kv:
            kv_raw = (k, v)
        # reshard BEFORE the GQA head-repeat: when n_kv_heads doesn't
        # divide the model axis the repeat's broadcast would otherwise
        # trigger XLA's "involuntary full rematerialization" fallback
        # (replicate + re-partition); an explicit constraint makes the
        # all-gather deliberate and schedulable.
        k = sharder.ac(k, ("batch", None, "kv_heads", None))
        v = sharder.ac(v, ("batch", None, "kv_heads", None))
        k = _repeat_kv(k, cfg.n_heads)
        v = _repeat_kv(v, cfg.n_heads)
        kv_pos = positions if positions.ndim == 2 else positions[..., 0]
        cross = False
    else:
        k, v, kv_pos = kv
        cross = True
    q = sharder.ac(q, ("batch", "q_seq", "heads", None))
    k = sharder.ac(k, ("batch", None, "heads", None))
    v = sharder.ac(v, ("batch", None, "heads", None))
    q_pos = positions if positions.ndim == 2 else positions[..., 0]
    if k.shape[1] > chunk:
        out = blockwise_attention(q, k, v, q_pos, kv_pos,
                                  window=cfg.sliding_window, chunk=chunk,
                                  cross=cross,
                                  multiplier=cfg.attn_multiplier)
    else:
        out = naive_causal_attention(q, k, v, q_pos, kv_pos,
                                     window=cfg.sliding_window, cross=cross,
                                     multiplier=cfg.attn_multiplier)
    # sequence-parallel out-projection (see mamba.apply_mamba): reshard
    # (seq <- model, heads full) before contracting over the sharded
    # head axis, replacing a full-sequence f32 partial-sum + all-reduce
    # with a bf16 all-to-all.
    out = sharder.ac(out, ("batch", "seq", None, None))
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if return_kv:
        return y, kv_raw
    return y


def kv_to_cache(k, v, capacity: int, sharder: Sharder):
    """Prefill KV (b, s, kvh, hd) -> ring-buffer cache (b, kvh, S, hd).

    When s > capacity (sliding window), keeps the last ``capacity``
    entries rolled so that token t occupies slot t % capacity —
    consistent with ``attention_decode``'s ring-buffer writes.
    """
    s = k.shape[1]
    if s > capacity:
        k, v = k[:, -capacity:], v[:, -capacity:]
        shift = s % capacity
        if shift:
            k = jnp.roll(k, shift, axis=1)
            v = jnp.roll(v, shift, axis=1)
    elif s < capacity:
        pad = capacity - s
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    ck = k.transpose(0, 2, 1, 3)
    cv = v.transpose(0, 2, 1, 3)
    ck = sharder.ac(ck, ("batch", "kv_heads_c", "kv_seq", None))
    cv = sharder.ac(cv, ("batch", "kv_heads_c", "kv_seq", None))
    return {"k": ck, "v": cv}


def attention_decode(p: Dict, x, cfg, cache: Dict, cur_len,
                     sharder: Sharder):
    """Single-token decode with a (possibly ring-buffered) KV cache.

    x: (b, 1, d).  cache: {"k": (b, kvh, S, hd), "v": ...}.  cur_len:
    scalar int32 (uniform batch) OR (b,) int32 (continuous batching:
    per-slot lengths).  Returns (out (b,1,d), new_cache).  The cache seq
    axis carries logical axis "kv_seq" (sharded over the model axis per
    the uniform KV rule).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    S = cache["k"].shape[2]
    cur_len = jnp.asarray(cur_len, jnp.int32)
    per_slot = cur_len.ndim == 1
    if per_slot:
        pos_now = cur_len[:, None]
    else:
        pos_now = jnp.full((b, 1), cur_len, jnp.int32)
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k_new = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v_new = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = _qk_norm(q, p["q_norm"], cfg.norm_eps)
        k_new = _qk_norm(k_new, p["k_norm"], cfg.norm_eps)
    q = apply_rope(cfg, q, pos_now if cfg.pos_scheme != "mrope"
                   else jnp.broadcast_to(pos_now[..., None], (b, 1, 3)))
    k_new = apply_rope(cfg, k_new, pos_now if cfg.pos_scheme != "mrope"
                       else jnp.broadcast_to(pos_now[..., None], (b, 1, 3)))
    slot = jnp.mod(cur_len, S)                 # ring buffer (sliding window)
    knc = k_new.transpose(0, 2, 1, 3)          # (b, kvh, 1, hd)
    vnc = v_new.transpose(0, 2, 1, 3)
    if per_slot:
        hit = (jnp.arange(S)[None, :] == slot[:, None])   # (b, S)
        hit = hit[:, None, :, None]
        ck = jnp.where(hit, knc, cache["k"])
        cv = jnp.where(hit, vnc, cache["v"])
    else:
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], knc, slot, 2)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], vnc, slot, 2)
    ck = sharder.ac(ck, ("batch", "kv_heads_c", "kv_seq", None))
    cv = sharder.ac(cv, ("batch", "kv_heads_c", "kv_seq", None))

    kvh = cfg.n_kv_heads
    g = cfg.n_heads // kvh
    qg = q.reshape(b, kvh, g, hd)              # (b, kvh, g, hd)
    scores = jnp.einsum("bkgd,bksd->bkgs", qg, ck).astype(jnp.float32)
    scores = scale_scores(scores, hd, cfg.attn_multiplier)

    # slot validity: slot index < number of tokens written (incl. new one)
    n_valid = jnp.minimum(cur_len + 1, S)
    slot_ids = jnp.arange(S)
    if per_slot:
        valid = slot_ids[None, None, None, :] < n_valid[:, None, None, None]
    else:
        valid = slot_ids[None, None, None, :] < n_valid
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    probs = e / jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bkgs,bksd->bkgd", probs.astype(x.dtype), cv)
    out = out.reshape(b, 1, cfg.n_heads, hd)
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    return y, {"k": ck, "v": cv}


def kv_capacity(cfg, seq_len: int) -> int:
    """Slots of a KV cache for ``seq_len`` positions: a sliding window
    keeps only its last ``sliding_window``."""
    if cfg.sliding_window:
        return min(seq_len, cfg.sliding_window)
    return seq_len


def attention(p: Dict, x, cfg, mode: str, sharder: Sharder, *, positions,
              cache: Optional[Dict], cur_len, chunk: int, seq_capacity: int):
    """Causal self-attention in one of the three modes.  Returns
    (out, new_cache):

      train   -> over all positions; no cache
      prefill -> over all positions; the prompt's K/V as a cache of
                 ``kv_capacity(cfg, seq_capacity)`` slots
      decode  -> one token against ``cache`` at ``cur_len``; the cache
                 with that token written
    """
    if mode == "decode":
        return attention_decode(p, x, cfg, cache, cur_len, sharder)
    if mode == "prefill":
        y, (k, v) = attention_train(p, x, cfg, positions, sharder,
                                    chunk=chunk, return_kv=True)
        return y, kv_to_cache(k, v, kv_capacity(cfg, seq_capacity), sharder)
    return attention_train(p, x, cfg, positions, sharder, chunk=chunk), None


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def init_mlp(key, cfg, d_ff: Optional[int] = None) -> Dict:
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = split_key(key, 3)
    p = {
        "wi": param(ks[0], (d, f), ("embed", "mlp")),
        "wo": param(ks[1], (f, d), ("mlp", "embed")),
    }
    if cfg.act == "swiglu":
        p["wg"] = param(ks[2], (d, f), ("embed", "mlp"))
    return p


def apply_mlp(p: Dict, x, cfg, sharder: Sharder = IDENTITY_SHARDER):
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if cfg.act == "swiglu":
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = jax.nn.silu(h) * g
    elif cfg.act == "sq_relu":
        h = jnp.square(jax.nn.relu(h))
    else:  # gelu
        h = jax.nn.gelu(h)
    h = sharder.ac(h, ("batch", None, "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding / unembedding / loss
# ---------------------------------------------------------------------------

def padded_vocab(cfg) -> int:
    v = cfg.vocab_size
    return v if v % 128 == 0 else (v // 128 + 1) * 128


def init_embedding(key, cfg) -> Dict:
    vp = padded_vocab(cfg)
    ks = split_key(key, 2)
    p = {"table": param(ks[0], (vp, cfg.d_model), (None, "embed"),
                        scale=1.0)}
    if not cfg.tie_embeddings:
        p["head"] = param(ks[1], (cfg.d_model, vp), ("embed", "vocab"))
    if cfg.pos_scheme == "learned":
        p["pos_table"] = param(
            key, (8192 if cfg.enc_seq == 0 else max(8192, cfg.enc_seq),
                  cfg.d_model),
            (None, "embed"), scale=0.02)
    return p


def embed_tokens(p: Dict, tokens, cfg, positions=None):
    x = jnp.take(p["table"], tokens, axis=0)
    if cfg.embed_scale != 1.0:
        x = x * cfg.embed_scale
    if cfg.pos_scheme == "learned" and positions is not None:
        pos = positions if positions.ndim == 2 else positions[..., 0]
        x = x + jnp.take(p["pos_table"], pos, axis=0)
    return x


def unembed(p: Dict, x, cfg, sharder: Sharder = IDENTITY_SHARDER):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["table"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["head"])
    if cfg.logits_scaling != 1.0:
        logits = logits / cfg.logits_scaling
    return sharder.ac(logits, ("batch", None, "vocab"))


def cross_entropy(logits, labels, cfg, mask=None):
    """Mean next-token xent; handles vocab padding; logits (b, s, Vp)."""
    vp = logits.shape[-1]
    logits = logits.astype(jnp.float32)
    if vp != cfg.vocab_size:
        pad_mask = jnp.arange(vp) < cfg.vocab_size
        logits = jnp.where(pad_mask, logits, NEG_INF)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
