"""Decoder LM assembly for all families: dense / moe / vlm (standard
blocks), ssm (RWKV6 blocks), hybrid (a period of state-space layers and
one attention layer: Jamba's Mamba-1 with MoE, period 8; Granite-4.0-H's
Mamba-2, period 10; ``ArchConfig.ssm_kind`` picks the mixer).

Layers execute under ``scan_layers`` (one ``jax.lax.scan``, shared with
the encoder-decoder's decoder) with stacked parameters so the HLO size
is O(1) in depth (deepseek-67b = 95 layers compiles as one while loop).
Hybrid archs scan over *periods* (Jamba: 4 periods of 8 sublayers each,
attention at position 4, MoE on odd positions).

Three modes share the block code:
  train   -> logits over all positions (activation-rematerialized)
  prefill -> logits at the last position + KV/state cache
  decode  -> one-token step updating the cache
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import layers as ll
from repro.models import mamba as mm
from repro.models import mamba2 as m2
from repro.models import moe as me
from repro.models import rwkv as rw
from repro.models.common import (IDENTITY_SHARDER, Sharder, split_key,
                                 stack_inits)


# ---------------------------------------------------------------------------
# Per-layer init
# ---------------------------------------------------------------------------

def layer_kind(cfg, layer_idx: int) -> str:
    """The layer's mixer: ``attn``, ``mamba1``, ``mamba2`` or ``rwkv``."""
    if cfg.family == "ssm":
        return "rwkv"
    if cfg.family == "hybrid" and not cfg.is_attn_layer(layer_idx):
        return cfg.ssm_kind
    return "attn"


def init_layer(key, cfg, layer_idx: int) -> Dict:
    """One decoder layer (norms + mixer + ffn) as a marker tree."""
    kind = layer_kind(cfg, layer_idx)
    ks = split_key(key, 4)
    if kind == "rwkv":
        blk = rw.init_rwkv_block(ks[0], cfg)
        return {
            "norm1": ll.init_norm(ks[1], cfg, cfg.d_model),
            "mixer": blk["time_mix"],
            "norm2": ll.init_norm(ks[2], cfg, cfg.d_model),
            "ffn": blk["channel_mix"],
        }
    init_mixer = {"attn": ll.init_attention, "mamba1": mm.init_mamba_block,
                  "mamba2": m2.init_mamba2_block}[kind]
    ffn = (me.init_moe(ks[3], cfg) if cfg.is_moe_layer(layer_idx)
           else ll.init_mlp(ks[3], cfg))
    return {
        "norm1": ll.init_norm(ks[1], cfg, cfg.d_model),
        "mixer": init_mixer(ks[0], cfg),
        "norm2": ll.init_norm(ks[2], cfg, cfg.d_model),
        "ffn": ffn,
    }


def init_decoder_layers(key, cfg) -> Any:
    """Stacked layer params: period-1 archs -> one stacked tree;
    hybrid -> tuple of per-position stacked trees (stacked over periods).
    """
    if cfg.family == "hybrid":
        period = cfg.attn_every
        n_periods = cfg.n_layers // period
        assert n_periods * period == cfg.n_layers
        out = []
        for pos in range(period):
            k = jax.random.fold_in(key, pos)
            out.append(stack_inits(
                lambda kk, _pos=pos: init_layer(kk, cfg, _pos), k, n_periods))
        return tuple(out)
    return stack_inits(lambda kk: init_layer(kk, cfg, 0), key, cfg.n_layers)


# ---------------------------------------------------------------------------
# Cache construction (zeros / specs)
# ---------------------------------------------------------------------------

def layer_cache_shape(cfg, layer_idx: int, batch: int, seq_len: int,
                      dtype=jnp.bfloat16) -> Dict:
    kind = layer_kind(cfg, layer_idx)
    if kind == "attn":
        S = ll.kv_capacity(cfg, seq_len)
        shp = (batch, cfg.n_kv_heads, S, cfg.head_dim)
        return {"k": jax.ShapeDtypeStruct(shp, dtype),
                "v": jax.ShapeDtypeStruct(shp, dtype)}
    if kind == "rwkv":
        h = cfg.n_rwkv_heads
        n = cfg.rwkv_head_size
        return {
            "shift_tm": jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dtype),
            "shift_cm": jax.ShapeDtypeStruct((batch, 1, cfg.d_model), dtype),
            "wkv": jax.ShapeDtypeStruct((batch, h, n, n), jnp.float32),
        }
    if kind == "mamba2":
        conv = (batch, cfg.d_conv - 1, m2.conv_dim(cfg))
        ssm = (batch, cfg.ssm_heads, cfg.ssm_head_dim, cfg.d_state)
    else:
        conv = (batch, cfg.d_conv - 1, cfg.d_inner)
        ssm = (batch, cfg.d_inner, cfg.d_state)
    return {"conv": jax.ShapeDtypeStruct(conv, dtype),
            "ssm": jax.ShapeDtypeStruct(ssm, jnp.float32)}


def _stack_specs(specs):
    """List of identical-structure ShapeDtypeStruct trees -> stacked."""
    n = len(specs)
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), specs[0])


def cache_spec(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct pytree of the full decode cache."""
    if cfg.family == "hybrid":
        period = cfg.attn_every
        n_periods = cfg.n_layers // period
        return tuple(
            _stack_specs([layer_cache_shape(cfg, pos, batch, seq_len, dtype)
                          for _ in range(n_periods)])
            for pos in range(period))
    return _stack_specs([layer_cache_shape(cfg, 0, batch, seq_len, dtype)
                         for _ in range(cfg.n_layers)])


def init_cache(cfg, batch: int, seq_len: int, dtype=jnp.bfloat16) -> Any:
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_spec(cfg, batch, seq_len, dtype))


# ---------------------------------------------------------------------------
# Per-layer apply
# ---------------------------------------------------------------------------

def apply_layer(p: Dict, x, cfg, layer_idx: int, sharder: Sharder,
                positions, mode: str, cache: Optional[Dict], cur_len,
                chunk: int, seq_capacity: int) -> Tuple:
    """Returns (x, new_cache_entry, aux_loss)."""
    kind = layer_kind(cfg, layer_idx)
    rs = cfg.residual_scale
    aux = jnp.zeros((), jnp.float32)
    h = ll.apply_norm(p["norm1"], x, cfg)
    st = cache or {}

    if kind == "rwkv":
        mix, new_shift_tm, new_wkv = rw.apply_time_mix(
            p["mixer"], h, cfg, sharder,
            shift_state=st.get("shift_tm"), wkv_state=st.get("wkv"))
        x = x + rs * mix
        x = sharder.ac(x, ("batch", "seq", None))
        h2 = ll.apply_norm(p["norm2"], x, cfg)
        f, new_shift_cm = rw.apply_channel_mix(
            p["ffn"], h2, cfg, shift_state=st.get("shift_cm"))
        x = x + rs * f
        x = sharder.ac(x, ("batch", "seq", None))
        new_cache = None
        if mode != "train":
            new_cache = {"shift_tm": new_shift_tm, "shift_cm": new_shift_cm,
                         "wkv": new_wkv}
        return x, new_cache, aux

    if kind == "attn":
        mix, new_cache = ll.attention(
            p["mixer"], h, cfg, mode, sharder, positions=positions,
            cache=cache, cur_len=cur_len, chunk=chunk,
            seq_capacity=seq_capacity)
    else:
        if kind == "mamba2":
            mix, new_conv, new_ssm = m2.apply_mamba2(
                p["mixer"], h, cfg, sharder,
                conv_state=st.get("conv"), ssm_state=st.get("ssm"))
        else:
            mix, new_conv, new_ssm = mm.apply_mamba(
                p["mixer"], h, cfg, sharder,
                conv_state=st.get("conv"), ssm_state=st.get("ssm"),
                remat=(mode == "train"))
        new_cache = None
        if mode != "train":
            new_cache = {"conv": new_conv, "ssm": new_ssm}

    x = x + rs * mix
    x = sharder.ac(x, ("batch", "seq", None))
    h2 = ll.apply_norm(p["norm2"], x, cfg)
    if cfg.is_moe_layer(layer_idx):
        f, aux = me.apply_moe(p["ffn"], h2, cfg, sharder)
    else:
        f = ll.apply_mlp(p["ffn"], h2, cfg, sharder)
    x = x + rs * f
    x = sharder.ac(x, ("batch", "seq", None))
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# Layer stack (one scan over layers / periods, for every family)
# ---------------------------------------------------------------------------

def scan_layers(layers, params: Any, x, mode: str, cache: Any = None,
                aux=None) -> Tuple[jnp.ndarray, Any, Any]:
    """Run a stack of layers over ``x`` under one ``lax.scan``.  Returns
    (x, new_cache, aux).

    ``layers`` is one layer function ``fn(x, layer_params, layer_cache)
    -> (x, new_layer_cache, layer_aux)``, with ``params`` and ``cache``
    its stacked trees (leading axis: the layers).  A hybrid period is a
    tuple of such functions, one per position, each with its own stacked
    tree in the tuples ``params`` and ``cache`` (leading axis: the
    periods); each scan step runs the whole period.  ``aux``, where not
    None, is a float32 scalar the layers' ``layer_aux`` are added to.

      train   -> each layer under ``jax.checkpoint``; no cache (None)
      prefill -> each layer's cache emitted as the scan's ``ys``
      decode  -> ``cache`` carried whole through the scan; each step
                 reads its layer's slice with ``dynamic_index_in_dim``
                 and writes it back with ``dynamic_update_index_in_dim``

    Decode carries the cache because XLA aliases while-loop carries, so
    the multi-GB cache exists ONCE.  Passing it as scan xs/ys instead
    double-buffers it (measured +12.8 GB/device at deepseek decode_32k
    scale).
    """
    single = callable(layers)
    if single:
        layers, params, cache = (layers,), (params,), (cache,)

    def apply(fn, x, aux, lp, lc):
        if mode == "train":
            fn = jax.checkpoint(fn)
        x, nc, a = fn(x, lp, lc)
        return x, nc, (aux if aux is None else aux + a)

    if mode == "decode":
        def body(carry, lp):
            x, aux, cache, li = carry
            new = []
            for fn, p, c in zip(layers, lp, cache):
                lc = jax.tree.map(lambda t: jax.lax.dynamic_index_in_dim(
                    t, li, 0, keepdims=False), c)
                x, nc, aux = apply(fn, x, aux, p, lc)
                new.append(jax.tree.map(
                    lambda t, n: jax.lax.dynamic_update_index_in_dim(
                        t, n.astype(t.dtype), li, 0), c, nc))
            return (x, aux, tuple(new), li + 1), None

        (x, aux, cache, _), _ = jax.lax.scan(body, (x, aux, cache, 0),
                                             params)
    else:
        def body(carry, lp):
            x, aux = carry
            new = []
            for fn, p in zip(layers, lp):
                x, nc, aux = apply(fn, x, aux, p, None)
                new.append(nc)
            return (x, aux), tuple(new)

        (x, aux), cache = jax.lax.scan(body, (x, aux), params)
        if mode == "train":
            cache = None
    if single and cache is not None:
        cache = cache[0]
    return x, cache, aux


def decoder_forward(layers_params: Any, x, cfg, sharder: Sharder, positions,
                    mode: str = "train", cache: Any = None, cur_len=None,
                    chunk: int = 2048, seq_capacity: int = 0
                    ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Run the full decoder stack.  Returns (x, new_cache, aux_loss)."""
    seq_capacity = seq_capacity or x.shape[1]

    def layer(pos):
        def fn(x, p, c):
            return apply_layer(p, x, cfg, pos, sharder, positions, mode,
                               c, cur_len, chunk, seq_capacity)
        return fn

    layers = (tuple(layer(pos) for pos in range(cfg.attn_every))
              if cfg.family == "hybrid" else layer(0))
    return scan_layers(layers, layers_params, x, mode, cache,
                       aux=jnp.zeros((), jnp.float32))


# ---------------------------------------------------------------------------
# Full LM
# ---------------------------------------------------------------------------

def init_lm(key, cfg) -> Dict:
    ks = split_key(key, 3)
    return {
        "embed": ll.init_embedding(ks[0], cfg),
        "layers": init_decoder_layers(ks[1], cfg),
        "final_norm": ll.init_norm(ks[2], cfg, cfg.d_model),
    }


def make_positions(cfg, b: int, s: int, n_vis: int = 0, offset: int = 0):
    """Sequential positions; M-RoPE 3-D positions for the vlm family."""
    if cfg.pos_scheme != "mrope":
        return jnp.broadcast_to(jnp.arange(offset, offset + s), (b, s))
    # vision tokens: (t=0, h, w) over the patch grid; text tokens: all
    # three coordinates equal the sequence index (so a decode step at
    # cur_len uses position cur_len without knowing n_vis).
    grid = max(1, int(math.sqrt(max(n_vis, 1))))
    pos = []
    for i in range(3):
        vis = {
            0: jnp.zeros((n_vis,), jnp.int32),
            1: jnp.arange(n_vis) // grid,
            2: jnp.arange(n_vis) % grid,
        }[i]
        txt = jnp.arange(n_vis, s)
        pos.append(jnp.concatenate([vis, txt]) + offset)
    p3 = jnp.stack(pos, axis=-1)                      # (s, 3)
    return jnp.broadcast_to(p3, (b, s, 3))


def lm_apply(params: Dict, batch: Dict, cfg, sharder: Sharder = IDENTITY_SHARDER,
             mode: str = "train", cache: Any = None, cur_len=None,
             chunk: int = 2048, seq_capacity: int = 0
             ) -> Tuple[jnp.ndarray, Any, jnp.ndarray]:
    """Unified LM entry, computing in the dtype of ``params``.  Returns
    (logits, new_cache, aux_loss).

    train  : logits (b, s, Vp)
    prefill: logits (b, 1, Vp) at the last position, + cache
    decode : logits (b, 1, Vp), + updated cache
    """
    tokens = batch["tokens"]
    b = tokens.shape[0]
    embed_pos = batch.get("positions")
    if cfg.pos_scheme == "learned" and embed_pos is None:
        if mode == "decode":
            embed_pos = jnp.broadcast_to(
                jnp.reshape(jnp.asarray(cur_len, jnp.int32), (-1, 1)),
                (b, 1))
        else:
            embed_pos = jnp.broadcast_to(
                jnp.arange(tokens.shape[1]), tokens.shape)
    x = ll.embed_tokens(params["embed"], tokens, cfg, positions=embed_pos)
    n_vis = 0
    if "vision_embeds" in batch:
        vis = batch["vision_embeds"].astype(x.dtype)
        n_vis = vis.shape[1]
        x = jnp.concatenate([vis, x], axis=1)
    s = x.shape[1]
    if mode == "decode":
        positions = None                 # decode builds its own from cur_len
    else:
        positions = make_positions(cfg, b, s, n_vis=n_vis)
    x = sharder.ac(x, ("batch", "seq", None))
    x, new_cache, aux = decoder_forward(
        params["layers"], x, cfg, sharder, positions, mode=mode, cache=cache,
        cur_len=cur_len, chunk=chunk, seq_capacity=seq_capacity)
    if mode != "train":
        x = x[:, -1:]
    x = ll.apply_norm(params["final_norm"], x, cfg)
    logits = ll.unembed(params["embed"], x, cfg, sharder)
    return logits, new_cache, aux
