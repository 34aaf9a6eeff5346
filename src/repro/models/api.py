"""Unified model API: every assigned architecture behind one interface.

``build_model(cfg)`` dispatches on family and returns a ``Model`` whose
methods are pure functions suitable for jit/pjit:

    init(key)                          -> params (f32 master)
    param_specs()                      -> (ShapeDtypeStruct tree, logical-axes tree)
    train_logits(params, batch, ...)   -> (logits, aux_loss)
    prefill(params, batch, ...)        -> (last_logits, cache)
    decode(params, batch, cache, cur_len, ...) -> (logits, cache)
    cache_spec(batch, seq_len)         -> ShapeDtypeStruct tree
    input_specs(shape, kind)           -> batch ShapeDtypeStruct dict

This is the gem5 'modular port interface' idea applied to models: any
architecture plugs into the same train/serve/dry-run drivers.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import encdec as ed
from repro.models import transformer as tf
from repro.models.common import IDENTITY_SHARDER, Sharder, unzip


@dataclass
class Model:
    cfg: ArchConfig

    # ------------------------------------------------------------------
    def _init_fn(self) -> Callable:
        if self.cfg.family == "audio":
            return ed.init_encdec
        return tf.init_lm

    def init(self, key) -> Any:
        vals, _ = unzip(self._init_fn()(key, self.cfg))
        return vals

    def param_specs(self) -> Tuple[Any, Any]:
        box: Dict[str, Any] = {}

        def f(key):
            t = self._init_fn()(key, self.cfg)
            vals, axes = unzip(t)
            box["axes"] = axes
            return vals

        shapes = jax.eval_shape(f, jax.random.PRNGKey(0))
        return shapes, box["axes"]

    # ------------------------------------------------------------------
    def train_logits(self, params, batch, sharder: Sharder = IDENTITY_SHARDER,
                     chunk: int = 2048) -> Tuple[jnp.ndarray, jnp.ndarray]:
        if self.cfg.family == "audio":
            logits, _, aux = ed.encdec_apply(params, batch, self.cfg, sharder,
                                             mode="train", chunk=chunk)
        else:
            logits, _, aux = tf.lm_apply(params, batch, self.cfg, sharder,
                                         mode="train", chunk=chunk)
        return logits, aux

    def prefill(self, params, batch, sharder: Sharder = IDENTITY_SHARDER,
                chunk: int = 2048, seq_capacity: int = 0):
        if self.cfg.family == "audio":
            logits, cache, _ = ed.encdec_apply(
                params, batch, self.cfg, sharder, mode="prefill", chunk=chunk,
                seq_capacity=seq_capacity)
        else:
            logits, cache, _ = tf.lm_apply(
                params, batch, self.cfg, sharder, mode="prefill", chunk=chunk,
                seq_capacity=seq_capacity)
        return logits, cache

    def decode(self, params, batch, cache, cur_len,
               sharder: Sharder = IDENTITY_SHARDER):
        if self.cfg.family == "audio":
            logits, cache, _ = ed.encdec_apply(
                params, batch, self.cfg, sharder, mode="decode", cache=cache,
                cur_len=cur_len)
        else:
            logits, cache, _ = tf.lm_apply(
                params, batch, self.cfg, sharder, mode="decode", cache=cache,
                cur_len=cur_len)
        return logits, cache

    # ------------------------------------------------------------------
    def cache_spec(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        if self.cfg.family == "audio":
            return ed.encdec_cache_spec(self.cfg, batch, seq_len, dtype)
        return tf.cache_spec(self.cfg, batch, seq_len, dtype)

    def init_cache(self, batch: int, seq_len: int, dtype=jnp.bfloat16):
        return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            self.cache_spec(batch, seq_len, dtype))

    # ------------------------------------------------------------------
    def input_specs(self, shape: ShapeConfig, kind: Optional[str] = None
                    ) -> Dict[str, Any]:
        """Batch ShapeDtypeStructs for one assigned (arch x shape) cell.

        kind defaults to shape.kind.  Vision/audio frontends are stubs:
        precomputed embeddings appear as inputs (assignment spec).
        """
        cfg = self.cfg
        kind = kind or shape.kind
        B, S = shape.global_batch, shape.seq_len
        i32 = jnp.int32
        bf16 = jnp.bfloat16
        sds = jax.ShapeDtypeStruct

        def extras() -> Dict[str, Any]:
            out: Dict[str, Any] = {}
            if cfg.family == "vlm" and kind != "decode":
                out["vision_embeds"] = sds((B, cfg.n_vis, cfg.d_model), bf16)
            if cfg.family == "audio" and kind != "decode":
                out["enc_embeds"] = sds((B, cfg.enc_seq, cfg.d_model), bf16)
            return out

        if kind == "train":
            s_text = S - (cfg.n_vis if cfg.family == "vlm" else 0)
            return {
                "tokens": sds((B, s_text), i32),
                "labels": sds((B, S), i32),
                "mask": sds((B, S), jnp.float32),
                **extras(),
            }
        if kind == "prefill":
            s_text = S - (cfg.n_vis if cfg.family == "vlm" else 0)
            return {"tokens": sds((B, s_text), i32), **extras()}
        # decode: one new token against a seq_len-capacity cache
        return {
            "tokens": sds((B, 1), i32),
            "cache": self.cache_spec(B, S),
            "cur_len": sds((), i32),
        }


def cache_bytes_by_kind(cache: Any) -> Dict[str, int]:
    """Bytes of a cache tree (arrays or ``ShapeDtypeStruct``s) by leaf
    kind: attention keys and values (leaves named ``k``, ``v``) and
    recurrent state (everything else: conv, SSM, WKV, token-shift rows)."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    out = {"kv": 0, "state": 0}
    for path, s in flat:
        last = getattr(path[-1], "key", None) if path else None
        out["kv" if last in ("k", "v") else "state"] += \
            math.prod(s.shape) * s.dtype.itemsize
    return out


def build_model(cfg: ArchConfig) -> Model:
    return Model(cfg)
