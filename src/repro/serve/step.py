"""Jitted serving steps: prefill (prompt -> cache) and decode (one token
against a donated cache)."""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models.api import Model
from repro.models.common import IDENTITY_SHARDER, Sharder


def build_prefill_step(model: Model, sharder: Sharder = IDENTITY_SHARDER,
                       chunk: int = 2048, seq_capacity: int = 0) -> Callable:
    def prefill_step(params, batch):
        logits, cache = model.prefill(params, batch, sharder=sharder,
                                      chunk=chunk, seq_capacity=seq_capacity)
        return logits, cache
    return prefill_step


def build_decode_step(model: Model, sharder: Sharder = IDENTITY_SHARDER
                      ) -> Callable:
    """decode_step(params, batch) with batch = {tokens, cache, cur_len}.

    Returns (greedy next_tokens (b, 1), logits, new_cache).  The cache is
    functionally updated; jit callers should donate it.
    """
    def decode_step(params, batch):
        logits, cache = model.decode(
            params, {"tokens": batch["tokens"]}, batch["cache"],
            batch["cur_len"], sharder=sharder)
        nxt = jnp.argmax(logits[:, -1].astype(jnp.float32),
                         axis=-1).astype(jnp.int32)[:, None]
        return nxt, logits, cache
    return decode_step
