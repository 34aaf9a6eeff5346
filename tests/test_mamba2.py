"""The Mamba-2 mixer and the hybrid serving path, at smoke width on the CPU.

- The chunked SSD form (prefill and train) against the one-step
  recurrence (decode), in float32.
- The cache path (prefill, then decode steps carrying KV and recurrent
  state per slot) against the full forward pass, in float32, for both
  hybrids: Mamba-1 (Jamba) and Mamba-2 (Granite-4.0-H).
- ``BatchServer`` against the plain float32 reference of the benchmark
  (``chipbench/refs/hybrid_ssm.py``), on the benchmark's seeded weights.
- The muP multipliers of ``ArchConfig``.
"""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.models import build_model
from repro.models import mamba2 as m2
from repro.models import transformer as tf

pytestmark = pytest.mark.slow


def _inputs(b, s, nh, p, n, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    x = jax.random.normal(ks[0], (b, s, nh, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, nh)))
    A = -jnp.exp(jax.random.normal(ks[2], (nh,)))
    B = jax.random.normal(ks[3], (b, s, n))
    C = jax.random.normal(ks[4], (b, s, n))
    h0 = jax.random.normal(ks[5], (b, nh, p, n))
    return x, dt, A, B, C, h0


@pytest.mark.parametrize("s,chunk", [(32, 8), (37, 8), (300, 256)])
@pytest.mark.parametrize("with_h0", [False, True])
def test_chunked_ssd_equals_recurrence(s, chunk, with_h0):
    """Lengths that are and are not multiples of the chunk, from zeros
    and from a carried state: float32 round-off only."""
    x, dt, A, B, C, h0 = _inputs(2, s, 4, 8, 16, seed=s)
    h0 = h0 if with_h0 else None
    y, h_last = jax.jit(lambda *a: m2.ssd_chunked(*a, chunk, h0))(
        x, dt, A, B, C)

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        yt, h = m2.ssd_step(x_t, dt_t, A, b_t, c_t, h)
        return h, yt

    h_ref, ys = jax.lax.scan(
        step, jnp.zeros_like(h_last) if h0 is None else h0,
        tuple(t.swapaxes(0, 1) for t in (x, dt, B, C)))
    ys = ys.swapaxes(0, 1)
    assert float(jnp.max(jnp.abs(y - ys)) / jnp.max(jnp.abs(ys))) <= 1e-5
    assert float(jnp.max(jnp.abs(h_last - h_ref))
                 / jnp.max(jnp.abs(h_ref))) <= 1e-5


@pytest.mark.parametrize("arch", ["jamba-v0.1-52b", "granite-4.0-h-micro"])
def test_hybrid_cache_path_matches_forward_in_f32(arch):
    """Prefill, then one decode step per token with per-slot lengths,
    carrying KV and recurrent state through the period scan, gives the
    forward pass's logits to float32 round-off.  Jamba's experts get room
    for every token: at its capacity factor of 1.0 the forward pass over
    many tokens drops tokens that decode keeps (the gap there is the
    expert layer's, not the cache path's)."""
    cfg = smoke(get_config(arch))
    if cfg.n_experts:
        cfg = dataclasses.replace(cfg, capacity_factor=8.0)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S, n0 = 24, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, S), 0,
                              cfg.vocab_size)
    full, _, _ = jax.jit(lambda p, t: tf.lm_apply(
        p, {"tokens": t}, cfg, mode="train"))(params, toks)
    _, cache, _ = jax.jit(lambda p, t: tf.lm_apply(
        p, {"tokens": t}, cfg, mode="prefill", seq_capacity=S))(
        params, toks[:, :n0])
    dec = jax.jit(lambda p, t, c, n: tf.lm_apply(
        p, {"tokens": t}, cfg, mode="decode", cache=c, cur_len=n))
    full = np.asarray(full)
    worst = 0.0
    for i in range(n0, S):
        lg, cache, _ = dec(params, toks[:, i:i + 1], cache,
                           jnp.full((2,), i, jnp.int32))
        worst = max(worst, float(np.max(np.abs(np.asarray(lg)[:, 0]
                                               - full[:, i]))))
    assert worst <= 1e-5 * np.max(np.abs(full))


def test_server_matches_reference():
    """``BatchServer`` (B=1 prefill, slot insert of KV and state, batched
    decode) against the plain float32 reference on the benchmark's
    seeded weights.  The program computes in bfloat16 from float32
    weights, about 0.4% per operation; over these 10 layers its logits
    differ from the reference's by 2.7-4.7% of their RMS, and the served
    token's reference logit lies at most 4% of the largest logit below
    the best (6 seeds, 18 requests).  The reference in float8 differs by
    20-33%; a wrong state, slot, multiplier or decay by the logits' own
    size."""
    from chipbench import harness, weights
    from chipbench.refs import hybrid_ssm
    from repro.serve import BatchServer, Request
    cfg = smoke(get_config("granite-4.0-h-micro"))
    sizes = dataclasses.asdict(cfg)
    model = build_model(cfg)
    params = weights.make(model.param_specs()[0], harness.jax_key(3),
                          hybrid_ssm.leaf_init)
    srv = BatchServer(model=model, params=params, slots=3, seq_capacity=48)
    srv.instantiate()
    rng = np.random.default_rng(0)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, n,
                                               dtype=np.int32),
                    max_new_tokens=10) for i, n in enumerate((7, 16, 12, 9))]
    assert len(srv.serve(reqs)) == 4
    ref_fn = jax.jit(lambda p, t: hybrid_ssm.logits(p, t, sizes))
    for r in reqs[1:]:            # served in slots other than 0, or refilled
        toks = np.concatenate([r.prompt, np.asarray(r.output[:-1])])
        with jax.default_matmul_precision("highest"):
            ref = np.asarray(ref_fn(params, toks))[len(r.prompt) - 1:]
        got = srv.replay_logits(r)[:, :cfg.vocab_size]
        assert got.argmax(-1).tolist() == r.output
        assert np.sqrt(np.mean((got - ref) ** 2) / np.mean(ref ** 2)) < 0.1
        gaps = ref.max(-1) - ref[np.arange(len(r.output)), r.output]
        assert gaps.max() < 0.08 * np.max(np.abs(ref))


def _logits(cfg, seed=0):
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(seed))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    out, _ = jax.jit(lambda p: model.train_logits(p, {"tokens": toks}))(
        params)
    return np.asarray(out.astype(jnp.float32))


def test_multipliers_at_one_give_the_plain_numbers():
    """Neutral multipliers, spelled out, compute what a config without
    them computes, bit for bit (the attention scale 1/sqrt(head_dim) is
    the default's)."""
    cfg = smoke(get_config("stablelm-1.6b"))
    spelled = dataclasses.replace(cfg, embed_scale=1.0,
                                  attn_multiplier=1 / math.sqrt(cfg.head_dim),
                                  logits_scaling=1.0, residual_scale=1.0)
    np.testing.assert_array_equal(_logits(cfg), _logits(spelled))


def test_multipliers_scale_what_they_name():
    cfg = smoke(get_config("granite-4.0-h-micro"))
    plain = dataclasses.replace(cfg, logits_scaling=1.0)
    np.testing.assert_array_equal(_logits(plain) / 8.0, _logits(cfg))
    # the embedding multiplier reaches the output (tied table, NoPE)
    assert not np.array_equal(
        _logits(dataclasses.replace(cfg, embed_scale=1.0)), _logits(cfg))


def test_slot_cache_bytes_split_kv_and_state():
    """One pipeline stage of 20 layers at 4096 positions: KV of the two
    attention layers (8 heads of 64, bf16) and, per Mamba-2 layer, the
    float32 state of 64 x 64 x 128 and the conv's last 3 bf16 inputs of
    x, B and C (4096 + 2 x 128 channels)."""
    from repro.models.api import cache_bytes_by_kind
    cfg = dataclasses.replace(get_config("granite-4.0-h-micro"), n_layers=20)
    got = cache_bytes_by_kind(build_model(cfg).cache_spec(1, 4096))
    assert got == {"kv": 2 * 2 * 8 * 64 * 2 * 4096,
                   "state": 18 * (4 * 64 * 64 * 128 + 3 * 4352 * 2)}
