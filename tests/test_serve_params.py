"""``BatchServer``'s own parameters, at smoke width on the CPU.

The server casts the caller's float32 tree to the models' compute dtype
once, when it is built, and its jitted steps read that tree.  For a
dense decoder and a Mamba-2/attention hybrid:

- the held tree is in the compute dtype (float leaves) or unchanged
  (integer leaves), the caller's tree stays float32, and ``param_bytes``
  counts the held tree;
- the served tokens and ``replay_logits`` are, bit for bit, what
  ``Model.prefill`` and ``Model.decode`` give from the caller's float32
  tree (the models cast it themselves);
- the lowered decode step converts no array of a parameter's shape from
  float32, so the per-call cast of the weights stays off its path.
"""

import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke
from repro.models import build_model
from repro.models.common import COMPUTE_DTYPE
from repro.serve import BatchServer, Request

pytestmark = pytest.mark.slow

ARCHS = ["stablelm-1.6b", "granite-4.0-h-micro"]
SLOTS, CAPACITY = 3, 32


def _floating(x) -> bool:
    return jnp.issubdtype(x.dtype, jnp.floating)


def _server(arch: str, params=None):
    model = build_model(smoke(get_config(arch)))
    if params is None:
        params = model.init(jax.random.PRNGKey(0))
    srv = BatchServer(model=model, params=params, slots=SLOTS,
                      seq_capacity=CAPACITY)
    srv.instantiate()
    return srv, params


def _requests(vocab: int):
    rng = np.random.default_rng(1)
    return [Request(rid=i, prompt=rng.integers(0, vocab, n, dtype=np.int32),
                    max_new_tokens=6) for i, n in enumerate((5, 11, 8, 3))]


def _reference_rows(model, params, req: Request) -> np.ndarray:
    """``replay_logits``'s rows from ``Model.prefill``/``Model.decode``
    on ``params``: the prompt alone (B=1), its cache put in slot 0 (axis
    1, behind the layers) of a fresh batch cache, then one decode step
    per served token."""
    prefill = jax.jit(functools.partial(model.prefill,
                                        seq_capacity=CAPACITY))
    decode = jax.jit(lambda p, t, c, n: model.decode(p, {"tokens": t}, c, n))
    logits, rcache = prefill(params, {"tokens": jnp.asarray(req.prompt[None])})
    cache = jax.tree.map(
        lambda c, rc: jax.lax.dynamic_update_slice_in_dim(
            c, rc.astype(c.dtype), 0, 1),
        model.init_cache(SLOTS, CAPACITY), rcache)
    rows = [logits[0, -1]]
    for i, t in enumerate(req.output[:-1]):
        tok = np.zeros((SLOTS, 1), np.int32)
        tok[0, 0] = t
        cur_len = np.zeros((SLOTS,), np.int32)
        cur_len[0] = len(req.prompt) + i
        logits, cache = decode(params, jnp.asarray(tok), cache,
                               jnp.asarray(cur_len))
        rows.append(logits[0, -1])
    return np.asarray(jnp.stack(rows).astype(jnp.float32))


@pytest.mark.parametrize("arch", ARCHS)
def test_server_holds_compute_dtype_params(arch):
    model = build_model(smoke(get_config(arch)))
    params = dict(model.init(jax.random.PRNGKey(0)),
                  step=jnp.arange(3, dtype=jnp.int32))
    before = jax.tree.map(np.asarray, params)
    srv, _ = _server(arch, params)
    held, given = jax.tree.leaves(srv.params), jax.tree.leaves(params)
    assert jax.tree.structure(srv.params) == jax.tree.structure(params)
    assert any(not _floating(x) for x in given)
    for h, g, b in zip(held, given, jax.tree.leaves(before)):
        assert h.shape == g.shape
        if _floating(g):
            assert g.dtype == jnp.float32 and h.dtype == COMPUTE_DTYPE
            np.testing.assert_array_equal(np.asarray(h),
                                          b.astype(COMPUTE_DTYPE))
        else:
            assert h.dtype == g.dtype
            np.testing.assert_array_equal(np.asarray(h), b)
        # the caller's tree is left as it was
        np.testing.assert_array_equal(np.asarray(g), b)


@pytest.mark.parametrize("arch", ARCHS)
def test_param_bytes_counts_the_held_tree(arch):
    srv, params = _server(arch)
    held = sum(x.nbytes for x in jax.tree.leaves(srv.params))
    assert srv.stats.flat()["server.param_bytes"] == held
    # every smoke leaf is float32: the held tree is half of it in bf16
    assert 2 * held == sum(x.nbytes for x in jax.tree.leaves(params))


@pytest.mark.parametrize("arch", ARCHS)
def test_served_logits_equal_the_models_on_float32_params(arch):
    srv, params = _server(arch)
    reqs = _requests(srv.model.cfg.vocab_size)
    assert len(srv.serve(reqs)) == len(reqs)
    for r in reqs:
        ref = _reference_rows(srv.model, params, r)
        assert ref.argmax(-1).tolist() == r.output, r.rid
        np.testing.assert_array_equal(srv.replay_logits(r), ref)


@pytest.mark.parametrize("arch", ARCHS)
def test_decode_step_converts_no_float32_weight(arch):
    """The HLO of the server's decode step on its held params has no
    ``convert`` from a float32 array shaped like a parameter; the same
    step on the caller's float32 tree has one per float leaf."""
    srv, params = _server(arch)
    shapes = {x.shape for x in jax.tree.leaves(params) if _floating(x)}
    batch = {"tokens": jnp.zeros((SLOTS, 1), jnp.int32),
             "cache": srv.model.init_cache(SLOTS, CAPACITY),
             "cur_len": jnp.zeros((SLOTS,), jnp.int32)}

    def weight_converts(p):
        hlo = srv._decode.lower(p, batch).as_text()
        found = re.findall(
            r"stablehlo\.convert %\S+ : \(tensor<([0-9x]*)xf32>\)", hlo)
        return [s for s in found
                if tuple(int(d) for d in s.split("x") if d) in shapes]

    assert weight_converts(srv.params) == []
    assert len(weight_converts(params)) >= len(shapes)
