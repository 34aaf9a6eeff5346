"""The comparisons that decide ``correct``, against the plain reference.

Serving: for a sample of the requests the window finished, the reference
runs once over each prompt with its served tokens, and each served token
is read by how far its reference logit lies below the reference's best at
that position (``gap``).  The number compared is the widest gap.  The
control puts the reference in the program's place at float8: the gap of
the token the float8 logits put first.

Training: the reference follows the program's first three steps from the
same weights and batches.  Three numbers are compared, each the worst
over its steps or leaves:

- ``loss_rel``: the relative gap of each step's loss;
- ``grad_norm_rel``: per leaf, the gap between the norms of the first
  (clipped) gradient, the program's worked out from its Adam first moment
  after one step, over the larger of the reference leaf's norm and the
  median leaf's;
- ``update_norm_rel``: the same for the change of each leaf over the
  three steps.  Leaves whose reference gradient is under a thousandth of
  the median leaf's move by round-off alone and are left out.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.harness import np_rng
from chipbench.refs.common import PRECISIONS, adamw

# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def sample_finished(served: Sequence, seed: int, min_tokens: int,
                    max_requests: int) -> List[int]:
    """Indices of finished requests: the longest, then others drawn from
    the seed, until ``min_tokens`` served tokens or ``max_requests``."""
    done = [i for i, r in enumerate(served) if r.finished()]
    if not done:
        return []
    longest = max(done, key=lambda i: (served[i].max_new, -i))
    rng = np_rng(seed, 5)
    rest = [i for i in rng.permutation(done) if i != longest]
    pick, n = [longest], served[longest].max_new
    for i in rest:
        if n >= min_tokens or len(pick) >= max_requests:
            break
        pick.append(int(i))
        n += served[i].max_new
    return pick


def serve_gaps(family, sizes: Dict, params, seqs: Sequence[Tuple],
               capacity: int, control: Optional[str] = None
               ) -> Dict[str, float]:
    """``seqs``: ``(tokens, prompt_len)`` with ``tokens`` the prompt and
    the served tokens.  Returns the widest gap (and the control's), and
    the number of tokens read."""

    def gaps(params, toks):
        ref = family.logits(params, toks, sizes)
        best = jnp.max(ref, axis=-1)
        nxt = jnp.roll(toks, -1)
        out = {"gap": best - jnp.take_along_axis(ref, nxt[:, None], 1)[:, 0]}
        if control:
            ctl = family.logits(params, toks, sizes, PRECISIONS[control])
            pick = jnp.argmax(ctl, axis=-1)
            out["control_gap"] = best - jnp.take_along_axis(
                ref, pick[:, None], 1)[:, 0]
        return out

    worst = {"gap": 0.0, "control_gap": 0.0}
    if any(np.any((t < 0) | (t >= sizes["vocab_size"])) for t, _ in seqs):
        worst["gap"] = float("inf")      # a token outside the vocabulary
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(gaps)
        n = 0
        for toks, plen in seqs:
            pad = np.zeros((capacity,), np.int32)
            pad[:len(toks)] = toks
            out = jax.device_get(fn(params, pad))
            lo, hi = plen - 1, len(toks) - 1       # positions that served
            n += hi - lo
            for k, v in out.items():
                worst[k] = max(worst[k], float(np.max(v[lo:hi])))
    if not control:
        worst.pop("control_gap")
    worst["tokens"] = n
    return worst


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def leaf_norms(tree) -> Dict[str, float]:
    from chipbench.weights import flat_named
    named = flat_named(tree)
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in t])(list(named.values()))
    return dict(zip(named, (float(x) for x in jax.device_get(norms))))


def change_norms(new, old) -> Dict[str, float]:
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32), new, old))


def train_reference(family, sizes: Dict, opt: Dict, params0,
                    batches: Sequence[Dict], block_rows: int,
                    precision: str = "float32", rows: Optional[int] = None
                    ) -> Dict:
    """The reference's steps from ``params0`` over ``batches``: losses,
    leaf norms of the first clipped gradient and of its raw gradient, and
    of each leaf's change.  ``rows`` keeps only the first rows of each
    batch (a fault read for the limits, never in a run)."""
    rnd = PRECISIONS[precision]

    def block_grad(p, blk):
        return jax.value_and_grad(
            lambda p: family.loss_sum(p, blk, sizes, rnd))(p)

    def clipped(g):
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                            for x in jax.tree.leaves(g)))
        return jax.tree.map(lambda x: x * jnp.minimum(
            1.0, opt["clip_norm"] / jnp.maximum(norm, 1e-9)), g)

    losses: List[float] = []
    with jax.default_matmul_precision("highest"):
        gfn = jax.jit(block_grad)
        step_fn = jax.jit(lambda p, g, m, v, t: adamw(p, g, m, v, t, opt),
                          donate_argnums=(0, 2, 3))
        p = jax.tree.map(lambda x: jnp.array(x, jnp.float32, copy=True),
                         params0)          # a copy: the steps donate it
        m = jax.tree.map(jnp.zeros_like, p)
        v = jax.tree.map(jnp.zeros_like, p)
        out: Dict = {}
        for step, batch in enumerate(batches):
            b = {k: jnp.asarray(x[:rows]) for k, x in batch.items()}
            n = float(np.sum(batch["mask"][:rows]))
            tot, g = 0.0, None
            for r in range(0, b["tokens"].shape[0], block_rows):
                blk = {k: x[r:r + block_rows] for k, x in b.items()}
                lb, gb = gfn(p, blk)
                tot += float(lb)
                g = gb if g is None else jax.tree.map(jnp.add, g, gb)
            g = jax.tree.map(lambda x: x / n, g)
            losses.append(tot / n)
            if step == 0:
                out["grad_raw"] = leaf_norms(g)
                out["grad"] = leaf_norms(jax.jit(clipped)(g))
                p0 = jax.tree.map(jnp.copy, p)
            p, m, v = step_fn(p, g, m, v, jnp.int32(step))
        out["change"] = change_norms(p, p0)
    out["losses"] = losses
    return out


def rel_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                 keep: Optional[Callable[[str], bool]] = None
                 ) -> Tuple[float, str]:
    """The worst leaf's gap between the two norms, over the larger of the
    reference leaf's norm and the median leaf's; and that leaf."""
    names = [k for k in ref if keep is None or keep(k)]
    med = float(np.median([ref[k] for k in names]))
    return max((abs(got[k] - ref[k]) / max(ref[k], med, 1e-30), k)
               for k in names)


def train_numbers(prog: Dict, ref: Dict) -> Tuple[Dict[str, float],
                                                  Dict[str, str]]:
    """The three compared numbers, and the leaf that sets each leaf
    number; ``prog`` and ``ref`` as ``train_reference`` returns them (the
    program's ``grad_raw`` is not needed)."""
    med = float(np.median(list(ref["grad_raw"].values())))
    moves = lambda k: ref["grad_raw"][k] >= 1e-3 * med  # noqa: E731
    grad, grad_leaf = rel_leaf_gap(prog["grad"], ref["grad"])
    upd, upd_leaf = rel_leaf_gap(prog["change"], ref["change"], moves)
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    return ({"loss_rel": loss, "grad_norm_rel": grad,
             "update_norm_rel": upd},
            {"grad_norm_rel": grad_leaf, "update_norm_rel": upd_leaf})
