"""The one general generator that every traffic mix's data file drives.

Serving: a mix gives the burst size, a distribution of prompt lengths and
one of output lengths.  Sizes are stratified, not sampled: a burst of
``n`` requests takes the quantiles at ``(i + 0.5) / n`` of each
distribution, so every seed serves the same multiset of sizes.  The
strata are dealt into blocks of ``block`` requests, each block holding
every ``n / block``-th stratum, so that each block spans the whole
distribution; a seed reorders the blocks and the requests inside each
block, and draws the tokens.

Training mixes give the batch and sequence length; their batches come
from the program's own input pipeline (``drivers/train.py``).
"""

from __future__ import annotations

import math
from statistics import NormalDist
from typing import Dict, List, Sequence, Tuple

import numpy as np

from chipbench.harness import np_rng


def quantile(dist: Dict, u: float) -> int:
    """Length at quantile ``u`` of ``dist``: ``{"values": [...]}`` (equal
    shares) or ``{"lognormal": {"median", "sigma"}}``, then rounded up to
    the first of ``buckets`` and clipped to ``[min, max]``."""
    if "values" in dist:
        vals = dist["values"]
        x = float(vals[min(int(u * len(vals)), len(vals) - 1)])
    else:
        ln = dist["lognormal"]
        x = ln["median"] * math.exp(ln["sigma"] * NormalDist().inv_cdf(u))
    x = max(x, dist.get("min", 1))
    x = min(x, dist.get("max", x))
    n = math.ceil(x)
    for b in dist.get("buckets", ()):
        if n <= b:
            return int(b)
    if "buckets" in dist:
        return int(dist["buckets"][-1])
    return n


def lengths(dist: Dict) -> List[int]:
    """Every length ``dist`` can give (each one is a compiled shape)."""
    if "values" in dist:
        return sorted({int(v) for v in dist["values"]})
    if "buckets" in dist:
        return sorted(int(b) for b in dist["buckets"])
    raise ValueError("a prompt distribution needs values or buckets, so "
                     "that its shapes can be warmed up")


def burst_sizes(mix: Dict, seed: int, burst: int) -> List[Tuple[int, int]]:
    """``(prompt_len, max_new)`` of each request of burst ``burst``."""
    n, b = int(mix["burst"]), int(mix.get("block", mix["burst"]))
    if n % b:
        raise ValueError(f"burst {n} is not a multiple of block {b}")
    nb = n // b
    pair = np.random.default_rng(mix.get("pairing_seed", 0)).permutation(b)
    blocks = []
    for k in range(nb):
        strata = [k + nb * m for m in range(b)]
        blocks.append([
            (quantile(mix["prompt"], (strata[m] + 0.5) / n),
             quantile(mix["output"], (strata[pair[m]] + 0.5) / n))
            for m in range(b)])
    rng = np_rng(seed, 1, burst)
    out: List[Tuple[int, int]] = []
    for k in rng.permutation(nb):
        blk = blocks[k]
        out.extend(blk[i] for i in rng.permutation(b))
    return out


def prompt_tokens(seed: int, burst: int, sizes: Sequence[Tuple[int, int]],
                  vocab: int) -> List[np.ndarray]:
    rng = np_rng(seed, 2, burst)
    return [rng.integers(0, vocab, p, dtype=np.int32) for p, _ in sizes]

