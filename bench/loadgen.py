"""The one load generator.  A traffic mix is a data file
(``bench/traffic/<name>.json``) of lengths and sampling shares; this
module turns it into a stream of requests for a closed loop.

Every seed gets the same work in another order.  Requests come in blocks
(of the mix's ``block``, or else of the cell's number of decode slots);
within a block the prompt lengths, the output lengths and the greedy
flags are the same stratified draws from the mix's distributions for
every seed, and the seed only permutes them and picks the token ids.  So
every block asks for the same number of tokens whatever the seed, and so
does the first fill of the slots when it is a whole number of blocks.  A
window that ends inside a block still sees the seed's order; a block of
one gives every seed the same lengths in the same order, and the seed then
picks only the token ids (and the weights).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

_GOLDEN = (math.sqrt(5.0) - 1.0) / 2.0


@dataclass(frozen=True)
class GenRequest:
    index: int                  # position in the stream
    prompt: List[int]
    max_new_tokens: int
    temperature: float
    top_k: int
    top_p: float

    @property
    def greedy(self) -> bool:
        return self.temperature == 0.0


def quantile(dist: dict, p: float) -> int:
    """The ``p`` quantile of a length distribution, clipped and rounded."""
    lo, hi = int(dist["min"]), int(dist["max"])
    kind = dist["dist"]
    if kind == "lognormal":
        x = float(dist["median"]) * math.exp(
            float(dist["sigma"]) * NormalDist().inv_cdf(p))
    elif kind == "uniform":
        x = lo + p * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return int(min(hi, max(lo, round(x))))


def block_lengths(dist: dict, block: int, b: int) -> List[int]:
    """The stratified lengths of block ``b``: one draw from each of
    ``block`` equal-probability strata, at an offset that moves from block
    to block but not from seed to seed."""
    u = (b + 1) * _GOLDEN % 1.0
    return [quantile(dist, (j + u) / block) for j in range(block)]


def requests(traffic: dict, *, slots: int, seed: int, vocab: int
             ) -> Iterator[GenRequest]:
    """The endless request stream of a closed loop over ``slots``."""
    block = int(traffic.get("block", slots))
    share = float(traffic["greedy_share"])
    rng = np.random.default_rng(seed)
    sampled = traffic.get("sampled", {})
    b = 0
    while True:
        prompts = block_lengths(traffic["prompt_tokens"], block, b)
        outputs = block_lengths(traffic["output_tokens"], block, b)
        # greedy requests in this block: the stream's first k requests
        # hold floor(k * share) of them, whatever the block size
        n_greedy = int((b + 1) * block * share) - int(b * block * share)
        p_perm, o_perm, g_perm = (rng.permutation(block) for _ in range(3))
        for j in range(block):
            greedy = g_perm[j] < n_greedy
            n = prompts[p_perm[j]]
            yield GenRequest(
                index=b * block + j,
                prompt=rng.integers(1, vocab, n).tolist(),
                max_new_tokens=outputs[o_perm[j]],
                temperature=0.0 if greedy else
                float(sampled.get("temperature", 1.0)),
                top_k=0 if greedy else int(sampled.get("top_k", 0)),
                top_p=1.0 if greedy else float(sampled.get("top_p", 1.0)))
        b += 1
