"""The comparison that decides ``correct``.

For a sample of the requests the window served, the reference runs once
over each prompt followed by the tokens the program delivered, and two
numbers are read over every delivered token:

- ``gap``: how far below the reference's best logit the served token's
  reference logit lies, over the greedy requests' tokens (a greedy token
  that is the reference's own choice reads 0);
- ``logprob``: the largest difference between the log-probability the
  program reported for a served token and the reference's, over every
  request (greedy or sampled), which covers the sampler's readback.

A control puts a lower-precision copy of the reference in the program's
place: at the same positions of the same sequences it reads the ``gap``
of the token that the lower precision puts first, and the ``logprob`` it
gives the served token.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

import numpy as np


@dataclass
class Served:
    """One request's output as the window delivered it."""
    prompt: List[int]
    tokens: List[int]
    logprobs: List[float]
    greedy: bool


def sample(candidates: Sequence, n: int, seed: int, *, length, greedy
           ) -> list:
    """``n`` requests drawn from ``seed``: the longest one (by
    ``length``), then as even a split of greedy and sampled ones as the
    candidates allow."""
    if not candidates:
        return []
    rng = np.random.default_rng([seed % 2 ** 63, 7])
    longest = max(candidates, key=length)
    rest = [c for c in candidates if c is not longest]
    order = [rest[i] for i in rng.permutation(len(rest))]
    g = [c for c in order if greedy(c)]
    s = [c for c in order if not greedy(c)]
    picked = [longest]
    while len(picked) < n and (g or s):
        want_greedy = sum(map(greedy, picked)) * 2 < len(picked) + 1
        src = g if (want_greedy and g) or not s else s
        picked.append(src.pop(0))
    return picked


def _rows(served: Served) -> tuple:
    seq = list(served.prompt) + list(served.tokens[:-1])
    p = len(served.prompt)
    return seq, np.arange(p - 1, p - 1 + len(served.tokens))


@functools.lru_cache(maxsize=None)
def _compare():
    """(gap below the best, log-probability) of the tokens ``toks`` under
    ``ref`` logits, and of the token ``other`` logits put first, and
    ``other``'s log-probability of ``toks``."""
    import jax
    import jax.numpy as jnp

    def fn(ref, other, toks):
        best = jnp.max(ref, axis=-1)
        at = jnp.take_along_axis(ref, toks[:, None], 1)[:, 0]
        ref_lp = at - jax.nn.logsumexp(ref, axis=-1)
        first = jnp.argmax(other, axis=-1)
        o_gap = best - jnp.take_along_axis(ref, first[:, None], 1)[:, 0]
        o_lp = jnp.take_along_axis(other, toks[:, None], 1)[:, 0] - \
            jax.nn.logsumexp(other, axis=-1)
        return best - at, ref_lp, o_gap, o_lp
    return jax.jit(fn)


def readings(ref, served: List[Served], kinds: Sequence[str] = ()) -> dict:
    """The program's numbers, and each control kind's, over ``served``."""
    import jax
    import jax.numpy as jnp
    out = {"gap": 0.0, "logprob": 0.0, "tokens": 0, "greedy_tokens": 0,
           "control": {k: {"gap": 0.0, "logprob": 0.0} for k in kinds}}
    cmp = _compare()
    for sv in served:
        n = len(sv.tokens)
        if not n:
            continue
        seq, rows = _rows(sv)
        ref_logits = ref.logits(seq, rows)
        toks = np.full(ref_logits.shape[0], sv.tokens[-1], np.int32)
        toks[:n] = sv.tokens
        toks = jnp.asarray(toks)
        gap, ref_lp, _, _ = jax.device_get(cmp(ref_logits, ref_logits,
                                               toks))
        gap, ref_lp = gap[:n], ref_lp[:n]
        lp_err = np.abs(np.asarray(sv.logprobs, np.float64) - ref_lp)
        out["logprob"] = max(out["logprob"], float(lp_err.max()))
        out["tokens"] += n
        if sv.greedy:
            out["gap"] = max(out["gap"], float(gap.max()))
            out["greedy_tokens"] += n
        for k in kinds:
            c = ref.logits(seq, rows, kind=k)
            _, _, c_gap, c_lp = jax.device_get(cmp(ref_logits, c, toks))
            rec = out["control"][k]
            rec["logprob"] = max(rec["logprob"], float(
                np.abs(c_lp[:n] - ref_lp).max()))
            if sv.greedy:
                rec["gap"] = max(rec["gap"], float(c_gap[:n].max()))
    return out


def sane(served: List[Served], vocab: int) -> Optional[str]:
    """Why the served output is malformed, or None."""
    for sv in served:
        if len(sv.logprobs) != len(sv.tokens):
            return "a request has as many logprobs as tokens"
        if any(not 0 <= t < vocab for t in sv.tokens):
            return "a served token lies outside the vocabulary"
        if not np.all(np.isfinite(sv.logprobs)):
            return "a served logprob is not finite"
    return None


def verdict(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """``{name: {"value", "limit"}}`` and whether every number is within
    its limit (a number without a limit fails)."""
    table = {k: {"value": numbers[k], "limit": limits.get(k)}
             for k in ("gap", "logprob")}
    ok = all(v["limit"] is not None and v["value"] <= v["limit"]
             for v in table.values())
    return {"ok": ok, "table": table}
