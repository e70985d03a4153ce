"""The one traffic generator. A traffic mix is a data file in
``bench/traffic/<name>.json``; everything here is driven by its keys:

- ``arrival``: ``{"mode": "closed", "clients_per_row": c}`` (a client
  submits its next request when its last one ends) or
  ``{"mode": "poisson", "rate_per_s": r}`` (requests are due on a fixed
  schedule, whatever the server does);
- ``prompt_tokens`` / ``new_tokens``: lognormal lengths (``median``,
  ``sigma``, clipped to ``min``..``max``; prompts rounded up to a
  ``multiple``);
- ``method``: how every request is decoded (KAPPA at ``kappa``'s
  settings);
- ``preroll``: the population already in flight when the window opens.

Sizes are stratified: every seed gets the same multiset of lengths and
arrival gaps (the quantiles (i + 1/2) / n of each distribution), in an
order and with token ids drawn from the seed. So seeds change the work's
order and content, not its amount.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_STD = NormalDist()


@dataclasses.dataclass
class Request:
    prompt: np.ndarray          # int32 token ids
    max_new: int
    method: str                 # the program's decoding method


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lognormal_at(q, spec: dict, *, length_biased: bool = False):
    """Lognormal lengths at quantiles ``q``, clipped and rounded up to
    ``spec['multiple']`` (default 1). Length-biased: the law of the
    length of a request found in flight (density ~ length x density),
    which for a lognormal is the lognormal with mu + sigma^2."""
    mu, sigma = math.log(spec["median"]), spec["sigma"]
    if length_biased:
        mu += sigma * sigma
    z = np.array([_STD.inv_cdf(float(x)) for x in np.atleast_1d(q)])
    v = np.clip(np.exp(mu + sigma * z), spec["min"], spec["max"])
    m = spec.get("multiple", 1)
    return (np.ceil(v / m) * m).astype(np.int64)


def prompt_lengths(traffic: dict) -> List[int]:
    """Every prompt length the mix can send (the shapes to warm up)."""
    spec = traffic["prompt_tokens"]
    m = spec.get("multiple", 1)
    lo = int(math.ceil(spec["min"] / m) * m)
    return list(range(lo, int(spec["max"]) + 1, m))


def requests(traffic: dict, seed: int, vocab: int, n: int) -> List[Request]:
    """``n`` requests: stratified lengths in a seeded order."""
    rng = _rng(seed, 1)
    q = quantiles(n)
    plen = lognormal_at(q, traffic["prompt_tokens"])[rng.permutation(n)]
    new = lognormal_at(q, traffic["new_tokens"])[rng.permutation(n)]
    return [Request(rng.integers(0, vocab, int(p), dtype=np.int32),
                    int(m), traffic["method"]) for p, m in zip(plen, new)]


def arrivals(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    """Due times of a Poisson stream over ``[0, seconds)``: the
    ``round(rate x seconds)`` gaps at the exponential law's stratified
    quantiles, in a seeded order, scaled so that they fill the window
    exactly (the offered rate is the stated one)."""
    rate = traffic["arrival"]["rate_per_s"]
    n = max(1, int(round(rate * seconds)))
    gaps = -np.log1p(-quantiles(n)) / rate
    gaps = gaps[_rng(seed, 2).permutation(n)]
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds / gaps.sum())


def _population(traffic: dict, n: int):
    """``n`` requests in flight, as (context, remaining) groups of equal
    context, ascending. The pairing of lengths, ages and prompts is the
    same for every seed, so every seed prefills the same contexts."""
    spec = traffic["preroll"]
    group, mult = spec["group"], spec["context_multiple"]
    fixed = _rng(0, 3)
    q = quantiles(n)
    total = lognormal_at(q, traffic["new_tokens"], length_biased=True)
    total = total[fixed.permutation(n)]
    age = np.floor(q[fixed.permutation(n)] * total).astype(np.int64)
    plen = lognormal_at(q, traffic["prompt_tokens"])[fixed.permutation(n)]
    ctx = plen + age
    remaining = np.maximum(total - age, 1)
    order = np.argsort(ctx, kind="stable")
    out = []
    for g in range(0, n, group):
        idx = order[g:g + group]
        c = int(max(mult, round(np.median(ctx[idx]) / mult) * mult))
        out.append((c, remaining[idx]))
    return out


def preroll(traffic: dict, seed: int, vocab: int, *,
            max_seq: int) -> List[List[Request]]:
    """The population in flight when the window opens: ``requests``
    requests of the mix's ``method`` as groups of equal context (each
    group prefills in lockstep), whose context (prompt + tokens already
    generated) and remaining length follow the steady state of the mix:
    a request in flight has a length-biased length and a uniform age
    within it. Contexts are rounded to ``context_multiple``; each
    remaining length gains ``lead_tokens``, the tokens a request decodes
    in set-up before the window opens."""
    spec = traffic["preroll"]
    group = spec["group"]
    pop = _population(traffic, max(group, spec["requests"] // group * group))
    lead = spec.get("lead_tokens", 0)
    rng = _rng(seed, 3)
    return [[Request(rng.integers(0, vocab, c, dtype=np.int32),
                     int(min(r + lead, max_seq - c)), traffic["method"])
             for r in rng.permutation(rem)] for c, rem in pop]
