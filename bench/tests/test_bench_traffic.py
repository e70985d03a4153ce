"""The traffic generator: determinism per seed, stratified lengths,
arrivals and the pre-roll population."""
import json
import math
from pathlib import Path

import numpy as np

from bench import traffic

MIX = json.loads((Path(__file__).resolve().parents[1]
                  / "traffic/kappa-reasoning-batch.json").read_text())
POISSON = json.loads((Path(__file__).resolve().parent
                      / "data/toy-poisson.json").read_text())
BIG = 2 ** 33 + 12345          # seeds are wider than 32 bits


def _sig(reqs):
    return [(len(r.prompt), r.max_new, r.method, int(r.prompt.sum()))
            for r in reqs]


def test_requests_repeat_per_seed():
    a = traffic.requests(MIX, BIG, 151936, 512)
    b = traffic.requests(MIX, BIG, 151936, 512)
    assert _sig(a) == _sig(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_seeds_reorder_the_same_sizes():
    a = traffic.requests(MIX, 1, 151936, 512)
    b = traffic.requests(MIX, BIG, 151936, 512)
    assert _sig(a) != _sig(b)
    for key in (lambda r: len(r.prompt), lambda r: r.max_new,
                lambda r: r.method):
        assert sorted(map(key, a)) == sorted(map(key, b))


def test_length_quantiles():
    reqs = traffic.requests(MIX, 3, 151936, 4096)
    plen = np.array([len(r.prompt) for r in reqs])
    new = np.array([r.max_new for r in reqs])
    assert set(plen % 64) == {0}
    assert plen.min() >= 48 and plen.max() <= 512
    assert new.min() >= 256 and new.max() <= 4096
    # median 160 rounded up to the next 64-token page
    assert np.median(plen) == 192
    # answers: lognormal(median 1200, sigma 0.7) at its quartiles
    for q in (0.25, 0.5, 0.75):
        z = {0.25: -0.6744897501960817, 0.5: 0.0, 0.75: 0.6744897501960817}[q]
        want = 1200 * math.exp(0.7 * z)
        assert abs(np.quantile(new, q) - want) <= 2
    # every request is decoded by the mix's method (KAPPA)
    assert {r.method for r in reqs} == {"kappa"}


def test_prompt_lengths_cover_every_shape():
    lens = traffic.prompt_lengths(MIX)
    assert lens == list(range(64, 513, 64))
    reqs = traffic.requests(MIX, 9, 151936, 4096)
    assert {len(r.prompt) for r in reqs} <= set(lens)


def test_arrivals():
    a = traffic.arrivals(POISSON, BIG, 40.0)
    b = traffic.arrivals(POISSON, 7, 40.0)
    n = round(POISSON["arrival"]["rate_per_s"] * 40.0)
    assert len(a) == len(b) == n
    assert a[0] == 0.0 and np.all(np.diff(a) > 0) and a[-1] < 40.0
    assert np.allclose(sorted(np.diff(np.append(a, 40.0))),
                       sorted(np.diff(np.append(b, 40.0))))


def test_preroll_same_contexts_every_seed():
    kw = dict(max_seq=5120)
    a = traffic.preroll(MIX, 1, 151936, **kw)
    b = traffic.preroll(MIX, BIG, 151936, **kw)
    ctx = lambda gs: [len(g[0].prompt) for g in gs]
    assert ctx(a) == ctx(b)
    assert all(len(g) == MIX["preroll"]["group"] for g in a)
    assert len(a) * MIX["preroll"]["group"] == MIX["preroll"]["requests"]
    assert {r.method for g in a for r in g} == {"kappa"}
    assert all(len({len(r.prompt) for r in g}) == 1 for g in a)
    assert all(len(g[0].prompt) % 512 == 0 for g in a)
    assert all(len(r.prompt) + r.max_new <= 5120 for g in a for r in g)
    pages = sum(math.ceil(len(r.prompt) / 64) for g in a for r in g)
    assert 0.5 * 2304 < pages < 0.9 * 2304
    assert sorted(r.max_new for g in a for r in g) == \
        sorted(r.max_new for g in b for r in g)
