"""Cross-request radix prefix cache (PR 6, DESIGN.md §7).

Three layers of coverage:

  * radix tree + pin bookkeeping against a bare :class:`PageAllocator`
    (publish/lookup roundtrip, page-granular keying, idempotent
    republish, LRU leaf eviction, aliased pages never evicted, tree
    drop = zero leak);
  * serving-level equivalence and savings: repeated/shared prompts hit
    the cache, chunked prefill resumes at the cached extent, and every
    served token stays identical to the cache-off run (the fuzz sweep
    in test_fuzz_equivalence.py covers random mixes; here the targeted
    scenarios) — including the generated-prefix (Path-Consistency)
    resubmission path that aliases DECODE-written pages;
  * eviction racing preemption: under page pressure the least-recently
    -hit cached pages are released first, so a lone request never
    preempts anything (evictions > 0, preemptions == 0) and stays
    token-for-token equal.

Also covers the PR 5 follow-up satellite: multiple concurrent prefill
chunks riding ONE fused decode dispatch.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving import engine
from repro.serving.cache import PageAllocator, RadixPrefixCache
from repro.serving.scheduler import PagedScheduler

from allocator_harness import check_invariants

MAX_SEQ = 32
PAGE_SIZE = 4


# ------------------------------------------------------- radix tree unit

def test_radix_publish_lookup_roundtrip():
    alloc = PageAllocator(8, 4, 2, 8)
    pc = RadixPrefixCache(alloc, 4)
    toks = np.arange(12)
    alloc.alloc_row(0, 3)
    pages = [int(p) for p in alloc.row_pages(0)]
    assert pc.publish(toks, pages) == 3
    assert pc.publish(toks, pages) == 0          # idempotent republish
    assert pc.pinned_count == 3
    check_invariants(alloc)
    alloc.free_row(0)                            # pins keep pages live
    assert alloc.free_count == 8 - 3
    check_invariants(alloc)
    assert pc.lookup(toks) == pages
    assert pc.lookup(toks[:11]) == pages[:2]     # partial page never matches
    assert pc.lookup(toks[:3]) == []             # shorter than one page
    div = toks.copy()
    div[5] = 99                                  # diverges inside page 1
    assert pc.lookup(div) == pages[:1]
    assert pc.evictable_count == 3
    assert pc.drop() == 3
    check_invariants(alloc)
    assert alloc.free_count == 8 and int(alloc.pinned.sum()) == 0


def test_radix_lru_leaf_eviction_order():
    alloc = PageAllocator(8, 4, 3, 8)
    pc = RadixPrefixCache(alloc, 4)
    a = np.arange(12)                            # chain A: 3 pages
    b = np.concatenate([[50], np.arange(1, 8)])  # chain B: 2 pages
    alloc.alloc_row(0, 3)
    pc.publish(a, [int(p) for p in alloc.row_pages(0)])
    alloc.free_row(0)
    alloc.alloc_row(1, 2)
    b_pages = [int(p) for p in alloc.row_pages(1)]
    pc.publish(b, b_pages)
    alloc.free_row(1)
    pc.lookup(a)                                 # stamp chain A hotter
    # leaves only: chain B's tail is the coldest evictable node
    assert pc.evict_one() == b_pages[1]
    assert pc.evict_one() == b_pages[0]
    # chain A evicts deepest-first (inner nodes have children)
    a_hit = pc.lookup(a)
    assert pc.evict_one() == a_hit[2]
    check_invariants(alloc)
    assert pc.pinned_count == 2 and pc.evictions == 3


def test_radix_aliased_pages_never_evicted():
    alloc = PageAllocator(6, 4, 2, 6)
    pc = RadixPrefixCache(alloc, 4)
    toks = np.arange(8)
    alloc.alloc_row(0, 2)
    pages = [int(p) for p in alloc.row_pages(0)]
    pc.publish(toks, pages)
    alloc.free_row(0)
    # a later request aliases the cached pages (lookup -> set_row_pages)
    alloc.set_row_pages(1, pc.lookup(toks))
    check_invariants(alloc)
    assert pc.evictable_count == 0
    assert pc.evict_one() is None                # nothing evictable
    alloc.free_row(1)
    assert pc.evictable_count == 2
    assert pc.evict_one() is not None
    pc.drop()
    check_invariants(alloc)
    assert alloc.free_count == 6


def test_pin_requires_live_page():
    alloc = PageAllocator(4, 4, 1, 4)
    with pytest.raises(ValueError):
        alloc.pin_page(0)                        # unreferenced
    with pytest.raises(ValueError):
        alloc.unpin_page(0)                      # never pinned
    alloc.alloc_row(0, 1)
    p = int(alloc.row_pages(0)[0])
    alloc.pin_page(p)
    alloc.free_row(0)
    assert alloc.free_count == 3                 # pin holds the page
    alloc.unpin_page(p)
    assert alloc.free_count == 4


# ------------------------------------------------------ serving fixtures

@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=20, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    return cfg, params, kcfg


def _prompt(seed, plen):
    body = np.random.default_rng(seed).integers(0, tok.MOD, size=plen - 2)
    return np.concatenate([[tok.BOS], body, [tok.QM]])


def _sched(setup, *, num_pages=None, prefix_cache=False, chunk=5):
    cfg, params, kcfg = setup
    return PagedScheduler(
        params, cfg, kcfg, rows=8, max_seq=MAX_SEQ, page_size=PAGE_SIZE,
        num_pages=num_pages or 8 * MAX_SEQ // PAGE_SIZE, method="kappa",
        eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=chunk,
        prefix_cache=prefix_cache)


def _teardown_ok(sched):
    """Quiescence + zero-leak after the tree drop — the harness
    invariants hold both with live pins and after."""
    check_invariants(sched.alloc)
    if sched.pcache is not None:
        sched.pcache.drop()
    assert sched.alloc.free_count == sched.num_pages
    assert int(sched.alloc.pinned.sum()) == 0
    check_invariants(sched.alloc)


# ------------------------------------------------- hits, savings, equality

def test_repeated_prompt_hits_and_stays_equal(setup):
    """The same prompt served twice in a row: the replay aliases the
    published pages up to the full-hit cap ((plen-1)//ps pages — the
    last token always re-prefills for its logits) and both requests
    stay token-for-token equal to the cache-off run."""
    plen = 13
    p = _prompt(3, plen)

    def serve(pc):
        s = _sched(setup, prefix_cache=pc)
        r1 = s.submit(p, jax.random.PRNGKey(1), max_new=8, method="kappa")
        first = s.run()[r1].tokens
        r2 = s.submit(p, jax.random.PRNGKey(2), max_new=8, method="bon")
        second = s.run()[r2].tokens
        return first, second, s

    f0, s0, _ = serve(False)
    f1, s1, sched = serve(True)
    assert f0 == f1 and s0 == s1
    assert sched.counters["prefix_hits"] == 1
    assert sched.counters["prefix_tokens_saved"] \
        == ((plen - 1) // PAGE_SIZE) * PAGE_SIZE
    assert sched.throughput()["prefix_hit_rate"] == 0.5   # miss, then hit
    _teardown_ok(sched)


def test_generated_prefix_resubmission(setup):
    """Path-Consistency scenario: resubmitting prompt + the winner's
    generated prefix aliases DECODE-written pages and must stay exactly
    equal to re-prefilling those tokens from scratch."""
    p1 = _prompt(11, 9)
    ref = _sched(setup)
    rid = ref.submit(p1, jax.random.PRNGKey(5), max_new=10, method="kappa")
    gen = ref.run()[rid].tokens
    assert len(gen) >= 6
    p2 = np.concatenate([p1, gen[:6]])

    def serve(pc):
        s = _sched(setup, prefix_cache=pc)
        a = s.submit(p1, jax.random.PRNGKey(5), max_new=10, method="kappa")
        ra = s.run()[a].tokens
        b = s.submit(p2, jax.random.PRNGKey(9), max_new=8, method="stbon")
        rb = s.run()[b].tokens
        return ra, rb, s

    a0, b0, _ = serve(False)
    a1, b1, sched = serve(True)
    assert a0 == a1 and b0 == b1
    assert sched.counters["prefix_hits"] == 1
    # the hit extends past the original prompt into generated pages
    assert sched.counters["prefix_tokens_saved"] > len(p1)
    _teardown_ok(sched)


def test_eviction_races_preemption(setup):
    """A lone admission under page pressure reclaims pinned prefix
    pages instead of preempting: evictions > 0, preemptions == 0, and
    the tokens match the cache-off run exactly."""
    cfg, params, kcfg = setup
    prompts = [_prompt(s, 12) for s in (21, 22, 23)]

    # the admission guard needs the pool to hold one request's worst
    # case (15 pages); each completed request pins 5 prefix pages and
    # peaks at ~7 live, so by the third sequential request the 16-page
    # pool's free count (16 - 10 pinned = 6) is below its peak — it must
    # reclaim least-recently-hit pins, never preempt (it runs alone)
    def serve(pc):
        s = _sched(setup, num_pages=16, prefix_cache=pc)
        toks = []
        for i, p in enumerate(prompts):
            r = s.submit(p, jax.random.PRNGKey(i + 1), max_new=10,
                         method="kappa")
            toks.append(s.run()[r].tokens)
        return toks, s

    t0, off = serve(False)
    t1, on = serve(True)
    assert t0 == t1
    assert on.counters["prefix_evictions"] > 0, \
        "pressure never forced an eviction — scenario too loose"
    assert on.counters["preemptions"] == 0
    assert off.counters["preemptions"] == 0
    _teardown_ok(on)


def test_forced_pressure_with_cache_stays_equal(setup):
    """Concurrent mixed traffic on a pool tight enough to preempt, with
    the prefix cache live: eviction composes with youngest-first
    preemption and the result stays equal to the generous-pool run."""
    reqs = [(_prompt(31, 12), "kappa", 10), (_prompt(32, 12), "kappa", 8),
            (_prompt(31, 12), "bon", 6)]

    def serve(num_pages, pc):
        s = _sched(setup, num_pages=num_pages, prefix_cache=pc)
        rids = [s.submit(p, jax.random.PRNGKey(i), max_new=mn, method=m)
                for i, (p, m, mn) in enumerate(reqs)]
        res = s.run()
        return [res[r].tokens for r in rids], s

    base, _ = serve(None, False)
    got, sched = serve(17, True)
    assert base == got
    _teardown_ok(sched)


# ------------------------------------------- PR 5 follow-up: multi-fuse

def test_concurrent_prefill_chunks_fuse_into_one_dispatch(setup, monkeypatch):
    """Two long-prompt admissions prefilling while a third request
    decodes: BOTH pending chunks ride a single fused decode dispatch
    (PR 5 fused only the oldest), and the served tokens still match the
    sequential engine."""
    cfg, params, kcfg = setup
    import dataclasses
    calls = []
    orig = engine._fused_decode_chunks

    def spy(*args):
        calls.append(len(args[7]))
        return orig(*args)

    monkeypatch.setattr(engine, "_fused_decode_chunks", spy)
    s = _sched(setup, chunk=4)
    prompts = [_prompt(41, 8), _prompt(42, 16), _prompt(43, 16)]
    meths = ["kappa", "greedy", "greedy"]
    rids = [s.submit(p, jax.random.PRNGKey(i), max_new=10, method=m)
            for i, (p, m) in enumerate(zip(prompts, meths))]
    res = s.run()
    assert max(calls) >= 2, "younger prefill chunk did not fuse"
    assert s.counters["fused_chunks"] == sum(calls)
    for i, (p, m) in enumerate(zip(prompts, meths)):
        kc = dataclasses.replace(kcfg, max_new_tokens=10)
        ref = getattr(engine, f"generate_{m}")(
            params, cfg, kc, p, jax.random.PRNGKey(i),
            eos_id=tok.EOS, bos_id=tok.BOS, max_seq=MAX_SEQ)
        assert ref.tokens == res[rids[i]].tokens
