"""Chunked prefill interleaved with decode (DESIGN.md §6): bitwise
equality of the final chunk's logits with one-shot prefill, chunk/page
boundary edge cases, scheduler equivalence with chunked admission with
and without the prefix cache, mid-PREFILLING preemption replay,
decode-stall bounds, and the prompt-sized admission-cache regression."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import (init_cache, init_paged_cache, init_params,
                          prefill_chunk)
from repro.serving import cache as cache_lib
from repro.serving import engine
from repro.serving.cache import PageAllocator
from repro.serving.scheduler import PagedScheduler


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=20, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    prompts = [
        np.array([tok.BOS, tok.PROB, 3, tok.PLUS, 4, tok.EQ, tok.QM]),
        np.array([tok.BOS, tok.PROB, 7, tok.PLUS, 2, tok.PLUS, 1, tok.EQ, tok.QM]),
        np.array([tok.BOS, tok.PROB, 5, tok.PLUS, 5, tok.EQ, tok.QM]),
    ]
    max_seq = max(len(p) for p in prompts) + kcfg.max_new_tokens
    return cfg, params, kcfg, prompts, max_seq


# ------------------------------------------------- bitwise logit parity

def test_prefill_chunked_bitwise_matches_oneshot(setup):
    """The acceptance property: on a global-attention layer pattern the
    final chunk's logits are BITWISE equal to the one-shot prefill —
    chunk == prompt, chunk dividing the prompt, chunk > prompt, and
    chunk = 1 (pure decode-style prefill) alike."""
    cfg, params, kcfg, prompts, max_seq = setup
    prompt = prompts[1]                       # len 9
    pf, _ = engine._prefill_one(params, cfg, prompt, max_seq)
    pf = np.asarray(pf)
    for chunk in (1, 3, 4, len(prompt), len(prompt) + 5):
        lc, _ = engine.prefill_chunked(params, cfg, prompt, max_seq, chunk)
        assert np.array_equal(np.asarray(lc), pf), f"chunk={chunk} diverged"


def test_prefill_chunked_paged_bitwise_matches_oneshot(setup):
    """Paged edition: chunk K/V written straight into allocator-owned
    pages, attention through the block table — last chunk's logits stay
    bitwise equal to the contiguous one-shot prefill."""
    cfg, params, kcfg, prompts, max_seq = setup
    prompt = prompts[1]
    ps = 4
    pf, _ = engine._prefill_one(params, cfg, prompt, max_seq)
    pf = np.asarray(pf)
    for chunk in (3, len(prompt), 2 * ps):    # incl. chunk == 2 pages
        num_pages = 12
        alloc = PageAllocator(num_pages, ps, rows=2,
                              max_pages=-(-max_seq // ps))
        pool = init_paged_cache(cfg, 2, num_pages, ps,
                                -(-max_seq // ps) * ps)
        aux = init_cache(cfg, 1, 1)
        logits, filled = None, 0
        while filled < len(prompt):
            piece = prompt[filled:filled + chunk]
            need = alloc.pages_for(filled + len(piece))
            while int(alloc.owned[0]) < need:
                if int(alloc.owned[0]) == 0:
                    alloc.set_row_pages(0, alloc.alloc_pages(1))
                else:
                    alloc.append_page(0)
            qpos = np.arange(filled, filled + len(piece))
            cpages = alloc.block[0][qpos // ps]
            logits, pool, aux = prefill_chunk(
                params, cfg, jnp.asarray(piece)[None],
                jnp.full((1,), filled, jnp.int32), 0, pool,
                jnp.asarray(alloc.block[0:1]),
                jnp.asarray(cpages.astype(np.int32))[None], aux)
            filled += len(piece)
        assert np.array_equal(np.asarray(logits)[0], pf), \
            f"paged chunk={chunk} diverged"


def test_prefill_chunked_bitwise_on_ring_pattern():
    """Sliding-window layers: the chunked path re-gathers the ring
    window in ascending absolute-position order, so the nonzero softmax
    terms sum in the same order as one-shot prefill and the final
    chunk's logits are BITWISE equal across chunk arrangements (was
    allclose-only while the history rode in rotated slot order)."""
    cfg = get_config("gemma3-4b").reduced(num_layers=6, d_model=64,
                                          vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.arange(2, 15) % 10 + 2
    pf, _ = engine._prefill_one(params, cfg, prompt, 40)
    for chunk in (1, 3, 4, 5, len(prompt), len(prompt) + 3):
        lc, _ = engine.prefill_chunked(params, cfg, prompt, 40, chunk)
        assert np.array_equal(np.asarray(lc), np.asarray(pf)), \
            f"ring chunk={chunk} diverged"


# ------------------------------------------------ scheduler equivalence

def _sequential(setup, method):
    cfg, params, kcfg, prompts, max_seq = setup
    fn = getattr(engine, f"generate_{method}")
    return [fn(params, cfg, kcfg, p, jax.random.PRNGKey(i), eos_id=tok.EOS,
               bos_id=tok.BOS, max_seq=max_seq)
            for i, p in enumerate(prompts)]


def _check_equal(seq, res, rids):
    for s, rid in zip(seq, rids):
        c = res[rid]
        assert s.tokens == c.tokens
        assert s.chosen_branch == c.chosen_branch
        assert s.logical_tokens == c.logical_tokens
        assert s.compute_tokens == c.compute_tokens
        assert s.steps == c.steps


@pytest.mark.parametrize("prefix_cache", [False, True],
                         ids=["paged", "paged+prefix"])
def test_chunked_paged_scheduler_matches_sequential(setup, prefix_cache):
    cfg, params, kcfg, prompts, max_seq = setup
    seq = _sequential(setup, "kappa")
    sched = PagedScheduler(
        params, cfg, kcfg, rows=6, max_seq=max_seq, page_size=8,
        num_pages=24, method="kappa", eos_id=tok.EOS, bos_id=tok.BOS,
        prefill_chunk=3, prefix_cache=prefix_cache)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    _check_equal(seq, sched.run(), rids)
    assert (sched.pcache is not None) == prefix_cache
    if prefix_cache:
        assert sched.pcache.pinned_count > 0   # completions published
        sched.pcache.drop()
    assert sched.alloc.free_count == 24        # zero leaked pages
    assert int(sched.alloc.pinned.sum()) == 0  # zero leaked pins
    assert sorted(sched.free) == list(range(6))
    assert not sched.prefilling


def test_chunked_mixed_strategies_match_sequential(setup):
    """Chunked admission under mixed kappa/bon/greedy traffic with
    per-request max_new — the whole strategy surface rides the same
    final-chunk logits."""
    cfg, params, kcfg, prompts, max_seq = setup
    specs = [("kappa", 20), ("bon", 12), ("greedy", 16)]
    seq = []
    for i, (p, (m, mn)) in enumerate(zip(prompts, specs)):
        kc = dataclasses.replace(kcfg, max_new_tokens=mn)
        fn = getattr(engine, f"generate_{m}")
        seq.append(fn(params, cfg, kc, p, jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=max_seq))
    sched = PagedScheduler(params, cfg, kcfg, rows=12, max_seq=max_seq,
                           page_size=8, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)
    rids = [sched.submit(p, jax.random.PRNGKey(i), max_new=mn, method=m)
            for i, (p, (m, mn)) in enumerate(zip(prompts, specs))]
    res = sched.run()
    for s, rid in zip(seq, rids):
        assert s.tokens == res[rid].tokens
        assert s.logical_tokens == res[rid].logical_tokens
    assert sched.alloc.free_count == sched.num_pages


# ------------------------------------------------------ edge cases

def test_chunk_boundary_edge_cases(setup):
    """Prompt exactly one chunk, prompt an exact chunk multiple, chunk
    larger than the prompt, and a page-aligned prompt (no COW boundary
    page) all reproduce the sequential engine."""
    cfg, params, kcfg, prompts, max_seq = setup
    ps = 4
    cases = [
        (prompts[0], len(prompts[0])),        # one chunk == prompt
        (prompts[2], len(prompts[2]) // 2),   # len 6, chunk 3: multiple
        (prompts[1], 2 * len(prompts[1])),    # chunk > prompt
        (np.concatenate([prompts[0], [5]]), 3),  # len 8 = 2 pages exactly
    ]
    assert len(cases[3][0]) % ps == 0
    for prompt, chunk in cases:
        seq = engine.generate_kappa(params, cfg, kcfg, prompt,
                                    jax.random.PRNGKey(7), eos_id=tok.EOS,
                                    bos_id=tok.BOS, max_seq=max_seq)
        sched = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                               page_size=ps, num_pages=40, method="kappa",
                               eos_id=tok.EOS, bos_id=tok.BOS,
                               prefill_chunk=chunk)
        rid = sched.submit(prompt, jax.random.PRNGKey(7))
        res = sched.run()
        assert seq.tokens == res[rid].tokens, f"chunk={chunk} diverged"
        assert sched.alloc.free_count == sched.num_pages
        if len(prompt) % ps == 0 and kcfg.num_branches > 1:
            # page-aligned prompt: finalize shares every prompt page,
            # no boundary copy was ever allocated
            assert sched._page_peak <= len(prompt) // ps \
                + kcfg.num_branches * (sched.alloc.pages_for(
                    len(prompt) + kcfg.max_new_tokens) - len(prompt) // ps)


def test_eos_on_first_post_prefill_token(setup):
    """A greedy request whose very first sampled token is EOS finishes
    at activation: the chunked path must release its rows and pages
    without ever joining a decode tick."""
    cfg, params, kcfg, prompts, max_seq = setup
    prompt = prompts[0]
    pf, _ = engine._prefill_one(params, cfg, prompt, max_seq)
    eos = int(np.argmax(np.asarray(pf)))      # force: argmax IS the EOS id
    seq = engine.generate_greedy(params, cfg, kcfg, prompt,
                                 jax.random.PRNGKey(0), eos_id=eos,
                                 bos_id=tok.BOS, max_seq=max_seq)
    assert seq.tokens == [eos]
    sched = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                           page_size=4, num_pages=32, method="greedy",
                           eos_id=eos, bos_id=tok.BOS, prefill_chunk=3)
    rid = sched.submit(prompt, jax.random.PRNGKey(0))
    res = sched.run()
    assert res[rid].tokens == [eos]
    assert res[rid].steps == 0
    assert not sched.active and not sched.prefilling
    assert sched.alloc.free_count == sched.num_pages
    assert sorted(sched.free) == list(range(4))


def test_preemption_mid_prefill_replays_token_equal(setup):
    """Page pressure evicts the youngest request while it is still
    PREFILLING: its pages and rows come back, the original submission is
    requeued, and the replay is token-for-token identical to an
    unpreempted run."""
    cfg, params, kcfg, prompts, max_seq = setup
    short = prompts[0]
    long_p = np.concatenate([short] + [short[1:]] * 4)   # len 31
    max_seq2 = len(long_p) + kcfg.max_new_tokens + 1
    seq_a = engine.generate_bon(params, cfg, kcfg, short,
                                jax.random.PRNGKey(0), eos_id=tok.EOS,
                                bos_id=tok.BOS, max_seq=max_seq2)
    seq_b = engine.generate_greedy(params, cfg, kcfg, long_p,
                                   jax.random.PRNGKey(1), eos_id=tok.EOS,
                                   bos_id=tok.BOS, max_seq=max_seq2)
    sched = PagedScheduler(params, cfg, kcfg, rows=6, max_seq=max_seq2,
                           page_size=4, num_pages=26, method="bon",
                           eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=2)
    ra = sched.submit(short, jax.random.PRNGKey(0), method="bon")
    sched.tick()                              # A enters the pool first
    rb = sched.submit(long_p, jax.random.PRNGKey(1), method="greedy")
    saw_mid_prefill = False
    for _ in range(400):
        sched.tick()
        pf = sched.prefilling.get(rb)
        if pf is not None and 0 < pf.filled < len(long_p):
            saw_mid_prefill = True
        if not (sched.queue or sched.active or sched.prefilling):
            break
    assert saw_mid_prefill, "long request never observed mid-PREFILLING"
    assert sched.counters["preemptions"] >= 1
    assert sched.results[ra].tokens == seq_a.tokens
    assert sched.results[rb].tokens == seq_b.tokens
    assert sched.alloc.free_count == sched.num_pages
    assert sorted(sched.free) == list(range(6))


# -------------------------------------------- interleaving / no stalls

def test_decode_advances_every_tick_during_long_prefill(setup):
    """The head-of-line fix itself: while a long prompt is PREFILLING,
    already-decoding requests emit one token EVERY tick (with one-shot
    admission the whole prompt lands inside a single tick instead)."""
    cfg, params, kcfg, prompts, max_seq = setup
    long_p = np.concatenate([prompts[0]] + [prompts[0][1:]] * 4)
    max_seq2 = len(long_p) + kcfg.max_new_tokens
    sched = PagedScheduler(params, cfg, kcfg, rows=6, max_seq=max_seq2,
                           page_size=8, num_pages=64, method="greedy",
                           eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=2)
    r1 = sched.submit(prompts[0], jax.random.PRNGKey(0))
    r2 = sched.submit(prompts[2], jax.random.PRNGKey(2))
    for _ in range(12):
        sched.tick()
        if r1 in sched.active and r2 in sched.active:
            break
    assert r1 in sched.active and r2 in sched.active
    rl = sched.submit(long_p, jax.random.PRNGKey(1))
    steps_before = sched.active[r1][0].step
    prefill_ticks = 0
    while rl in sched.prefilling or rl in (q.rid for q in sched.queue):
        sched.tick()
        prefill_ticks += 1
        if r1 not in sched.active:
            break
        # decode advanced THIS tick even though a prefill chunk also ran
        assert sched.active[r1][0].step == steps_before + prefill_ticks
    assert prefill_ticks >= len(long_p) // 2  # genuinely chunked
    sched.run()
    assert sched.alloc.free_count == sched.num_pages


def test_scheduler_latency_stats(setup):
    """TTFT / ITL accounting: every served request has a TTFT and a
    token timestamp per decode tick it participated in."""
    cfg, params, kcfg, prompts, max_seq = setup
    sched = PagedScheduler(
        params, cfg, kcfg, rows=6, max_seq=max_seq, page_size=8,
        method="kappa", eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    res = sched.run()
    stats = sched.latency_stats()
    assert set(rids) == set(sched.ttft)
    for rid in rids:
        assert sched.ttft[rid] > 0
        # first stamp at activation + one per decode tick the request saw
        assert len(sched.token_times[rid]) == res[rid].steps + 1
    assert stats["itl_p99_s"] >= stats["itl_p50_s"] >= 0
    assert stats["ttft_p99_s"] >= stats["ttft_p50_s"] > 0


# ------------------------------------------- admission-cache sizing fix

def test_admission_prefill_cache_sized_to_prompt(setup):
    """Regression: the one-shot admission's transient prefill cache is
    sized to the PROMPT (in whole pages), not max_seq — per-admission
    peak bytes shrink accordingly, and the chunked path's aux state is
    smaller still (global KV goes straight to pages)."""
    cfg, params, kcfg, prompts, max_seq = setup
    big_seq = 4 * max_seq                     # roomy pool, short prompts
    old_bytes = cache_lib.cache_bytes(init_cache(cfg, 1, big_seq))

    sched = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=big_seq,
                           page_size=8, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rid = sched.submit(prompts[0], jax.random.PRNGKey(0), max_new=8)
    sched.run()
    assert len(prompts[0]) <= 8               # the prompt fits one page
    prompt_bytes = cache_lib.cache_bytes(init_cache(cfg, 1, 8))
    assert sched.admit_peak_bytes == prompt_bytes
    assert sched.admit_peak_bytes * 4 <= old_bytes

    chunked = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=big_seq,
                             page_size=8, num_pages=64, method="kappa",
                             eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)
    rid = chunked.submit(prompts[0], jax.random.PRNGKey(0), max_new=8)
    chunked.run()
    # chunked admissions carry only the batch-1 per-row aux state
    assert chunked.admit_peak_bytes < prompt_bytes
