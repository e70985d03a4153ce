"""Paged KV pool: allocator invariants, paged decode correctness, and
the paged scheduler's token-for-token equivalence with both the
sequential engine and the contiguous scheduler (DESIGN.md §5)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import decode_step, init_paged_cache, init_params
from repro.serving import cache as cache_lib
from repro.serving import engine
from repro.serving.cache import PageAllocator
from repro.serving.scheduler import ContinuousBatchingScheduler, PagedScheduler


# ---------------------------------------------------------- allocator

# one source of truth for the allocator's global invariant set —
# shared with the hypothesis op-stream property test (test_property.py)
# and the fuzz-equivalence leak checks
from allocator_harness import check_invariants as _check_invariants  # noqa: E402


def test_allocator_alloc_free_reuse():
    alloc = PageAllocator(8, 4, rows=4, max_pages=3)
    p0 = alloc.alloc_row(0, 3)
    p1 = alloc.alloc_row(1, 2)
    _check_invariants(alloc)
    assert alloc.used_count == 5 and alloc.free_count == 3
    alloc.free_row(0)
    _check_invariants(alloc)
    assert alloc.free_count == 6
    # freed pages are reusable by another row
    p2 = alloc.alloc_row(2, 3)
    _check_invariants(alloc)
    assert set(int(p) for p in p0) & set(int(p) for p in p2)
    assert alloc.pages_for(1) == 1 and alloc.pages_for(4) == 1 \
        and alloc.pages_for(5) == 2


def test_allocator_out_of_pages_and_misuse():
    alloc = PageAllocator(4, 4, rows=3, max_pages=4)
    alloc.alloc_row(0, 3)
    assert not alloc.can_alloc(2)
    with pytest.raises(ValueError):
        alloc.alloc_row(1, 2)           # only 1 page free
    with pytest.raises(ValueError):
        alloc.alloc_row(0, 1)           # row already owns pages
    with pytest.raises(ValueError):
        alloc.alloc_row(1, 5)           # > max_pages
    alloc.free_row(0)
    alloc.free_row(0)                   # double free is a no-op
    _check_invariants(alloc)
    assert alloc.free_count == 4


def test_allocator_churn_integrity():
    """Random prune→backfill churn never corrupts the block tables."""
    rng = np.random.RandomState(0)
    alloc = PageAllocator(32, 8, rows=12, max_pages=4)
    live = set()
    for _ in range(300):
        if live and (rng.rand() < 0.45 or len(live) == alloc.rows):
            r = rng.choice(sorted(live))
            alloc.free_row(r)
            live.discard(r)
        else:
            r = rng.choice([i for i in range(alloc.rows) if i not in live])
            n = rng.randint(1, alloc.max_pages + 1)
            if alloc.can_alloc(n):
                alloc.alloc_row(r, n)
                live.add(r)
        _check_invariants(alloc)
    for r in sorted(live):
        alloc.free_row(r)
    _check_invariants(alloc)
    assert alloc.free_count == alloc.num_pages


# --------------------------------------------------- COW / refcounts

def test_allocator_cow_share_diverge_free():
    """alloc -> share -> diverge -> free lifecycle: shared prompt pages
    carry one refcount per aliasing row, private growth is refcount-1,
    and a page returns to the free heap only on its LAST dereference."""
    alloc = PageAllocator(16, 4, rows=4, max_pages=6)
    shared = alloc.alloc_pages(2)               # prompt pages, shared by all
    for r in range(4):
        priv = alloc.alloc_pages(1)             # boundary COW copy
        alloc.set_row_pages(r, list(shared) + priv)
    _check_invariants(alloc)
    assert all(alloc.ref[p] == 4 for p in shared)
    assert alloc.used_count == 2 + 4            # shared counted once
    # diverge: rows grow private decode pages lazily
    for r in range(4):
        alloc.append_page(r)
    _check_invariants(alloc)
    assert alloc.used_count == 2 + 8
    # write pages are private (refcount 1): position 12 -> logical page 3
    phys = alloc.write_page(np.arange(4), np.full((4,), 12))
    assert len(set(int(p) for p in phys)) == 4
    # writing into the shared prompt pages would violate COW
    with pytest.raises(AssertionError):
        alloc.write_page(np.array([0]), np.array([2]))  # logical page 0: shared
    # writing past the owned table is a missed lazy-growth bug
    with pytest.raises(AssertionError):
        alloc.write_page(np.array([0]), np.array([16]))  # logical page 4
    # free three rows: shared pages stay allocated (ref > 0)
    for r in range(3):
        alloc.free_row(r)
        _check_invariants(alloc)
    assert all(alloc.ref[p] == 1 for p in shared)
    assert alloc.used_count == 2 + 2
    alloc.free_row(3)                           # last reference frees them
    _check_invariants(alloc)
    assert alloc.free_count == alloc.num_pages


def test_allocator_seeded_interleaving_invariants():
    """Seeded alloc / share / COW-diverge / free interleavings through
    the shared op-stream interpreter (allocator_harness) — the tier-1
    twin of the hypothesis property test in test_property.py, which
    needs the optional dependency: invariants hold after every op, zero
    pages leaked at quiescence."""
    from allocator_harness import run_allocator_ops
    rng = np.random.RandomState(42)
    kinds = ["alloc", "share", "diverge", "free", "pin", "unpin"]
    for trial in range(6):
        num_pages = int(rng.randint(6, 24))
        max_pages = int(rng.randint(2, 6))
        ops = [(kinds[int(rng.randint(len(kinds)))],
                int(rng.randint(10 ** 6)),
                int(rng.randint(10 ** 6))) for _ in range(120)]
        run_allocator_ops(num_pages, 4, 8, max_pages, ops)


def test_allocator_alloc_order_deterministic():
    """The free list is a min-heap, not a sorted-on-every-free list:
    allocation always hands out the smallest free ids, so two identical
    alloc/free histories produce identical page placement."""
    def churn(alloc):
        trace = []
        rng = np.random.RandomState(7)
        live = set()
        for _ in range(200):
            if live and (rng.rand() < 0.5 or len(live) == alloc.rows):
                r = int(rng.choice(sorted(live)))
                alloc.free_row(r)
                live.discard(r)
            else:
                r = int(rng.choice([i for i in range(alloc.rows)
                                    if i not in live]))
                n = int(rng.randint(1, alloc.max_pages + 1))
                if alloc.can_alloc(n):
                    trace.append(tuple(int(p) for p in alloc.alloc_row(r, n)))
                    live.add(r)
        return trace

    a, b = (PageAllocator(24, 8, rows=10, max_pages=4) for _ in range(2))
    assert churn(a) == churn(b)
    assert np.array_equal(a.block, b.block)
    assert sorted(a.free_pages) == sorted(b.free_pages)
    # smallest-first: out-of-order frees still allocate lowest ids next
    alloc = PageAllocator(8, 4, rows=4, max_pages=8)
    for r in range(3):
        alloc.alloc_row(r, 2)                   # rows own [0,1],[2,3],[4,5]
    alloc.free_row(1)                           # heap: 2,3,6,7
    alloc.free_row(0)                           # heap: 0,1,2,3,6,7
    assert alloc.alloc_pages(3) == [0, 1, 2]


# ----------------------------------------------------- paged decode step

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


def test_decode_step_paged_matches_contiguous(setup):
    """A paged pool with a scrambled page layout produces bitwise the
    same logits as the contiguous cache — across two decode steps so the
    paged write path is exercised too."""
    cfg, params, kcfg, prompts, _ = setup
    ps, max_seq = 8, 32
    MP = max_seq // ps
    rows, num_pages = 3, 14
    prompt = prompts[0]
    _, c1 = engine._prefill_one(params, cfg, prompt, max_seq)
    pool_c = cache_lib.broadcast_batch(c1, rows)

    alloc = PageAllocator(num_pages, ps, rows, MP)
    alloc.free_pages = [7, 2, 9, 0, 4, 1, 3, 5, 6, 8, 10, 11, 12, 13]
    for r in range(rows):
        alloc.alloc_row(r, MP)
    pool_p = init_paged_cache(cfg, rows, num_pages, ps, max_seq)
    pool_p = cache_lib.install_paged(
        cfg, pool_p, jnp.arange(rows), jnp.asarray(alloc.block.reshape(-1)),
        cache_lib.broadcast_batch(c1, rows), ps)

    step = jax.jit(decode_step, static_argnums=(1,))
    pos = jnp.array([len(prompt)] * rows, jnp.int32)
    bt = jnp.asarray(alloc.block)
    lc, pool_c = step(params, cfg, jnp.array([5, 9, 7]), pos, pool_c)
    lp, pool_p = step(params, cfg, jnp.array([5, 9, 7]), pos, pool_p, bt)
    assert np.array_equal(np.asarray(lc), np.asarray(lp))
    lc2, _ = step(params, cfg, jnp.array([2, 3, 4]), pos + 1, pool_c)
    lp2, _ = step(params, cfg, jnp.array([2, 3, 4]), pos + 1, pool_p, bt)
    assert np.array_equal(np.asarray(lc2), np.asarray(lp2))


# -------------------------------------------------- scheduler equivalence

def _sequential(setup, method):
    cfg, params, kcfg, prompts, max_seq = setup
    fn = getattr(engine, f"generate_{method}")
    return [fn(params, cfg, kcfg, p, jax.random.PRNGKey(i), eos_id=tok.EOS,
               bos_id=tok.BOS, max_seq=max_seq)
            for i, p in enumerate(prompts)]


def _paged(setup, method, rows, page_size, num_pages):
    cfg, params, kcfg, prompts, max_seq = setup
    sched = PagedScheduler(
        params, cfg, kcfg, rows=rows, max_seq=max_seq, page_size=page_size,
        num_pages=num_pages, method=method, eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    res = sched.run()
    return sched, [res[r] for r in rids]


def test_paged_scheduler_matches_sequential(setup):
    """The issue's acceptance property, paged edition: a page-constrained
    pool (requests wait on pages, pruning backfills) reproduces the
    sequential engine token for token with the same per-request keys."""
    seq = _sequential(setup, "kappa")
    sched, conc = _paged(setup, "kappa", rows=6, page_size=8, num_pages=24)
    for s, c in zip(seq, conc):
        assert s.tokens == c.tokens
        assert s.chosen_branch == c.chosen_branch
        assert s.logical_tokens == c.logical_tokens
        assert s.compute_tokens == c.compute_tokens
        assert s.steps == c.steps
        assert s.compactions == c.compactions
    tp = sched.throughput()
    assert 0.0 < tp["page_utilization"] <= 1.0
    # pool fully drained: every page and row slot back on the free lists
    assert sorted(sched.alloc.free_pages) == list(range(24))
    assert sorted(sched.free) == list(range(6))


def test_paged_matches_contiguous_scheduler(setup):
    """Paged and contiguous schedulers are token-for-token identical —
    paging changes where KV bytes live, not what gets decoded."""
    cfg, params, kcfg, prompts, max_seq = setup
    cont = ContinuousBatchingScheduler(
        params, cfg, kcfg, rows=6, max_seq=max_seq, method="kappa",
        eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [cont.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    res_c = cont.run()
    _, res_p = _paged(setup, "kappa", rows=6, page_size=8, num_pages=48)
    for r, p in zip((res_c[i] for i in rids), res_p):
        assert r.tokens == p.tokens
        assert r.chosen_branch == p.chosen_branch
        assert r.logical_tokens == p.logical_tokens


def test_paged_scheduler_mixed_max_new(setup):
    """Per-request max_new overrides: reservation is sized per request
    and results match dedicated sequential runs with the same kcfg."""
    import dataclasses
    cfg, params, kcfg, prompts, max_seq = setup
    max_news = [20, 8, 12]
    seq = []
    for i, (p, mn) in enumerate(zip(prompts, max_news)):
        kc = dataclasses.replace(kcfg, max_new_tokens=mn)
        seq.append(engine.generate_kappa(params, cfg, kc, p,
                                         jax.random.PRNGKey(i), eos_id=tok.EOS,
                                         bos_id=tok.BOS, max_seq=max_seq))
    sched = PagedScheduler(params, cfg, kcfg, rows=8, max_seq=max_seq,
                           page_size=8, num_pages=24, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i), max_new=mn)
            for i, (p, mn) in enumerate(zip(prompts, max_news))]
    res = sched.run()
    for s, rid in zip(seq, rids):
        assert s.tokens == res[rid].tokens
        assert s.logical_tokens == res[rid].logical_tokens


def test_paged_mixed_pool_batched_controller_contract(setup):
    """Acceptance property: a paged pool serving SEVERAL kappa requests
    (mixed with bon and greedy traffic, per-request max_new) makes at
    most one controller device dispatch and one controller-carrying
    blocking transfer per tick — counted, not assumed — and stays
    token-for-token equivalent to sequential serving."""
    import dataclasses
    cfg, params, kcfg, prompts, max_seq = setup
    specs = [("kappa", 20), ("kappa", 8), ("bon", 12),
             ("greedy", 16), ("kappa", 12)]
    ps = [prompts[i % len(prompts)] for i in range(len(specs))]
    seq = []
    for i, (p, (m, mn)) in enumerate(zip(ps, specs)):
        kc = dataclasses.replace(kcfg, max_new_tokens=mn)
        fn = getattr(engine, f"generate_{m}")
        seq.append(fn(params, cfg, kc, p, jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=max_seq))
    sched = PagedScheduler(params, cfg, kcfg, rows=12, max_seq=max_seq,
                           page_size=8, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i), max_new=mn, method=m)
            for i, (p, (m, mn)) in enumerate(zip(ps, specs))]
    res = sched.run()
    for s, rid, (m, mn) in zip(seq, rids, specs):
        assert s.tokens == res[rid].tokens, f"{m} diverged in the paged pool"
        assert s.logical_tokens == res[rid].logical_tokens
        assert s.steps == res[rid].steps
    # the controller contract, independent of the active kappa count
    assert sched._kappa_pool is not None
    assert sched._kappa_pool.dispatches >= 1
    assert sched.counters["controller_dispatches"] <= sched.ticks
    assert sched.counters["controller_syncs"] == \
        sched.counters["controller_dispatches"]
    # ≤ 2 blocking transfers per tick total (RNG keys + tokens/controller)
    assert sched.counters["host_syncs"] <= 2 * sched.ticks
    # pool fully drained
    assert sorted(sched.free) == list(range(12))
    assert sorted(sched._kappa_pool.free) == list(range(12))


def test_paged_out_of_pages_refusal(setup):
    """A request whose worst case exceeds the whole pool is refused at
    submit; one that merely has to wait is served once pages free up."""
    cfg, params, kcfg, prompts, max_seq = setup
    sched = PagedScheduler(params, cfg, kcfg, rows=8, max_seq=max_seq,
                           page_size=8, num_pages=8, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    with pytest.raises(ValueError):
        # fan-out 4 × ceil(27/8)=4 pages = 16 > 8 total
        sched.submit(prompts[0], jax.random.PRNGKey(0))
    # shrink the requests so each fills the whole pool: they serialize,
    # the second waiting until the first returns its pages
    rids = [sched.submit(p, jax.random.PRNGKey(i), max_new=7)
            for i, p in enumerate(prompts[:2])]
    res = sched.run()
    assert set(res) == set(rids)
    assert sorted(sched.alloc.free_pages) == list(range(8))


# ------------------------------------- COW prefix sharing / lazy alloc

def test_shared_admission_page_accounting(setup):
    """The acceptance property: admitting a fan-out-N request allocates
    shared_prompt_pages + N x (boundary copy + 1 decode page) — NOT the
    pre-PR N x ceil((prompt+max_new)/page_size) broadcast worst case —
    and lazy growth never exceeds prompt_pages_shared + N x private
    worst."""
    cfg, params, kcfg, prompts, max_seq = setup
    ps, N = 4, kcfg.num_branches
    sched = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                           page_size=ps, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    sched.submit(prompts[0], jax.random.PRNGKey(0))
    item = sched.queue[0]
    pos0 = len(prompts[0])                       # 7: full=1, boundary=1
    full, boundary = pos0 // ps, 1 if pos0 % ps else 0
    assert sched._initial_pages(item) == full + N * (1 + boundary)
    old_worst = N * sched.alloc.pages_for(item.need)
    new_worst = sched._worst_pages(item)
    assert new_worst == full + N * (sched.alloc.pages_for(item.need) - full)
    assert new_worst < old_worst
    assert sched._admit_one()
    # exactly the initial reservation is allocated, prompt pages shared
    assert sched.alloc.used_count == full + N * (1 + boundary)
    shared_pages = [p for p in range(sched.num_pages)
                    if sched.alloc.ref[p] == N]
    assert len(shared_pages) == full
    # every branch's write page is private (refcount 1)
    slots = next(iter(sched.active.values()))[1]
    wp = sched.alloc.write_page(np.asarray(slots), sched.row_pos[slots])
    assert np.all(sched.alloc.ref[wp] == 1)
    sched.run()
    assert sched._page_peak <= new_worst
    assert sched.alloc.free_count == sched.num_pages   # zero leaked pages
    _check_invariants(sched.alloc)


def test_shared_prompt_matches_broadcast_engine(setup):
    """Branches aliasing shared prompt pages decode token-for-token
    equal to the engine's broadcast-N dedicated cache (with forced page
    pressure so lazy growth fires mid-request)."""
    cfg, params, kcfg, prompts, max_seq = setup
    seq = [engine.generate_kappa(params, cfg, kcfg, p, jax.random.PRNGKey(i),
                                 eos_id=tok.EOS, bos_id=tok.BOS,
                                 max_seq=max_seq)
           for i, p in enumerate(prompts)]
    sched = PagedScheduler(params, cfg, kcfg, rows=6, max_seq=max_seq,
                           page_size=4, num_pages=26, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    res = sched.run()
    for s, rid in zip(seq, rids):
        assert s.tokens == res[rid].tokens
        assert s.chosen_branch == res[rid].chosen_branch
        assert s.logical_tokens == res[rid].logical_tokens
    assert sched.alloc.free_count == sched.num_pages
    _check_invariants(sched.alloc)


def test_fanout8_fits_budget_that_breaks_broadcast(setup):
    """N=8 fan-out on a long prompt completes inside a num_pages budget
    the pre-PR broadcast allocator could not even admit one request
    into — and stays token-equal to the sequential engine."""
    import dataclasses
    cfg, params, kcfg, prompts, max_seq = setup
    kcfg8 = dataclasses.replace(kcfg, num_branches=8)
    prompt = np.concatenate([prompts[0], prompts[1][1:], prompts[2][1:]])
    ps = 8
    max_seq8 = len(prompt) + kcfg8.max_new_tokens
    need = max_seq8
    pages_req = -(-need // ps)
    full = len(prompt) // ps
    broadcast_worst = 8 * pages_req
    shared_worst = full + 8 * (pages_req - full)
    num_pages = shared_worst + 2
    assert broadcast_worst > num_pages           # pre-PR submit would raise
    seq = engine.generate_kappa(params, cfg, kcfg8, prompt,
                                jax.random.PRNGKey(0), eos_id=tok.EOS,
                                bos_id=tok.BOS, max_seq=max_seq8)
    sched = PagedScheduler(params, cfg, kcfg8, rows=8, max_seq=max_seq8,
                           page_size=ps, num_pages=num_pages, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rid = sched.submit(prompt, jax.random.PRNGKey(0))
    res = sched.run()
    assert seq.tokens == res[rid].tokens
    assert seq.chosen_branch == res[rid].chosen_branch
    assert sched._page_peak <= num_pages
    assert sched.alloc.free_count == num_pages
    _check_invariants(sched.alloc)


def test_preemption_requeue_matches_unpreempted(setup):
    """When lazy growth drains the pool, the youngest-admitted request
    is preempted (pages freed, request requeued) and — replayed from its
    original RNG — still produces exactly the tokens of an un-preempted
    run."""
    cfg, params, kcfg, prompts, max_seq = setup
    seq = [engine.generate_bon(params, cfg, kcfg, p, jax.random.PRNGKey(i),
                               eos_id=tok.EOS, bos_id=tok.BOS,
                               max_seq=max_seq)
           for i, p in enumerate(prompts[:2])]
    # worst cases overlap: both admit on their initial pages, lazy
    # growth then outruns the pool and forces a preemption
    sched = PagedScheduler(params, cfg, kcfg, rows=8, max_seq=max_seq,
                           page_size=4, num_pages=26, method="bon",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts[:2])]
    res = sched.run()
    assert sched.counters["preemptions"] >= 1
    for s, rid in zip(seq, rids):
        assert s.tokens == res[rid].tokens
        assert s.chosen_branch == res[rid].chosen_branch
        assert s.logical_tokens == res[rid].logical_tokens
    assert sched.alloc.free_count == sched.num_pages
    assert sorted(sched.free) == list(range(8))
    _check_invariants(sched.alloc)


def test_mixed_pool_drains_allocator(setup):
    """Mixed-strategy pool churn (kappa prunes, bon releases EOS rows
    eagerly, greedy holds one row) never double-frees or leaks: the free
    heap returns to the full pool after run()."""
    cfg, params, kcfg, prompts, max_seq = setup
    specs = [("kappa", 20), ("bon", 12), ("greedy", 16), ("kappa", 8)]
    sched = PagedScheduler(params, cfg, kcfg, rows=10, max_seq=max_seq,
                           page_size=4, num_pages=48, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    for i, (m, mn) in enumerate(specs):
        sched.submit(prompts[i % len(prompts)], jax.random.PRNGKey(i),
                     max_new=mn, method=m)
    res = sched.run()
    assert len(res) == len(specs)
    assert sched.alloc.free_count == sched.num_pages
    assert sorted(sched.free) == list(range(10))
    _check_invariants(sched.alloc)


def test_paged_request_bytes_allocator_truth(setup):
    """request_bytes() reports what the pool actually holds: distinct
    referenced pages x per-page bytes (shared prompt pages charged once)
    plus the analytic non-paged per-row state — not a contiguous
    min(pos, max_seq) estimate."""
    cfg, params, kcfg, prompts, max_seq = setup
    ps, N = 4, kcfg.num_branches
    sched = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                           page_size=ps, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rid = sched.submit(prompts[0], jax.random.PRNGKey(0))
    assert sched._admit_one()
    got = sched.request_bytes()[rid]
    rs, slots = sched.active[rid]
    pages = {int(p) for s in slots for p in sched.alloc.row_pages(s)}
    pb = cache_lib.page_bytes(cfg, ps)
    want = len(pages) * pb + cache_lib.used_cache_bytes(
        cfg, len(slots), rs.pos, sched.max_seq, skip_global=True)
    assert got == want
    # sharing is visible: N branches cost less than N private copies
    full = len(prompts[0]) // ps
    assert len(pages) < N * (full + 2)
    sched.run()


def test_paged_sjf_admission_order(setup):
    """Among queued requests that fit, the paged scheduler picks the
    shortest job (fewest reserved pages), FIFO on ties — unlike the
    contiguous scheduler's strict head-of-line FIFO."""
    cfg, params, kcfg, prompts, max_seq = setup
    sched = PagedScheduler(params, cfg, kcfg, rows=8, max_seq=max_seq,
                           page_size=8, num_pages=64, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    sched.submit(prompts[0], jax.random.PRNGKey(0), max_new=20)   # long
    sched.submit(prompts[2], jax.random.PRNGKey(2), max_new=6)    # short
    sched.submit(prompts[1], jax.random.PRNGKey(1), max_new=6)    # short, longer prompt
    picked = sched._select_admit()
    assert sched.queue[picked].rid == 1          # shortest need wins
    # FIFO tie-break: equal-need requests admit in arrival order
    sched.queue[picked].need = sched.queue[2].need
    assert sched.queue[sched._select_admit()].rid == 1


def _drive_with_short_stream(sched, long_rid, prompts, ticks):
    """Tick the scheduler while feeding it a fresh short request every
    tick; returns True iff the long request got admitted."""
    for i in range(ticks):
        if long_rid in sched._admit_seq or long_rid in sched.results:
            return True
        sched.submit(prompts[0], jax.random.PRNGKey(100 + i), max_new=4,
                     method="greedy")
        sched.tick()
    return long_rid in sched._admit_seq or long_rid in sched.results


def test_sjf_aging_prevents_starvation(setup):
    """Regression for SJF starvation: under a steady stream of short
    submissions a long request was bypassed forever. With bounded bypass
    (after max_bypass bypasses the head admits next-fit-or-nothing) it
    gets in; with the old unbounded policy (max_bypass=inf) it starves —
    this test fails on the pre-fix policy."""
    cfg, params, kcfg, prompts, max_seq = setup

    long_prompt = np.concatenate([prompts[0], prompts[1][1:], prompts[2][1:]])

    def build(max_bypass):
        sched = PagedScheduler(params, cfg, kcfg, rows=4,
                               max_seq=len(long_prompt) + 20,
                               page_size=4, num_pages=11, method="greedy",
                               eos_id=tok.EOS, bos_id=tok.BOS,
                               max_bypass=max_bypass)
        # two shorts occupy the pool first; then the long job (7 pages up
        # front, 11 worst case) joins the queue — inadmissible whenever
        # >= 2 of the streaming shorts (3 pages each) are in flight
        for i in range(2):
            sched.submit(prompts[0], jax.random.PRNGKey(50 + i), max_new=4,
                         method="greedy")
        long_rid = sched.submit(long_prompt, jax.random.PRNGKey(0),
                                max_new=20, method="greedy")
        return sched, long_rid

    TICKS = 80
    sched, long_rid = build(max_bypass=4)
    assert _drive_with_short_stream(sched, long_rid, prompts, TICKS), \
        "aged head request was never admitted"
    # control: the unbounded-bypass policy starves the same request
    sched, long_rid = build(max_bypass=10**9)
    assert not _drive_with_short_stream(sched, long_rid, prompts, TICKS), \
        "starvation scenario no longer reproduces - tighten the setup"


# ----------------------------------------------- paged kernel wiring

def _paged_decode_fixture(setup, cfg):
    """Install a prefilled prompt into a fresh paged pool; returns the
    pieces a decode_step call needs."""
    _, params, _, prompts, _ = setup
    ps, max_seq = 8, 32
    MP = max_seq // ps
    rows, num_pages = 3, 14
    prompt = prompts[0]
    _, c1 = engine._prefill_one(params, cfg, prompt, max_seq)
    alloc = PageAllocator(num_pages, ps, rows, MP)
    for r in range(rows):
        alloc.alloc_row(r, MP)
    pool = init_paged_cache(cfg, rows, num_pages, ps, max_seq)
    pool = cache_lib.install_paged(
        cfg, pool, jnp.arange(rows), jnp.asarray(alloc.block.reshape(-1)),
        cache_lib.broadcast_batch(c1, rows), ps)
    pos = jnp.array([len(prompt)] * rows, jnp.int32)
    bt = jnp.asarray(alloc.block)
    return pool, pos, bt


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_attn_decode_paged_kernel_wiring(setup, kv_dtype):
    """attn_decode_paged routes through paged_decode_attn_pallas when
    the kernel path is enabled (forced here, running the Pallas
    interpreter on CPU) and matches the jnp gather oracle.

    The backend counters make silent fallback a hard failure: with the
    kernel forced, not a single layer may take the oracle branch. The
    int8 case is the regression for the quantized bypass — the old
    dispatch quietly dropped to the gather oracle whenever the cache was
    quantized, and the allclose alone never noticed."""
    import dataclasses
    from repro.models import attention as attn_mod
    cfg, params = setup[0], setup[1]
    if kv_dtype != "model":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    pool, pos, bt = _paged_decode_fixture(setup, cfg)
    toks = jnp.array([5, 9, 7])
    # eager (unjitted) calls so the kernel toggle takes effect per call
    lo, _ = decode_step(params, cfg, toks, pos, pool, bt)
    attn_mod.reset_paged_backend_counts()
    attn_mod.set_paged_kernel(True)
    try:
        lk, _ = decode_step(params, cfg, toks, pos, pool, bt)
    finally:
        attn_mod.set_paged_kernel(None)
    counts = attn_mod.paged_backend_counts()
    assert counts["decode_kernel"] >= 1, "kernel path never taken"
    assert counts["decode_oracle"] == 0, \
        f"silent fallback to the gather oracle: {counts}"
    np.testing.assert_allclose(np.asarray(lk), np.asarray(lo),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_attn_prefill_chunk_paged_kernel_wiring(setup, kv_dtype):
    """Chunked paged prefill routes through paged_prefill_attn_pallas
    when the kernel path is forced — backend counters prove no layer
    fell back to the jnp gather oracle — and the last-chunk logits match
    the oracle run."""
    import dataclasses
    from repro.models import attention as attn_mod
    from repro.models import init_cache, prefill_chunk
    cfg, params, _, prompts, max_seq = setup
    if kv_dtype != "model":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    prompt, ps, chunk, num_pages = prompts[1], 4, 3, 12
    MP = -(-max_seq // ps)

    def run_prefill():
        alloc = PageAllocator(num_pages, ps, rows=2, max_pages=MP)
        pool = init_paged_cache(cfg, 2, num_pages, ps, MP * ps)
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
        return np.asarray(logits)

    lo = run_prefill()
    attn_mod.reset_paged_backend_counts()
    attn_mod.set_paged_kernel(True)
    try:
        lk = run_prefill()
    finally:
        attn_mod.set_paged_kernel(None)
    counts = attn_mod.paged_backend_counts()
    assert counts["prefill_kernel"] >= 1, "prefill kernel path never taken"
    assert counts["prefill_oracle"] == 0, \
        f"silent fallback to the gather oracle: {counts}"
    np.testing.assert_allclose(lk, lo, rtol=2e-5, atol=2e-5)


# ------------------------------------------------- int8 paged serving

def _paged_leaf_axis(leaf, num_pages):
    """Axis of the physical-page dimension in a paged global leaf, or
    None for per-row leaves. Pools may stack layers (leading K axis)."""
    if leaf.ndim >= 1 and leaf.shape[0] == num_pages + 1:
        return 0
    if leaf.ndim >= 2 and leaf.shape[1] == num_pages + 1:
        return 1
    return None


@pytest.mark.parametrize("kv_dtype", ["model", "int8"])
def test_page_bytes_matches_leaf_nbytes(setup, kv_dtype):
    """page_bytes() is allocator truth, not an estimate: summed over the
    pool's global-layer leaves (values AND the int8 scale leaves, minus
    the trash page) it equals num_pages * page_bytes exactly. The old
    amortized float cost (1 + 4/hd per element) drifted under int()."""
    import dataclasses
    cfg = setup[0]
    if kv_dtype != "model":
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    ps, num_pages, rows, max_seq = 8, 14, 3, 32
    pool = init_paged_cache(cfg, rows, num_pages, ps, max_seq)
    per_page = 0
    for leaf in jax.tree.leaves(pool):
        ax = _paged_leaf_axis(leaf, num_pages)
        if ax is not None:
            assert leaf.nbytes % (num_pages + 1) == 0
            per_page += leaf.nbytes // (num_pages + 1)
    assert per_page > 0, "no paged global leaves found"
    assert cache_lib.page_bytes(cfg, ps) == per_page
    assert cache_lib.page_bytes(cfg, ps) * num_pages \
        == per_page * num_pages


def test_int8_scale_leaves_ride_cow_paths(setup):
    """COW plumbing carries the quantization scales: install_paged_shared
    scatters k_s/v_s page-wise next to the int8 values (staying float32 —
    an astype into the value dtype would truncate them to garbage), and
    copy_pages duplicates them onto the boundary COW copy."""
    import dataclasses
    from jax.tree_util import keystr, tree_flatten_with_path
    cfg = dataclasses.replace(setup[0], kv_cache_dtype="int8")
    params, prompts = setup[1], setup[3]
    prompt = prompts[1]                 # len 9 @ ps=4: 2 full + boundary
    ps, num_pages, max_seq, n = 4, 12, 32, 2
    _, c1 = engine._prefill_one(params, cfg, prompt, max_seq)
    pool = init_paged_cache(cfg, n, num_pages, ps, max_seq)
    # shared map: full prompt pages 0,1 once; boundary page 2 per branch
    src_idx = np.asarray([0, 1, 2, 2], np.int32)
    phys = np.asarray([0, 1, 2, 3], np.int32)
    pool = cache_lib.install_paged_shared(
        cfg, pool, jnp.arange(n), jnp.asarray(src_idx), jnp.asarray(phys),
        c1, ps)
    sub = {keystr(p): l for p, l in tree_flatten_with_path(c1)[0]}
    checked = 0
    for path, a in tree_flatten_with_path(pool)[0]:
        key = keystr(path)
        if "k_s" not in key and "v_s" not in key:
            continue
        ax = _paged_leaf_axis(a, num_pages)
        if ax is None:
            continue                    # per-row aux scales (ring layers)
        assert a.dtype == jnp.float32, f"{key} truncated to {a.dtype}"
        b = np.asarray(sub[key])
        # pool scale pages are head-major (..., P, KV, 1, ps)
        if ax == 0:                     # b: (1, S, KV)
            br = b[0].reshape((b.shape[1] // ps, ps) + b.shape[2:])
            br = br.transpose(0, 2, 1)[:, :, None, :]
            got, want = np.asarray(a)[phys], br[src_idx]
        else:                           # stacked, b: (K, 1, S, KV)
            br = b[:, 0].reshape((b.shape[0], b.shape[2] // ps, ps)
                                 + b.shape[3:])
            br = br.transpose(0, 1, 3, 2)[:, :, :, None, :]
            got, want = np.asarray(a)[:, phys], br[:, src_idx]
        assert np.array_equal(got, want), f"{key} scales mangled"
        checked += 1
    assert checked >= 2, "int8 pool grew no paged scale leaves"
    # COW page copy carries every global leaf, scales included
    pool2 = cache_lib.copy_pages(cfg, pool, jnp.asarray([2]),
                                 jnp.asarray([7]))
    for (path, a2), (_, a) in zip(tree_flatten_with_path(pool2)[0],
                                  tree_flatten_with_path(pool)[0]):
        ax = _paged_leaf_axis(a2, num_pages)
        if ax is None:
            continue
        a2, a = np.asarray(a2), np.asarray(a)
        if ax == 0:
            assert np.array_equal(a2[7], a[2]), keystr(path)
        else:
            assert np.array_equal(a2[:, 7], a[:, 2]), keystr(path)


def test_paged_scheduler_int8_mixed_matches_sequential(setup):
    """Token-for-token int8 serving: a mixed kappa/bon/stbon/greedy
    paged pool with a quantized cache reproduces the sequential engine
    (also int8) exactly — paging moves quantized bytes and their scales,
    it never re-rounds them."""
    import dataclasses
    cfg, params, kcfg, prompts, max_seq = setup
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    specs = [("kappa", 20), ("bon", 12), ("stbon", 12), ("greedy", 16)]
    seq = []
    for i, (m, mn) in enumerate(specs):
        kc = dataclasses.replace(kcfg, max_new_tokens=mn)
        fn = getattr(engine, f"generate_{m}")
        seq.append(fn(params, cfg8, kc, prompts[i % len(prompts)],
                      jax.random.PRNGKey(i), eos_id=tok.EOS, bos_id=tok.BOS,
                      max_seq=max_seq))
    sched = PagedScheduler(params, cfg8, kcfg, rows=10, max_seq=max_seq,
                           page_size=8, num_pages=48, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(prompts[i % len(prompts)], jax.random.PRNGKey(i),
                         max_new=mn, method=m)
            for i, (m, mn) in enumerate(specs)]
    res = sched.run()
    for s, rid, (m, _) in zip(seq, rids, specs):
        assert s.tokens == res[rid].tokens, f"{m} diverged under int8"
        assert s.logical_tokens == res[rid].logical_tokens
        assert s.steps == res[rid].steps
    assert sched.alloc.free_count == sched.num_pages
    assert sorted(sched.free) == list(range(10))
    _check_invariants(sched.alloc)


def test_page_budget_bytes_capacity(setup):
    """Admission capacity follows page_bytes: at one fixed HBM budget an
    int8 pool holds ~2x the pages of the model-dtype pool (exactly
    2 * hd / (hd + 4) more), and passing both num_pages and a budget is
    rejected."""
    import dataclasses
    cfg, params, kcfg, prompts, max_seq = setup
    budget = 64 * cache_lib.page_bytes(cfg, 8)
    s_fp = PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                          page_size=8, page_budget_bytes=budget,
                          method="kappa", eos_id=tok.EOS, bos_id=tok.BOS)
    assert s_fp.num_pages == 64
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    s_i8 = PagedScheduler(params, cfg8, kcfg, rows=4, max_seq=max_seq,
                          page_size=8, page_budget_bytes=budget,
                          method="kappa", eos_id=tok.EOS, bos_id=tok.BOS)
    assert s_i8.num_pages >= int(1.8 * s_fp.num_pages)
    with pytest.raises(ValueError):
        PagedScheduler(params, cfg, kcfg, rows=4, max_seq=max_seq,
                       page_size=8, num_pages=64, page_budget_bytes=budget,
                       method="kappa", eos_id=tok.EOS, bos_id=tok.BOS)
