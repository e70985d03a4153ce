"""Randomized cross-scheduler fuzz equivalence (PR 5 satellite).

One generated workload — mixed kappa/bon/stbon/greedy strategies,
random prompt lengths (including page-aligned prompts and prompts
shorter than one chunk), a random shared preamble so request mixes
overlap on token prefixes, random per-request ``max_new``, random
submit order — is served five ways and must stay token-for-token
identical:

  * the sequential engine (the reference),
  * the paged scheduler with chunked admission (generous pages),
  * the paged scheduler under forced page pressure (preemption live),
  * the paged scheduler with the radix prefix cache on (PR 6): later
    requests alias earlier requests' published pages,
  * the prefix cache under forced page pressure (eviction racing
    preemption).

Shapes are pinned (one ``max_seq``, one page size, a small chunk-size
menu) so the jit cache is shared across cases and the sweep stays
CPU-friendly. With hypothesis installed the sweep draws cases through
real strategies (seeded, shrinkable); without it a fixed seed list
exercises the same generator. One small case runs in tier-1; the sweep
is marked ``slow`` + ``fuzz``.
"""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving import engine
from repro.serving.scheduler import PagedScheduler

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # tier-1 still runs the seeded generator
    HAVE_HYPOTHESIS = False

MAX_SEQ = 32                 # fixed: every case shares one compiled shape
PAGE_SIZE = 4
METHODS = ("kappa", "bon", "stbon", "greedy")
# prompt lengths: 8 and 16 are page-aligned (no COW boundary page),
# 3 is shorter than every chunk size in the menu
PLENS = (3, 5, 8, 9, 12, 16)
MAX_NEWS = (4, 6, 10, 14)
CHUNKS = (4, 5, 7)


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=20, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    return cfg, params, kcfg


PRE_LENS = (0, 4, 8, 11)     # shared-preamble lengths (0 = disjoint)


def _case_from_seed(seed: int, n_requests=None):
    """Seeded case generator — the no-hypothesis path (and the prompt
    body source for both paths)."""
    rng = np.random.default_rng(seed)
    n = n_requests or int(rng.integers(2, 5))
    reqs = []
    for _ in range(n):
        reqs.append((METHODS[int(rng.integers(len(METHODS)))],
                     int(rng.choice(PLENS)),
                     int(rng.choice(MAX_NEWS))))
    return {"seed": seed, "reqs": reqs,
            "order": rng.permutation(n).tolist(),
            "chunk": int(rng.choice(CHUNKS)),
            "pre_len": int(rng.choice(PRE_LENS))}


def _prompt(seed: int, i: int, plen: int, pre_len: int = 0) -> np.ndarray:
    """BOS + shared preamble prefix + private body + QM. Every request
    of one case draws the SAME per-case preamble, so requests whose
    bodies are long enough share a real token prefix — the radix
    prefix-cache hit population (and, truncated at ``plen``, a source of
    partial-page overlaps the page-granular keying must not match)."""
    body_len = plen - 2
    head = np.random.default_rng(seed * 7 + 3).integers(
        0, tok.MOD, size=min(pre_len, body_len))
    body = np.random.default_rng(seed * 1000 + i).integers(
        0, tok.MOD, size=body_len - len(head))
    return np.concatenate([[tok.BOS], head, body, [tok.QM]])


def _worst_pages(method: str, plen: int, max_new: int, n_branch: int) -> int:
    n = 1 if method == "greedy" else n_branch
    full = plen // PAGE_SIZE
    need = -(-(plen + max_new) // PAGE_SIZE)
    return full + n * (need - full)


from allocator_harness import check_invariants as _allocator_invariants  # noqa: E402


def _run_case(setup, case):
    cfg, params, kcfg = setup
    reqs, order, chunk = case["reqs"], case["order"], case["chunk"]
    pre_len = case.get("pre_len", 0)
    prompts = [_prompt(case["seed"], i, plen, pre_len)
               for i, (_, plen, _) in enumerate(reqs)]

    seq = []
    for i, (method, _, max_new) in enumerate(reqs):
        import dataclasses
        kc = dataclasses.replace(kcfg, max_new_tokens=max_new)
        fn = getattr(engine, f"generate_{method}")
        seq.append(fn(params, cfg, kc, prompts[i], jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=MAX_SEQ))

    def serve(sched):
        rids = {}
        for i in order:
            method, _, max_new = reqs[i]
            rids[i] = sched.submit(prompts[i], jax.random.PRNGKey(i),
                                   max_new=max_new, method=method)
        res = sched.run()
        return {i: res[r] for i, r in rids.items()}

    tight = max(_worst_pages(m, p, mn, kcfg.num_branches)
                for m, p, mn in reqs) + 2
    modes = {
        "paged": PagedScheduler(
            params, cfg, kcfg, rows=8, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE, num_pages=8 * MAX_SEQ // PAGE_SIZE,
            method="kappa", eos_id=tok.EOS, bos_id=tok.BOS,
            prefill_chunk=chunk),
        "paged-pressure": PagedScheduler(
            params, cfg, kcfg, rows=8, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE, num_pages=tight, method="kappa",
            eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=chunk),
        "paged-prefix": PagedScheduler(
            params, cfg, kcfg, rows=8, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE, num_pages=8 * MAX_SEQ // PAGE_SIZE,
            method="kappa", eos_id=tok.EOS, bos_id=tok.BOS,
            prefill_chunk=chunk, prefix_cache=True),
        "paged-prefix-pressure": PagedScheduler(
            params, cfg, kcfg, rows=8, max_seq=MAX_SEQ,
            page_size=PAGE_SIZE, num_pages=tight, method="kappa",
            eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=chunk,
            prefix_cache=True),
    }
    for name, sched in modes.items():
        res = serve(sched)
        for i, s in enumerate(seq):
            c = res[i]
            ctx = f"case={case} mode={name} req={i} ({reqs[i]})"
            assert s.tokens == c.tokens, ctx
            assert s.chosen_branch == c.chosen_branch, ctx
            assert s.logical_tokens == c.logical_tokens, ctx
            assert s.steps == c.steps, ctx
        assert sorted(sched.free) == list(range(8)), name
        assert not sched.prefilling and not sched.active, name
        if sched.pcache is not None:
            _allocator_invariants(sched.alloc)   # with live pins
            sched.pcache.drop()                  # tree drop frees pins
        assert sched.alloc.free_count == sched.num_pages, \
            f"{name}: leaked pages"
        assert int(sched.alloc.pinned.sum()) == 0, name
        _allocator_invariants(sched.alloc)


# ------------------------------------------------------------- tier-1

def test_fuzz_equivalence_small(setup):
    """One small fixed case in tier-1: mixed methods, a page-aligned
    prompt, a prompt shorter than the chunk, forced page pressure."""
    case = {"seed": 7,
            "reqs": [("kappa", 8, 10), ("greedy", 3, 6), ("bon", 9, 6)],
            "order": [1, 0, 2], "chunk": 5, "pre_len": 8}
    _run_case(setup, case)


def test_fuzz_equivalence_int8_small(setup):
    """The tier-1 case replayed with a quantized KV cache: all four
    serving modes must stay token-for-token equal to the (also int8)
    sequential reference — COW, preemption replay and the prefix cache
    move quantized pages plus their scale leaves, never re-rounding."""
    import dataclasses
    cfg, params, kcfg = setup
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    case = {"seed": 7,
            "reqs": [("kappa", 8, 10), ("greedy", 3, 6), ("bon", 9, 6)],
            "order": [1, 0, 2], "chunk": 5, "pre_len": 8}
    _run_case((cfg8, params, kcfg), case)


def test_fuzz_equivalence_stbon_aligned(setup):
    """Second fixed tier-1 case: ST-BoN in the mix, prompt length an
    exact multiple of both page size and chunk."""
    case = {"seed": 13,
            "reqs": [("stbon", 16, 10), ("kappa", 5, 6)],
            "order": [0, 1], "chunk": 4, "pre_len": 11}
    _run_case(setup, case)


# --------------------------------------------------------------- chaos

from repro.serving.faults import FaultPlan  # noqa: E402

TERMINAL = {"OK", "CANCELLED", "TIMEOUT", "FAILED", "SHED"}


def _run_chaos_case(setup, case):
    """The lifecycle-hardening twin of :func:`_run_case`: the same
    workload served under seeded fault injection plus random cancels
    and tick budgets, with the prefix cache on and off.
    Every request must reach a terminal status, OK survivors must stay
    token-for-token equal to the sequential reference (the fault-replay
    determinism guarantee), and nothing may leak."""
    cfg, params, kcfg = setup
    reqs, order, chunk = case["reqs"], case["order"], case["chunk"]
    pre_len = case.get("pre_len", 0)
    prompts = [_prompt(case["seed"], i, plen, pre_len)
               for i, (_, plen, _) in enumerate(reqs)]
    cancels = dict(case.get("cancel", {}))   # req index -> cancel tick
    budgets = case.get("ticks", {})          # req index -> max_wall_ticks

    seq = []
    for i, (method, _, max_new) in enumerate(reqs):
        import dataclasses
        kc = dataclasses.replace(kcfg, max_new_tokens=max_new)
        fn = getattr(engine, f"generate_{method}")
        seq.append(fn(params, cfg, kc, prompts[i], jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=MAX_SEQ))

    # a fresh FaultPlan per mode: its memo/fired state is mutable, and
    # the modes' tick counts differ
    def plan():
        return FaultPlan(seed=case["fault_seed"], max_faults=6)

    common = dict(rows=8, max_seq=MAX_SEQ, method="kappa", eos_id=tok.EOS,
                  bos_id=tok.BOS, prefill_chunk=chunk, max_retries=8)
    modes = {
        "paged": lambda: PagedScheduler(
            params, cfg, kcfg, page_size=PAGE_SIZE,
            num_pages=8 * MAX_SEQ // PAGE_SIZE, faults=plan(), **common),
        "paged-prefix": lambda: PagedScheduler(
            params, cfg, kcfg, page_size=PAGE_SIZE,
            num_pages=8 * MAX_SEQ // PAGE_SIZE, prefix_cache=True,
            faults=plan(), **common),
    }
    for name, mk in modes.items():
        sched = mk()
        rids = {}
        for i in order:
            method, _, max_new = reqs[i]
            rids[i] = sched.submit(prompts[i], jax.random.PRNGKey(i),
                                   max_new=max_new, method=method,
                                   max_wall_ticks=budgets.get(i))
        pending = dict(cancels)
        for _ in range(600):                 # bounded: a wedge fails loudly
            if not (sched.queue or sched.active or sched.prefilling):
                break
            for i in [i for i, t in pending.items() if sched.ticks >= t]:
                sched.cancel(rids[i])
                del pending[i]
            sched.tick()
        assert not (sched.queue or sched.active or sched.prefilling), \
            f"{name}: pool did not drain under chaos (case={case})"

        res = {i: sched.results[r] for i, r in rids.items()}
        for i, s in enumerate(seq):
            c = res[i]
            ctx = f"case={case} mode={name} req={i} ({reqs[i]})"
            assert c.status in TERMINAL, ctx
            if c.status == "OK" and i not in cancels and i not in budgets:
                # an undisturbed-or-replayed survivor is token-equal
                assert s.tokens == c.tokens, ctx
                assert s.chosen_branch == c.chosen_branch, ctx
                assert s.logical_tokens == c.logical_tokens, ctx
        # zero-leak: every row, page and pin returned
        assert sorted(sched.free) == list(range(8)), name
        if sched.pcache is not None:
            _allocator_invariants(sched.alloc)
            sched.pcache.drop()
        assert sched.alloc.free_count == sched.num_pages, \
            f"{name}: leaked pages under chaos"
        assert int(sched.alloc.pinned.sum()) == 0, name
        _allocator_invariants(sched.alloc)


def _chaos_case_from_seed(seed: int):
    case = _case_from_seed(seed)
    rng = np.random.default_rng(seed + 5000)
    n = len(case["reqs"])
    case["fault_seed"] = int(rng.integers(0, 100))
    if rng.random() < 0.7:
        case["cancel"] = {int(rng.integers(n)): int(rng.integers(2, 20))}
    if rng.random() < 0.7:
        case["ticks"] = {int(rng.integers(n)): int(rng.integers(4, 25))}
    return case


@pytest.mark.faults
def test_chaos_lifecycle_small(setup):
    """Tier-1 chaos case: faults + one mid-run cancel + one tick budget
    over mixed methods, both serving modes (prefix cache off and on)."""
    case = {"seed": 21, "fault_seed": 5,
            "reqs": [("kappa", 8, 10), ("greedy", 5, 6), ("bon", 9, 6)],
            "order": [2, 0, 1], "chunk": 4, "pre_len": 4,
            "cancel": {1: 6}, "ticks": {2: 15}}
    _run_chaos_case(setup, case)


@pytest.mark.slow
@pytest.mark.fuzz
@pytest.mark.faults
@pytest.mark.parametrize("seed", [5, 17, 29, 41])
def test_chaos_lifecycle_sweep(setup, seed):
    _run_chaos_case(setup, _chaos_case_from_seed(seed))


# --------------------------------------------------------------- sweep

if HAVE_HYPOTHESIS:
    @pytest.mark.slow
    @pytest.mark.fuzz
    @settings(max_examples=8, deadline=None)
    @given(data=st.data())
    def test_fuzz_equivalence_sweep(setup, data):
        n = data.draw(st.integers(2, 4), label="n_requests")
        reqs = [(data.draw(st.sampled_from(METHODS), label=f"method{i}"),
                 data.draw(st.sampled_from(PLENS), label=f"plen{i}"),
                 data.draw(st.sampled_from(MAX_NEWS), label=f"max_new{i}"))
                for i in range(n)]
        order = data.draw(st.permutations(range(n)), label="order")
        case = {"seed": data.draw(st.integers(0, 9999), label="seed"),
                "reqs": reqs, "order": list(order),
                "chunk": data.draw(st.sampled_from(CHUNKS), label="chunk"),
                "pre_len": data.draw(st.sampled_from(PRE_LENS),
                                     label="pre_len")}
        _run_case(setup, case)
else:
    @pytest.mark.slow
    @pytest.mark.fuzz
    @pytest.mark.parametrize("seed", [11, 23, 37, 59])
    def test_fuzz_equivalence_sweep(setup, seed):
        _run_case(setup, _case_from_seed(seed))
