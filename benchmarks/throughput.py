"""Serving throughput: sequential vs continuous-batched vs paged
decoding across methods and queue depths.

Part 1 (sequential vs contiguous, per method): sequential serving
decodes one request at a time — after KAPPA/ST-BoN prune to one
survivor, the device runs a single branch row for the whole EOS tail.
The continuous-batching scheduler backfills freed rows with queued
prefills, so the same hardware row budget serves several requests per
step.

Part 2 (contiguous vs paged at equal KV memory, mixed-length prompts):
the contiguous pool reserves ``max_seq`` slots per row no matter how
short a request is, so its row count is capped at ``budget / max_seq``.
The paged pool spends the *same KV byte budget* as pages sized to each
request's own ``prompt + max_new`` need — with mixed lengths it packs
more concurrent rows into the same memory, and pruning returns pages
the moment it happens. Three modes are timed on identical tokens:

  * ``pr1``   — contiguous pool, PR 1 dispatch pattern (one sampling
                call + one host sync per request per tick);
  * ``cont``  — contiguous pool + this PR's fused one-dispatch-per-tick
                sampler (isolates the batched-sampling win);
  * ``paged`` — paged pool + fused sampler (adds the admission win).

Acceptance: paged ≥ 1.5× the PR 1 contiguous scheduler's aggregate
tokens/s at queue depth ≥ 8.

Every mode decodes the same prompts with the same per-request RNG keys,
so outputs are token-for-token identical (asserted) — the comparison is
pure wall-clock.

Part 3 (high fan-out COW): N=8 branches over multi-page prompts inside
a page budget the pre-PR broadcast allocator could not admit one
request into — prefix sharing (prompt pages aliased across branches),
lazy decode-page allocation and youngest-admitted preemption serve the
whole queue; shared-page savings, peak pages and preemption counts are
emitted, and zero leaked pages is asserted after every paged run.

Part 5 (PR 6 acceptance): a queue of requests sharing one long preamble
(the shared-system-prompt regime) served with the radix prefix cache on
vs off. Later admissions alias the earlier requests' published prompt
pages and skip that part of prefill entirely; the scenario reports the
hit rate and the fraction of queue-wide prefill tokens saved (>= 50%
target) and asserts the cached run is token-for-token identical.

Part 6 (PR 8 acceptance): open-loop Poisson arrival sweeps at offered
rates expressed as multiples of the pool's measured closed-loop
capacity, static vs SLO-adaptive admission (``repro.serving.slo``).
Decode tick wall time is independent of the active count (fixed-shape
pool dispatch), so overload inflates admitted ITL only through the
prompt chunks fused into each tick — the adaptive controller bounds
exactly that by pausing admission into prefill/decode pulses. The
acceptance: at some offered rate where static admission pushes
admitted ITL p99 past 1.5x the unloaded baseline, adaptive admission
holds it within 1.5x; goodput-under-SLO per rate lands in
BENCH_throughput.json. ``--openloop-smoke`` runs a two-rate reduced
sweep on an untrained toy model (curve produced + zero leaks) for CI.

Each scheduler run also reports a per-tick wall-time breakdown by tick
phase (``serving/spans.py``: admission, prefill, page growth, step
dispatch, sampler keys and their transfer, sampler dispatch,
pooled-controller dispatch, blocking sync, per-request host work) so
controller-overhead regressions are visible:
in the ``pr1`` mode every kappa request pays its own controller dispatch
+ host sync inside the advance loop (it shows up as ``host`` time),
while the fused modes run ONE pooled controller dispatch per tick —
asserted here via the scheduler's dispatch/sync counters.
"""
from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tasks
from repro.data import tokenizer as tok
from repro.launch.serve import _strategy_factory
from repro.models import init_cache, init_params
from repro.serving import cache as cache_lib
from repro.serving import engine
from repro.serving import sampler
from repro.serving.scheduler import ContinuousBatchingScheduler, PagedScheduler
from repro.serving.slo import SLOConfig, SLOController

DEPTHS = [1, 4, 8] if common.FULL else [1, 4]
PAGED_DEPTHS = [8, 16]          # acceptance criterion lives at depth >= 8
PAGED_METHODS = ["kappa", "bon"]
PAGED_REPS = 3                  # best-of-R wall clock per mode (CPU noise)
BENCH_METHODS = ["kappa", "stbon", "bon"]
PAGE_SIZE = 16
# per-request decode budgets cycled over the queue — the mixed-length
# regime where need-sized page reservations beat max_seq-sized rows
MIXED_MAX_NEW = [common.MAX_NEW, 10, 16, 24]


def _kcfg(n: int = 5) -> KappaConfig:
    return KappaConfig(num_branches=n, max_new_tokens=common.MAX_NEW,
                       **common.KCFG_KW)


def _prompts(depth: int):
    probs = tasks.make_dataset(1234, depth, **common.DATASET_KW)
    return [np.array(p.prompt) for p in probs]


def _mixed_max_new(depth: int):
    return [MIXED_MAX_NEW[i % len(MIXED_MAX_NEW)] for i in range(depth)]


FANOUT_N = 8                    # high-fan-out COW scenario branches
FANOUT_DEPTH = 6

INTERLEAVE_CHUNK = 32           # prompt tokens per tick while decode runs
INTERLEAVE_LONG = 1536          # long-prompt target length (tokens): the
                                # whole-prompt prefill must dominate a
                                # decode tick for the head-of-line stall
                                # to be real (~10+ ticks at toy scale)
INTERLEAVE_REPS = 3             # best-of-R (CPU wall-clock noise; rep 1
                                # also absorbs jit compiles)

BREAKDOWN_KEYS = ("admit", "prefill", "pages", "step", "keys",
                  "sample", "control", "sync", "host")


def _tick_breakdown_us(tp):
    """Per-tick µs spent in each scheduler tick phase. ``host`` absorbs
    any UNPOOLED per-request controller dispatch + sync (the pr1 mode),
    which is exactly the regression this breakdown makes visible."""
    ticks = max(tp["ticks"], 1)
    return {k: tp[f"time_{k}_s"] * 1e6 / ticks for k in BREAKDOWN_KEYS}


def _run_sequential(cfg, params, kcfg, method, prompts, max_seq):
    factory = _strategy_factory(method, kcfg)
    t0 = time.time()
    gens = [engine._decode_loop(params, cfg, kcfg, p, jax.random.PRNGKey(i),
                                factory(), eos_id=tok.EOS, bos_id=tok.BOS,
                                max_seq=max_seq)
            for i, p in enumerate(prompts)]
    dt = time.time() - t0
    toks = sum(g.logical_tokens for g in gens)
    return gens, toks, dt


def _run_scheduled(cfg, params, kcfg, method, prompts, max_seq, rows, *,
                   paged=False, max_news=None, **sched_kw):
    factory = _strategy_factory(method, kcfg)
    cls = PagedScheduler if paged else ContinuousBatchingScheduler
    sched = cls(params, cfg, kcfg, rows=rows, max_seq=max_seq, method=method,
                eos_id=tok.EOS, bos_id=tok.BOS, strategy_factory=factory,
                **sched_kw)
    max_news = max_news or [None] * len(prompts)
    rids = [sched.submit(p, jax.random.PRNGKey(i), max_new=mn)
            for i, (p, mn) in enumerate(zip(prompts, max_news))]
    res = sched.run()
    tp = sched.throughput()
    if paged:
        # COW/refcount hygiene: every page reference dropped, none leaked
        # (the radix tree's pins are dropped first — tp already captured
        # the live pinned-page count)
        if getattr(sched, "pcache", None) is not None:
            sched.pcache.drop()
        assert sched.alloc.free_count == sched.num_pages, \
            f"leaked {sched.num_pages - sched.alloc.free_count} pages"
        assert int(sched.alloc.pinned.sum()) == 0
    return [res[r] for r in rids], tp


def _long_prompts(depth: int):
    """Multi-page prompts (3 problems concatenated) so prefix sharing has
    full prompt pages to alias."""
    base = _prompts(3 * depth)
    return [np.concatenate([base[3 * i]]
                           + [b[1:] for b in base[3 * i + 1: 3 * i + 3]])
            for i in range(depth)]


def _fanout_scenario(cfg, params):
    """High-fan-out COW scenario: N=8 branches over long prompts inside a
    page budget the pre-PR broadcast allocator could not even admit ONE
    request into (it reserved N x ceil((prompt+max_new)/page_size) pages
    up front). Prefix sharing + lazy allocation serve the whole queue in
    that budget; preemptions (youngest-admitted eviction on page
    exhaustion) are part of the deal and are reported."""
    kcfg = _kcfg(FANOUT_N)
    prompts = _long_prompts(FANOUT_DEPTH)
    max_seq = max(len(p) for p in prompts) + kcfg.max_new_tokens
    max_seq = -(-max_seq // PAGE_SIZE) * PAGE_SIZE
    need_pages = [-(-(len(p) + kcfg.max_new_tokens) // PAGE_SIZE)
                  for p in prompts]
    full_pages = [len(p) // PAGE_SIZE for p in prompts]
    broadcast_worst = max(FANOUT_N * n for n in need_pages)
    shared_worst = max(f + FANOUT_N * (n - f)
                       for f, n in zip(full_pages, need_pages))
    num_pages = shared_worst + 4
    assert broadcast_worst > num_pages, \
        "budget no longer breaks the broadcast allocator - shrink it"
    sched = PagedScheduler(params, cfg, kcfg, rows=2 * FANOUT_N,
                           max_seq=max_seq, page_size=PAGE_SIZE,
                           num_pages=num_pages, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    res = sched.run()
    assert set(res) == set(rids)
    tp = sched.throughput()
    assert sched.alloc.free_count == num_pages, \
        f"leaked {num_pages - sched.alloc.free_count} pages"
    assert tp["page_peak"] <= num_pages
    return [{
        "kind": "fanout", "method": "kappa", "fan_out": FANOUT_N,
        "depth": FANOUT_DEPTH, "page_size": PAGE_SIZE,
        "num_pages": num_pages,
        "broadcast_worst_pages_per_req": broadcast_worst,
        "shared_worst_pages_per_req": shared_worst,
        "page_peak": tp["page_peak"],
        "shared_page_savings": 1.0 - shared_worst / broadcast_worst,
        "preemptions": tp["preemptions"],
        "tokens_per_s": tp["tokens_per_s"],
        "page_utilization": tp["page_utilization"],
        "ticks": tp["ticks"], "time_s": tp["time_s"],
    }]


INT8_PARITY_PROBLEMS = 12       # answer-parity sweep size (per method)
INT8_DEPTH = 10                 # deeper queue: the int8 pool's peak
                                # concurrency must not be capped by
                                # running out of queued requests


def _int8_capacity_scenario(cfg, params):
    """Part 7 (int8 paged KV acceptance): ONE fixed HBM page budget,
    served twice — model-dtype pages vs int8 pages + scale leaves. The
    int8 pool cuts the same bytes into >= 1.8x the pages (page_bytes
    shrinks from hd*itemsize to hd+4 per token-head), so the N=8 fan-out
    queue reaches >= 1.8x the peak concurrent admitted requests. A
    BoN/KAPPA sweep over the synthetic tasks then checks answer
    accuracy parity against fp serving — quantization must buy capacity,
    not trade away correctness."""
    import dataclasses
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    kcfg = _kcfg(FANOUT_N)
    prompts = _long_prompts(INT8_DEPTH)
    max_seq = max(len(p) for p in prompts) + kcfg.max_new_tokens
    max_seq = -(-max_seq // PAGE_SIZE) * PAGE_SIZE
    need = [-(-(len(p) + kcfg.max_new_tokens) // PAGE_SIZE) for p in prompts]
    full = [len(p) // PAGE_SIZE for p in prompts]
    shared_worst = max(f + FANOUT_N * (n - f) for f, n in zip(full, need))
    # budget sized so the model-dtype pool serves the queue ~serially
    budget = (shared_worst + 4) * cache_lib.page_bytes(cfg, PAGE_SIZE)

    def serve(c):
        sched = PagedScheduler(params, c, kcfg, rows=FANOUT_N * INT8_DEPTH,
                               max_seq=max_seq, page_size=PAGE_SIZE,
                               page_budget_bytes=budget, method="kappa",
                               eos_id=tok.EOS, bos_id=tok.BOS)
        rids = [sched.submit(p, jax.random.PRNGKey(i))
                for i, p in enumerate(prompts)]
        peak, t0 = 0, time.perf_counter()
        while sched.queue or sched.active or sched.prefilling:
            sched.tick()
            peak = max(peak, len(sched.active))
        sched.elapsed = time.perf_counter() - t0   # run() normally sets it
        assert set(sched.results) == set(rids)
        assert sched.alloc.free_count == sched.num_pages, \
            f"leaked {sched.num_pages - sched.alloc.free_count} pages"
        return sched, peak, sched.throughput()

    s_fp, peak_fp, tp_fp = serve(cfg)
    s_i8, peak_i8, tp_i8 = serve(cfg8)
    assert s_i8.num_pages >= int(1.8 * s_fp.num_pages), \
        f"int8 page capacity only {s_i8.num_pages}/{s_fp.num_pages}"
    want_peak = min(INT8_DEPTH, int(np.ceil(1.8 * peak_fp)))
    assert peak_i8 >= want_peak, \
        f"int8 admitted {peak_i8} concurrent vs {peak_fp} fp " \
        f"(>= {want_peak} wanted)"

    # answer parity: same problems, same keys, fp sequential vs int8
    # paged serving, both BoN and KAPPA
    probs = tasks.make_dataset(4321, INT8_PARITY_PROBLEMS,
                               **common.DATASET_KW)
    sp = [np.array(p.prompt) for p in probs]
    kc = _kcfg()
    ms = -(-(max(len(p) for p in sp) + kc.max_new_tokens)
           // PAGE_SIZE) * PAGE_SIZE
    rows_par = 2 * kc.num_branches
    acc = {}
    for method in ("kappa", "bon"):
        fn = getattr(engine, f"generate_{method}")
        gens_fp = [fn(params, cfg, kc, p, jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=ms)
                   for i, p in enumerate(sp)]
        gens_i8, _ = _run_scheduled(
            cfg8, params, kc, method, sp, ms, rows_par, paged=True,
            page_size=PAGE_SIZE,
            num_pages=rows_par * ms // PAGE_SIZE)
        for label, gens in (("fp", gens_fp), ("int8", gens_i8)):
            acc[f"{method}_{label}"] = float(np.mean(
                [tasks.check_answer(g.tokens, pr)
                 for g, pr in zip(gens, probs)]))
    parity_tol = 2.0 / INT8_PARITY_PROBLEMS
    parity_ok = all(abs(acc[f"{m}_fp"] - acc[f"{m}_int8"]) <= parity_tol
                    for m in ("kappa", "bon"))
    assert parity_ok, f"int8 answer accuracy drifted: {acc}"
    return [{
        "kind": "int8", "fan_out": FANOUT_N, "depth": INT8_DEPTH,
        "page_size": PAGE_SIZE, "page_budget_bytes": budget,
        "num_pages_fp": s_fp.num_pages, "num_pages_int8": s_i8.num_pages,
        "peak_concurrent_fp": peak_fp, "peak_concurrent_int8": peak_i8,
        "admit_ratio": peak_i8 / max(peak_fp, 1),
        "page_ratio": s_i8.num_pages / max(s_fp.num_pages, 1),
        "parity_ok": parity_ok, "parity_problems": INT8_PARITY_PROBLEMS,
        "fp_tokens_per_s": tp_fp["tokens_per_s"],
        "int8_tokens_per_s": tp_i8["tokens_per_s"],
        "int8_preemptions": tp_i8["preemptions"],
        "fp_preemptions": tp_fp["preemptions"],
        "int8_ticks": tp_i8["ticks"], "int8_time_s": tp_i8["time_s"],
        "fp_ticks": tp_fp["ticks"], "fp_time_s": tp_fp["time_s"],
        **{f"acc_{k}": v for k, v in acc.items()},
    }]


PREFIX_DEPTH = 8                # requests sharing the preamble
PREFIX_PREAMBLE = 320           # shared-preamble target length (tokens):
                                # 20 full pages every later request aliases
PREFIX_CHUNK = 32               # chunked prefill (required for resuming
                                # at the cached extent)


def _prefix_scenario(cfg, params):
    """Part 5 (PR 6 acceptance): PREFIX_DEPTH requests share one long
    preamble and differ only in a short tail. With the radix prefix
    cache on, every admission after the first completions aliases the
    published preamble pages and prefills only its tail; with it off,
    every request re-prefills the whole preamble. Both runs must be
    token-for-token identical (the cache is a pure prefill shortcut)."""
    kcfg = _kcfg()
    base = _prompts(PREFIX_DEPTH + 40)
    pieces = [base[PREFIX_DEPTH][:-1]]       # BOS + body, no QM
    total, i = len(pieces[0]), PREFIX_DEPTH + 1
    while total < PREFIX_PREAMBLE:
        pieces.append(base[i][1:-1])         # strip BOS/QM, keep body
        total += len(base[i]) - 2
        i += 1
    preamble = np.concatenate(pieces)
    prompts = [np.concatenate([preamble, base[j][1:]])
               for j in range(PREFIX_DEPTH)]
    max_seq = max(len(p) for p in prompts) + kcfg.max_new_tokens
    max_seq = -(-max_seq // PAGE_SIZE) * PAGE_SIZE
    # one fan-out of rows: requests drain the queue one at a time, so
    # every request after the first finds the preamble already published
    # (concurrent-admission hit/miss races are exercised in the fuzz
    # equivalence suite; this scenario measures steady-state reuse)
    rows = kcfg.num_branches
    num_pages = 2 * rows * max_seq // PAGE_SIZE

    def run_once(pc):
        gens, tp = _run_scheduled(
            cfg, params, kcfg, "kappa", prompts, max_seq, rows,
            paged=True, page_size=PAGE_SIZE, num_pages=num_pages,
            prefill_chunk=PREFIX_CHUNK, prefix_cache=pc)
        return gens, tp

    run_once(True)                           # warm the chunked shapes
    run_once(False)
    gens_off, tp_off = run_once(False)
    gens_on, tp_on = run_once(True)
    assert all(a.tokens == b.tokens for a, b in zip(gens_off, gens_on)), \
        "prefix-cached serving diverged from the uncached run"
    prompt_tokens = sum(len(p) for p in prompts)
    looked = tp_on["prefix_hits"] + tp_on["prefix_misses"]
    return [{
        "kind": "prefix", "method": "kappa", "depth": PREFIX_DEPTH,
        "preamble_len": int(len(preamble)), "page_size": PAGE_SIZE,
        "prefill_chunk": PREFIX_CHUNK, "prompt_tokens": prompt_tokens,
        "prefix_hits": tp_on["prefix_hits"],
        "prefix_hit_rate": tp_on["prefix_hits"] / max(looked, 1),
        "prefix_tokens_saved": tp_on["prefix_tokens_saved"],
        "prefill_tokens_saved_frac": tp_on["prefix_tokens_saved"]
        / max(prompt_tokens, 1),
        "prefix_evictions": tp_on["prefix_evictions"],
        "prefix_pinned_pages": tp_on["prefix_pinned_pages"],
        "cached_tokens_per_s": tp_on["tokens_per_s"],
        "uncached_tokens_per_s": tp_off["tokens_per_s"],
        "cached_vs_uncached": tp_on["tokens_per_s"]
        / max(tp_off["tokens_per_s"], 1e-9),
        "ticks": tp_on["ticks"], "time_s": tp_on["time_s"],
    }]


def _interleave_scenario(cfg, params):
    """Part 4 (PR 5 acceptance): admit one LONG-prompt request while
    >= 2 short requests are decoding. With one-shot admission the whole
    prompt prefill lands inside a single tick — every in-flight request
    stalls for it (a multi-tick-sized ITL spike). With chunked prefill
    the admission advances ``INTERLEAVE_CHUNK`` tokens per tick inside
    the decode tick, so in-flight ITL stays within ~1.2x of a
    no-admission baseline and the long request's TTFT is reported.
    Token streams are asserted identical between the two admission
    modes (the final chunk's logits are bitwise-equal to the one-shot
    prefill)."""
    kcfg = _kcfg()
    shorts = _prompts(3)
    base = _prompts(160)
    pieces, total = [base[0]], len(base[0])
    for p in base[1:]:
        if total >= INTERLEAVE_LONG:
            break
        pieces.append(p[1:])
        total += len(p) - 1
    long_p = np.concatenate(pieces)
    max_seq = -(-(len(long_p) + common.MAX_NEW) // PAGE_SIZE) * PAGE_SIZE
    num_pages = 8 * max_seq // PAGE_SIZE

    def run_once(chunk, admit_long):
        sched = PagedScheduler(params, cfg, kcfg, rows=8, max_seq=max_seq,
                               page_size=PAGE_SIZE, num_pages=num_pages,
                               method="greedy", eos_id=tok.EOS,
                               bos_id=tok.BOS, prefill_chunk=chunk)
        rids = [sched.submit(p, jax.random.PRNGKey(i),
                             max_new=common.MAX_NEW, method="greedy")
                for i, p in enumerate(shorts)]
        for _ in range(200):        # warm: all shorts decoding steadily
            sched.tick()
            if all(r in sched.active and sched.active[r][0].step >= 4
                   for r in rids):
                break
        t_admit = time.perf_counter()
        rl = None
        if admit_long:
            rl = sched.submit(long_p, jax.random.PRNGKey(99), max_new=16,
                              method="greedy")
            # the admission window: ticks while the long prompt's
            # prefill is in flight — where one-shot admission stalls
            # every in-flight request for the whole prompt
            while rl not in sched.active and rl not in sched.results:
                sched.tick()
        else:
            # baseline window: plain decode ticks, sized like the
            # chunked admission window so p99 sees comparable samples
            for _ in range(-(-INTERLEAVE_LONG // INTERLEAVE_CHUNK)):
                sched.tick()
        t_end = time.perf_counter()
        sched.run()
        assert sched.alloc.free_count == sched.num_pages
        itl = np.asarray([t1 - t0 for r in rids
                          for t0, t1 in zip(sched.token_times[r],
                                            sched.token_times[r][1:])
                          if t_admit < t1 <= t_end] or [0.0])
        return {
            "itl_p50_s": float(np.percentile(itl, 50)),
            "itl_p99_s": float(np.percentile(itl, 99)),
            "itl_max_s": float(itl.max()),
            "ttft_long_s": sched.ttft.get(rl),
            "tokens": {r: sched.results[r].tokens for r in rids
                       + ([rl] if rl is not None else [])},
        }

    # interleaved best-of-R (machine speed phases hit every mode; rep 1
    # additionally absorbs the jit compiles of each mode's shapes)
    runs = {"base": [], "oneshot": [], "chunked": []}
    for _ in range(INTERLEAVE_REPS):
        runs["base"].append(run_once(INTERLEAVE_CHUNK, admit_long=False))
        runs["oneshot"].append(run_once(None, admit_long=True))
        runs["chunked"].append(run_once(INTERLEAVE_CHUNK, admit_long=True))
    base = min(runs["base"], key=lambda r: r["itl_p99_s"])
    oneshot = min(runs["oneshot"], key=lambda r: r["itl_p99_s"])
    chunked = min(runs["chunked"], key=lambda r: r["itl_p99_s"])
    assert oneshot["tokens"] == chunked["tokens"], \
        "chunked admission diverged from one-shot serving"
    return [{
        "kind": "interleave", "method": "greedy",
        "in_flight": len(shorts), "long_prompt_len": len(long_p),
        "prefill_chunk": INTERLEAVE_CHUNK, "page_size": PAGE_SIZE,
        "baseline_itl_p99_s": base["itl_p99_s"],
        "oneshot_itl_p99_s": oneshot["itl_p99_s"],
        "chunked_itl_p99_s": chunked["itl_p99_s"],
        "oneshot_itl_max_s": oneshot["itl_max_s"],
        "chunked_itl_max_s": chunked["itl_max_s"],
        "oneshot_ttft_long_s": oneshot["ttft_long_s"],
        "chunked_ttft_long_s": chunked["ttft_long_s"],
        "chunked_vs_baseline_itl_p99": chunked["itl_p99_s"]
        / max(base["itl_p99_s"], 1e-9),
        "oneshot_vs_baseline_itl_p99": oneshot["itl_p99_s"]
        / max(base["itl_p99_s"], 1e-9),
    }]


OVERLOAD_DEPTH = 6              # unloaded load: drains with minimal queuing
OVERLOAD_BURST = 2 * OVERLOAD_DEPTH  # the open-loop 2x burst
OVERLOAD_QUEUE = 8              # bounded admission queue during the burst
OVERLOAD_REPS = 2               # best-of-R for the ITL percentiles


def _itl_p99_s(sched, rids):
    itl = [t1 - t0 for r in rids
           for t0, t1 in zip(sched.token_times.get(r, []),
                             sched.token_times.get(r, [])[1:])]
    return float(np.percentile(np.asarray(itl or [0.0]), 99))


def _overload_scenario(cfg, params):
    """Graceful overload degradation (DESIGN.md §8): an open-loop burst
    at 2x the unloaded depth, served under a bounded admission queue and
    per-request tick budgets (the deterministic twin of wall-clock
    deadlines). The contract: excess load is SHED at the door, requests
    that cannot finish inside their budget TIMEOUT with partial tokens,
    and the requests that ARE admitted keep decoding at unloaded speed —
    admitted-ITL p99 within 1.5x of the unloaded baseline. Reported:
    shed rate, deadline-miss rate, goodput (OK logical tokens/s)."""
    kcfg = _kcfg()
    # one fan-out of rows: the pool is genuinely saturated (requests
    # admit one at a time, pruning backfills), so a 2x burst is real
    # overload rather than slack absorption
    rows = kcfg.num_branches
    prompts = _prompts(OVERLOAD_BURST)
    max_seq = max(len(p) for p in prompts) + kcfg.max_new_tokens

    def run_once(n_req, *, max_queue=None, ticks=None):
        sched = ContinuousBatchingScheduler(
            params, cfg, kcfg, rows=rows, max_seq=max_seq, method="kappa",
            eos_id=tok.EOS, bos_id=tok.BOS,
            strategy_factory=_strategy_factory("kappa", kcfg),
            max_queue=max_queue)
        rids = [sched.submit(prompts[i], jax.random.PRNGKey(i),
                             max_wall_ticks=ticks) for i in range(n_req)]
        res = sched.run()
        return sched, rids, res

    sched_w, _, _ = run_once(OVERLOAD_DEPTH)  # absorb jit compiles
    # all requests are submitted at tick 0, so the tick budget is an
    # absolute completion deadline. Keyed to the measured unloaded drain
    # (not max_new — how long requests actually run depends on how early
    # the model EOSes): the unloaded load fits with 10% slack; the 2x
    # burst admits ~8/6 the work through a saturated pool, so its tail
    # cannot
    budget = int(1.1 * sched_w.ticks)
    base_itl, over = None, None
    for _ in range(OVERLOAD_REPS):           # interleaved best-of-R
        sched_u, rids_u, _ = run_once(OVERLOAD_DEPTH)
        itl_u = _itl_p99_s(sched_u, rids_u)
        base_itl = itl_u if base_itl is None else min(base_itl, itl_u)
        sched_o, rids_o, res = run_once(OVERLOAD_BURST,
                                        max_queue=OVERLOAD_QUEUE,
                                        ticks=budget)
        ok = [r for r in rids_o if res[r].status == "OK"]
        itl_o = _itl_p99_s(sched_o, ok)
        if over is None or itl_o < over["itl"]:
            over = {"sched": sched_o, "rids": rids_o, "res": res,
                    "ok": ok, "itl": itl_o}
    sched_o, rids_o, res, ok = (over["sched"], over["rids"], over["res"],
                                over["ok"])
    statuses = [res[r].status for r in rids_o]
    # the burst must actually exercise all three outcomes — degrade,
    # don't collapse: some served, some shed at the door, some truncated
    assert ok, f"overload starved every request: {statuses}"
    assert "SHED" in statuses, "burst never hit the queue bound"
    assert "TIMEOUT" in statuses, "tick budget never fired — raise burst"
    # timed-out requests keep their partial decode (truncate-and-return)
    assert all(res[r].steps > 0 for r in rids_o
               if res[r].status == "TIMEOUT" and r in sched_o.token_times)
    goodput = sum(res[r].logical_tokens for r in ok) \
        / max(sched_o.elapsed, 1e-9)
    return [{
        "kind": "overload", "method": "kappa", "rows": rows,
        "depth": OVERLOAD_DEPTH, "burst": OVERLOAD_BURST,
        "max_queue": OVERLOAD_QUEUE, "tick_budget": budget,
        "served_ok": len(ok),
        "shed_rate": statuses.count("SHED") / len(rids_o),
        "deadline_miss_rate": statuses.count("TIMEOUT") / len(rids_o),
        "goodput_tokens_per_s": goodput,
        "baseline_itl_p99_s": base_itl,
        "overload_itl_p99_s": over["itl"],
        "overload_vs_baseline_itl_p99": over["itl"] / max(base_itl, 1e-9),
        "ticks": sched_o.ticks, "time_s": sched_o.elapsed,
    }]


OPENLOOP_ROWS = 8               # greedy pool rows (fixed dispatch shape)
OPENLOOP_CHUNK = 256            # prompt tokens fused into a tick per admit:
                                # big enough that chunk COMPUTE (not just
                                # dispatch overhead) is what a concurrent
                                # admission costs the in-flight decoders
OPENLOOP_QUEUE = 12             # bounded admission queue (static's only gate)
OPENLOOP_PROMPT = 256           # uniform prompt length == ONE chunk. The
                                # fused tick dispatch is keyed on each
                                # chunk's block-table extent (grows with
                                # chunk index), so multi-chunk prompts make
                                # the jit key the multiset of in-flight
                                # chunk indices — unwarmable. One chunk per
                                # prompt collapses the key to HOW MANY
                                # admissions ride the tick: rows-1 shapes,
                                # warmed exactly below
OPENLOOP_MAX_NEWS = [10, 10, 10, 28]  # cycled per request: trios of
                                # equal-length requests complete (and
                                # free rows) together, so under backlog
                                # a static gate re-admits ~3 at once —
                                # the burst whose fused chunks inflate
                                # the long-running requests' ITL; the
                                # 28s keep decoders in flight to witness
                                # it
OPENLOOP_REQS = 32              # enough ITL samples (~600 gaps) that a
                                # p99 is a population, not one outlier
OPENLOOP_RATES_X = [0.25, 1.0, 2.5]  # offered rate / measured capacity:
                                # clean unloaded anchor (arrivals rarely
                                # collide), saturation, sustained
                                # overload
OPENLOOP_SMOKE_RATES_X = [0.25, 2.5]
OPENLOOP_SLO_MARGIN = 1.35      # controller target = margin x unloaded
                                # p99. Must clear the cost of ONE paced
                                # admission tick (the unloaded p99 IS
                                # that tick), else every window that
                                # admits anything reads violated and the
                                # controller oscillates into pause
OPENLOOP_SLO_BOUND = 1.5        # acceptance bound (matches overload gate)
OPENLOOP_WINDOW = 8             # controller window (ticks) — reacts well
                                # inside one admission's prefill


def _openloop_prompts(n_req: int):
    """``n_req`` concatenated prompts of exactly OPENLOOP_PROMPT tokens
    each (distinct content, uniform length — see the shape note on
    OPENLOOP_PROMPT)."""
    base = _prompts(32 * n_req)
    prompts, i = [], 0
    for _ in range(n_req):
        pieces, total = [base[i]], len(base[i])
        i += 1
        while total < OPENLOOP_PROMPT:
            assert i < len(base), "ran out of prompt pieces"
            pieces.append(base[i][1:])       # strip BOS, keep body + QM
            total += len(base[i]) - 1
            i += 1
        flat = np.concatenate(pieces)[:OPENLOOP_PROMPT].copy()
        flat[-1] = tok.QM
        prompts.append(flat)
    return prompts


def _poisson_arrivals(rate_rps: float, n: int, seed: int):
    """Cumulative open-loop arrival times. Seeded: every rate reuses the
    same exponential draws, so sweeps differ only by the 1/rate scale."""
    rng = np.random.default_rng(seed)
    return np.cumsum(rng.exponential(1.0 / rate_rps, size=n))


def _drive_open_loop(sched, prompts, arrivals, max_news, ctl=None):
    """Open-loop serving: submit each request at its wall-clock arrival
    time regardless of pool state (arrivals do not wait for capacity —
    the definition of offered load), tick while anything is in flight,
    let the controller evaluate after every tick. Stamps
    ``sched.elapsed`` like ``run()`` does."""
    rids = [None] * len(prompts)
    # a GC pause inside a measured tick reads as a phantom ITL spike at
    # p99 — collect up front, disable during the run
    gc.collect()
    gc.disable()
    try:
        t0 = sched.clock()
        nxt = 0
        while nxt < len(prompts) or sched.has_work:
            now = sched.clock() - t0
            while nxt < len(prompts) and arrivals[nxt] <= now:
                rids[nxt] = sched.submit(prompts[nxt],
                                         jax.random.PRNGKey(nxt),
                                         max_new=max_news[nxt])
                nxt += 1
            if sched.has_work:
                sched.tick()
                if ctl is not None:
                    ctl.on_tick()
            else:
                time.sleep(min(max(arrivals[nxt] - now, 0.0), 0.002))
        sched.elapsed = sched.clock() - t0
    finally:
        gc.enable()
    return rids


def _openloop_scenario(cfg, params, smoke=False):
    """Part 6: offered-rate sweep, static vs SLO-adaptive admission.

    Capacity is calibrated from a warm closed-loop drain of the same
    prompts (which also absorbs every jit shape the sweep touches); the
    lowest-rate static run defines the unloaded admitted-ITL p99 that
    anchors both the controller's target and the acceptance bound.
    Every run asserts zero leaked pages/pins after drain."""
    kcfg = KappaConfig(num_branches=4,
                       max_new_tokens=max(OPENLOOP_MAX_NEWS),
                       **common.KCFG_KW)
    n_req = 8 if smoke else OPENLOOP_REQS
    rates_x = OPENLOOP_SMOKE_RATES_X if smoke else OPENLOOP_RATES_X
    prompts = _openloop_prompts(n_req)
    max_news = [OPENLOOP_MAX_NEWS[i % len(OPENLOOP_MAX_NEWS)]
                for i in range(n_req)]
    max_seq = OPENLOOP_PROMPT + max(OPENLOOP_MAX_NEWS)
    max_seq = -(-max_seq // PAGE_SIZE) * PAGE_SIZE
    num_pages = OPENLOOP_ROWS * max_seq // PAGE_SIZE

    def mk(max_queue=OPENLOOP_QUEUE):
        return PagedScheduler(params, cfg, kcfg, rows=OPENLOOP_ROWS,
                              max_seq=max_seq, page_size=PAGE_SIZE,
                              num_pages=num_pages, method="greedy",
                              eos_id=tok.EOS, bos_id=tok.BOS,
                              prefill_chunk=OPENLOOP_CHUNK,
                              max_queue=max_queue)

    # deterministic jit warm-up. The fused tick dispatch is keyed on how
    # many prompt chunks ride it, so warm every k the sweep can hit
    # (k prefilling + at least one decoding, bounded by the row pool):
    # admit one request to decode, then admit k more at once so their
    # chunks fuse into its ticks. A compile landing inside a measured
    # run would masquerade as a multi-second ITL spike.
    for k in range(1, OPENLOOP_ROWS):
        sched = mk(max_queue=None)
        sched.submit(prompts[0], jax.random.PRNGKey(0),
                     max_new=max(OPENLOOP_MAX_NEWS))
        for _ in range(OPENLOOP_PROMPT // OPENLOOP_CHUNK + 1):
            sched.tick()                     # request 0 reaches decode
        for j in range(1, k + 1):
            sched.submit(prompts[j % n_req], jax.random.PRNGKey(j),
                         max_new=min(OPENLOOP_MAX_NEWS))
        sched.run()
    # closed-loop drain (unbounded queue, whole batch at tick 0) on the
    # warmed shapes: the capacity estimate offered rates are scaled by
    sched_w = mk(max_queue=None)
    for i, p in enumerate(prompts):
        sched_w.submit(p, jax.random.PRNGKey(i), max_new=max_news[i])
    res_w = sched_w.run()
    assert all(r.status == "OK" for r in res_w.values())
    capacity_rps = len(prompts) / max(sched_w.elapsed, 1e-9)

    def run_rate(rate_rps, *, target_itl=None):
        sched = mk()
        ctl = None
        if target_itl is not None:
            # min_prefill_chunk pins the chunk knob: halving it mid-run
            # would introduce unwarmed fused-dispatch shapes whose
            # compiles dwarf the knob's benefit at toy scale — the
            # admission pacing budget (level 1), pause (level 2) and
            # shed (level 3) are the levers under test. start_level=1:
            # admission begins paced (one chunk of new prompt per tick)
            # and healthy windows relax it — reacting only AFTER a
            # violated window would serve the first burst at full blast
            ctl = SLOController(sched, SLOConfig(
                target_itl_p99_s=target_itl,
                window_ticks=OPENLOOP_WINDOW, min_itl_samples=4,
                min_prefill_chunk=OPENLOOP_CHUNK, start_level=1))
        arrivals = _poisson_arrivals(rate_rps, n_req, seed=4242)
        rids = _drive_open_loop(sched, prompts, arrivals, max_news, ctl)
        res = sched.results
        ok = [r for r in rids if res[r].status == "OK"]
        elapsed = max(sched.elapsed, 1e-9)
        stat = {
            "offered_rps": rate_rps,
            "ok": len(ok),
            "shed": sum(res[r].status == "SHED" for r in rids),
            "attained_ok_rps": len(ok) / elapsed,
            "goodput_tokens_per_s": sum(res[r].logical_tokens
                                        for r in ok) / elapsed,
            "admitted_itl_p99_s": _itl_p99_s(sched, ok),
            "elapsed_s": sched.elapsed,
            "ticks": sched.ticks,
        }
        if ctl is not None:
            stat["controller_max_level"] = max(
                (h["level"] for h in ctl.history), default=0)
            stat["controller_windows"] = len(ctl.history)
        assert sched.alloc.free_count == sched.num_pages, "leaked pages"
        assert int(sched.alloc.pinned.sum()) == 0, "leaked pins"
        return stat

    unloaded = run_rate(rates_x[0] * capacity_rps)
    unloaded_itl = max(unloaded["admitted_itl_p99_s"], 1e-9)
    target_itl = OPENLOOP_SLO_MARGIN * unloaded_itl
    slo_itl = OPENLOOP_SLO_BOUND * unloaded_itl
    out = []
    for rx in rates_x:
        rate = rx * capacity_rps
        static = unloaded if rx == rates_x[0] else run_rate(rate)
        adaptive = run_rate(rate, target_itl=target_itl)
        for stat in (static, adaptive):
            stat["meets_slo"] = stat["admitted_itl_p99_s"] <= slo_itl
            stat["goodput_under_slo_tokens_per_s"] = \
                stat["goodput_tokens_per_s"] if stat["meets_slo"] else 0.0
        out.append({
            "kind": "openloop", "method": "greedy", "rows": OPENLOOP_ROWS,
            "n_requests": n_req, "prompt_len": max(len(p) for p in prompts),
            "prefill_chunk": OPENLOOP_CHUNK, "max_queue": OPENLOOP_QUEUE,
            "page_size": PAGE_SIZE,
            "capacity_rps": capacity_rps, "rate_x_capacity": rx,
            "offered_rps": rate,
            "unloaded_itl_p99_s": unloaded_itl,
            "slo_itl_p99_s": slo_itl,
            "controller_target_itl_p99_s": target_itl,
            "static": static, "adaptive": adaptive,
            "static_itl_vs_unloaded": static["admitted_itl_p99_s"]
            / unloaded_itl,
            "adaptive_itl_vs_unloaded": adaptive["admitted_itl_p99_s"]
            / unloaded_itl,
        })
    return out


def run(cfg, params):
    kcfg = _kcfg()
    fan_out = kcfg.num_branches
    rows_pool = 2 * fan_out
    out = []
    # warm the jit caches so the timed comparison measures steady-state
    # serving, not compiles: prefill is keyed on prompt length (warm every
    # distinct length — the sequential pass runs first and would otherwise
    # absorb those compiles), decode on batch shape (one request walks the
    # whole bucket chain; one scheduler run compiles the pool shapes)
    warm = _prompts(max(DEPTHS + PAGED_DEPTHS))
    max_seq = max(len(p) for p in warm) + kcfg.max_new_tokens
    for p in warm:
        engine._prefill_one(params, cfg, p, max_seq)
        # admission prefills now run through PROMPT-sized transient
        # caches (PR 5 sizing fix), so warm those shapes too — one per
        # distinct prompt length per backend rounding
        engine._prefill_one(params, cfg, p, len(p))
        engine._prefill_one(params, cfg, p,
                            -(-len(p) // PAGE_SIZE) * PAGE_SIZE)

    def warm_decode_shapes(ms):
        # BoN's eager EOS-row release means the sequential engine can hit
        # ANY survivor batch size 1..fan_out; compile every decode + row-
        # sampling shape up front so none lands inside a timed region
        for n in range(1, fan_out + 1):
            cache = init_cache(cfg, n, ms)
            engine._model_step(params, cfg, jnp.zeros((n,), jnp.int32),
                               jnp.int32(4), cache)
            sampler.sample_rows(
                jnp.zeros((n, 2), jnp.uint32),
                jnp.zeros((n, cfg.vocab_size), jnp.float32),
                jnp.zeros((n,), bool), kcfg, want_picked_lp=True)
            sampler.sample_rows(
                jnp.zeros((n, 2), jnp.uint32),
                jnp.zeros((n, cfg.vocab_size), jnp.float32),
                jnp.zeros((n,), bool), kcfg)
            sampler.picked_logprob(
                jnp.zeros((n, cfg.vocab_size), jnp.float32),
                jnp.zeros((n,), jnp.int32))

    warm_decode_shapes(max_seq)
    for method in BENCH_METHODS:
        _run_sequential(cfg, params, kcfg, method, warm[:1], max_seq)
        # full warm list: the install scatter is keyed on the transient
        # cache's (prompt-sized) shape, one specialization per length
        _run_scheduled(cfg, params, kcfg, method, warm, max_seq, rows_pool)

    for method in BENCH_METHODS:
        for depth in DEPTHS:
            prompts = _prompts(depth)
            gens_s, toks_s, dt_s = _run_sequential(
                cfg, params, kcfg, method, prompts, max_seq)
            gens_c, tp = _run_scheduled(
                cfg, params, kcfg, method, prompts, max_seq, rows_pool)
            assert all(a.tokens == b.tokens for a, b in zip(gens_s, gens_c)), \
                f"{method}: scheduler diverged from sequential serving"
            seq_tps = toks_s / max(dt_s, 1e-9)
            out.append({
                "kind": "continuous", "method": method, "depth": depth,
                "rows": rows_pool,
                "seq_tokens_per_s": seq_tps,
                "cb_tokens_per_s": tp["tokens_per_s"],
                "speedup": tp["tokens_per_s"] / max(seq_tps, 1e-9),
                "row_utilization": tp["row_utilization"],
                "ticks": tp["ticks"],
                "seq_time_s": dt_s, "cb_time_s": tp["time_s"],
                "tick_breakdown_us": _tick_breakdown_us(tp),
            })

    # ---- contiguous vs paged at equal KV token budget, mixed lengths.
    # Contiguous: rows_pool rows × max_seq slots each. Paged: the same
    # slot budget cut into pages, spread over more row slots — admission
    # is bounded by pages actually needed, not worst-case rows.
    max_seq_p = -(-max_seq // PAGE_SIZE) * PAGE_SIZE
    num_pages = rows_pool * max_seq_p // PAGE_SIZE
    # 3× fan-out row slots: enough to hold every fan-out the page budget
    # can admit (pages bind first) without paying for a wider model step
    rows_paged = 3 * fan_out
    # warm every shape the comparison touches: prefill at the padded
    # max_seq, each pool's decode shape, and — because the KAPPA
    # controller jit is keyed on the whole kcfg — every mixed max_new
    # variant, in every mode (the PR 1 run goes first and would
    # otherwise absorb those compiles into its timing)
    for p in warm:
        engine._prefill_one(params, cfg, p, max_seq_p)
    warm_decode_shapes(max_seq_p)
    warm_mixed = _mixed_max_new(len(warm))
    for method in PAGED_METHODS:
        _run_scheduled(cfg, params, kcfg, method, warm, max_seq_p,
                       rows_pool, max_news=warm_mixed)
        _run_scheduled(cfg, params, kcfg, method, warm, max_seq_p,
                       rows_pool, max_news=warm_mixed, fused_sampling=False)
        _run_scheduled(cfg, params, kcfg, method, warm, max_seq_p,
                       rows_paged, paged=True, max_news=warm_mixed,
                       page_size=PAGE_SIZE, num_pages=num_pages)
    for method in PAGED_METHODS:
        for depth in PAGED_DEPTHS:
            prompts = _prompts(depth)
            max_news = _mixed_max_new(depth)
            runs = {
                "pr1": lambda: _run_scheduled(
                    cfg, params, kcfg, method, prompts, max_seq_p,
                    rows_pool, max_news=max_news, fused_sampling=False),
                "cont": lambda: _run_scheduled(
                    cfg, params, kcfg, method, prompts, max_seq_p,
                    rows_pool, max_news=max_news),
                "paged": lambda: _run_scheduled(
                    cfg, params, kcfg, method, prompts, max_seq_p,
                    rows_paged, paged=True, max_news=max_news,
                    page_size=PAGE_SIZE, num_pages=num_pages),
            }
            # interleaved best-of-R: each rep times all three modes
            # back-to-back, so multi-second machine speed phases hit
            # every mode instead of whichever block they land on; best
            # wall clock per mode is then comparable (token streams are
            # deterministic — only timing varies between reps)
            gens, tps = {}, {}
            for _ in range(PAGED_REPS):
                for mode, fn in runs.items():
                    g, tp = fn()
                    gens[mode] = g
                    if mode not in tps or tp["tokens_per_s"] \
                            > tps[mode]["tokens_per_s"]:
                        tps[mode] = tp
            gens_1, gens_c, gens_p = gens["pr1"], gens["cont"], gens["paged"]
            tp_1, tp_c, tp_p = tps["pr1"], tps["cont"], tps["paged"]
            assert all(a.tokens == b.tokens == c.tokens
                       for a, b, c in zip(gens_1, gens_c, gens_p)), \
                "paged/fused serving diverged from the PR 1 baseline"
            if method == "kappa":
                # batched-controller contract (the acceptance criterion):
                # the fused modes make at most ONE controller dispatch
                # and ONE controller-carrying blocking transfer per tick,
                # no matter how many kappa requests are in flight
                for mode in ("cont", "paged"):
                    tp = tps[mode]
                    assert tp["controller_dispatches"] <= tp["ticks"], \
                        f"{mode}: {tp['controller_dispatches']} controller " \
                        f"dispatches over {tp['ticks']} ticks"
                    assert tp["controller_syncs"] == \
                        tp["controller_dispatches"]
            out.append({
                "kind": "paged", "method": method, "depth": depth,
                "rows_contiguous": rows_pool, "rows_paged": rows_paged,
                "page_size": PAGE_SIZE, "num_pages": num_pages,
                "kv_slot_budget": rows_pool * max_seq_p,
                "pr1_tokens_per_s": tp_1["tokens_per_s"],
                "contiguous_tokens_per_s": tp_c["tokens_per_s"],
                "paged_tokens_per_s": tp_p["tokens_per_s"],
                "fused_sampling_speedup": tp_c["tokens_per_s"]
                / max(tp_1["tokens_per_s"], 1e-9),
                "paged_vs_contiguous": tp_p["tokens_per_s"]
                / max(tp_c["tokens_per_s"], 1e-9),
                "paged_speedup": tp_p["tokens_per_s"]
                / max(tp_1["tokens_per_s"], 1e-9),
                "contiguous_row_utilization": tp_c["row_utilization"],
                "paged_row_utilization": tp_p["row_utilization"],
                "page_utilization": tp_p["page_utilization"],
                "contiguous_ticks": tp_c["ticks"],
                "paged_ticks": tp_p["ticks"],
                "pr1_time_s": tp_1["time_s"],
                "contiguous_time_s": tp_c["time_s"],
                "paged_time_s": tp_p["time_s"],
                "pr1_tick_breakdown_us": _tick_breakdown_us(tp_1),
                "paged_tick_breakdown_us": _tick_breakdown_us(tp_p),
                "paged_controller_dispatches": tp_p["controller_dispatches"],
                "paged_controller_syncs": tp_p["controller_syncs"],
            })
    out.extend(_fanout_scenario(cfg, params))
    out.extend(_int8_capacity_scenario(cfg, params))
    out.extend(_interleave_scenario(cfg, params))
    out.extend(_prefix_scenario(cfg, params))
    out.extend(_overload_scenario(cfg, params))
    out.extend(_openloop_scenario(cfg, params))
    return out


def emit_csv(rows):
    out = []
    for r in rows:
        if r["kind"] == "continuous":
            name = f"throughput/{r['method']}_depth{r['depth']}"
            us = r["cb_time_s"] * 1e6 / max(r["ticks"], 1)
            derived = (f"seq_tok_s={r['seq_tokens_per_s']:.1f};"
                       f"cb_tok_s={r['cb_tokens_per_s']:.1f};"
                       f"speedup={r['speedup']:.2f};"
                       f"util={r['row_utilization']:.2f}")
        elif r["kind"] == "interleave":
            name = f"throughput/interleave_chunk{r['prefill_chunk']}"
            us = r["chunked_itl_p99_s"] * 1e6
            derived = (f"base_itl_p99_us={r['baseline_itl_p99_s'] * 1e6:.0f};"
                       f"oneshot_itl_p99_us={r['oneshot_itl_p99_s'] * 1e6:.0f};"
                       f"chunked_itl_p99_us={r['chunked_itl_p99_s'] * 1e6:.0f};"
                       f"chunked_ratio={r['chunked_vs_baseline_itl_p99']:.2f};"
                       f"ttft_long_s={r['chunked_ttft_long_s']:.3f}")
        elif r["kind"] == "prefix":
            name = f"throughput/prefix_depth{r['depth']}"
            us = r["time_s"] * 1e6 / max(r["ticks"], 1)
            derived = (f"hit_rate={r['prefix_hit_rate']:.2f};"
                       f"saved_frac={r['prefill_tokens_saved_frac']:.2f};"
                       f"saved_toks={r['prefix_tokens_saved']};"
                       f"cached_tok_s={r['cached_tokens_per_s']:.1f};"
                       f"uncached_tok_s={r['uncached_tokens_per_s']:.1f};"
                       f"evictions={r['prefix_evictions']}")
        elif r["kind"] == "overload":
            name = f"throughput/overload_burst{r['burst']}"
            us = r["overload_itl_p99_s"] * 1e6
            derived = (f"base_itl_p99_us={r['baseline_itl_p99_s'] * 1e6:.0f};"
                       f"over_itl_p99_us={r['overload_itl_p99_s'] * 1e6:.0f};"
                       f"ratio={r['overload_vs_baseline_itl_p99']:.2f};"
                       f"shed_rate={r['shed_rate']:.2f};"
                       f"miss_rate={r['deadline_miss_rate']:.2f};"
                       f"goodput_tok_s={r['goodput_tokens_per_s']:.1f}")
        elif r["kind"] == "openloop":
            name = f"throughput/openloop_{r['rate_x_capacity']:g}x"
            us = r["adaptive"]["admitted_itl_p99_s"] * 1e6
            derived = (f"offered_rps={r['offered_rps']:.2f};"
                       f"static_itl_ratio={r['static_itl_vs_unloaded']:.2f};"
                       f"adaptive_itl_ratio="
                       f"{r['adaptive_itl_vs_unloaded']:.2f};"
                       f"static_goodput_tok_s="
                       f"{r['static']['goodput_tokens_per_s']:.1f};"
                       f"adaptive_goodput_tok_s="
                       f"{r['adaptive']['goodput_tokens_per_s']:.1f};"
                       f"static_shed={r['static']['shed']};"
                       f"adaptive_shed={r['adaptive']['shed']}")
        elif r["kind"] == "int8":
            name = f"throughput/int8_fanout{r['fan_out']}"
            us = r["int8_time_s"] * 1e6 / max(r["int8_ticks"], 1)
            derived = (f"budget_kb={r['page_budget_bytes'] // 1024};"
                       f"pages_fp={r['num_pages_fp']};"
                       f"pages_int8={r['num_pages_int8']};"
                       f"peak_req_fp={r['peak_concurrent_fp']};"
                       f"peak_req_int8={r['peak_concurrent_int8']};"
                       f"admit_ratio={r['admit_ratio']:.2f};"
                       f"acc_kappa={r['acc_kappa_int8']:.2f}"
                       f"/{r['acc_kappa_fp']:.2f};"
                       f"acc_bon={r['acc_bon_int8']:.2f}"
                       f"/{r['acc_bon_fp']:.2f}")
        elif r["kind"] == "fanout":
            name = f"throughput/fanout{r['fan_out']}_depth{r['depth']}"
            us = r["time_s"] * 1e6 / max(r["ticks"], 1)
            derived = (f"tok_s={r['tokens_per_s']:.1f};"
                       f"num_pages={r['num_pages']};"
                       f"bcast_worst={r['broadcast_worst_pages_per_req']};"
                       f"page_peak={r['page_peak']};"
                       f"savings={r['shared_page_savings']:.2f};"
                       f"preemptions={r['preemptions']}")
        else:
            name = f"throughput/paged_{r['method']}_depth{r['depth']}"
            us = r["paged_time_s"] * 1e6 / max(r["paged_ticks"], 1)
            bd1, bdp = r["pr1_tick_breakdown_us"], r["paged_tick_breakdown_us"]
            derived = (f"pr1_tok_s={r['pr1_tokens_per_s']:.1f};"
                       f"cont_tok_s={r['contiguous_tokens_per_s']:.1f};"
                       f"paged_tok_s={r['paged_tokens_per_s']:.1f};"
                       f"paged_speedup={r['paged_speedup']:.2f};"
                       f"page_util={r['page_utilization']:.2f};"
                       f"pr1_host_us={bd1['host']:.0f};"
                       f"paged_host_us={bdp['host']:.0f};"
                       f"paged_ctrl_us={bdp['control']:.0f}")
        out.append(f"{name},{us:.1f},{derived}")
    return out


def openloop_smoke():
    """CI entry (``--openloop-smoke``): two-rate open-loop sweep on an
    untrained toy model — asserts the goodput-under-SLO curve is
    produced for both admission modes and (inside the scenario) that
    every run drains with zero leaked pages/pins."""
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    rows = _openloop_scenario(cfg, params, smoke=True)
    print("name,us_per_call,derived")
    for line in emit_csv(rows):
        print(line)
    assert len(rows) == len(OPENLOOP_SMOKE_RATES_X)
    for r in rows:
        for mode in ("static", "adaptive"):
            assert r[mode]["goodput_tokens_per_s"] >= 0.0
            assert "goodput_under_slo_tokens_per_s" in r[mode]
            assert r[mode]["ok"] > 0, f"{mode} starved every request"
    print(f"# openloop smoke: {len(rows)} rates x 2 admission modes, "
          f"goodput curve produced, zero leaks after drain -> PASS")


if __name__ == "__main__":
    import sys

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    if "--openloop-smoke" in sys.argv:
        openloop_smoke()
        sys.exit(0)
    cfg, params = common.bench_model()
    t0 = time.time()
    rows = run(cfg, params)
    print("name,us_per_call,derived")
    for line in emit_csv(rows):
        print(line)
    common.write_bench_json("throughput", rows, time.time() - t0)
    kap = {r["depth"]: r for r in rows
           if r["kind"] == "continuous" and r["method"] == "kappa"}
    for depth, r in sorted(kap.items()):
        if depth >= 4:
            verdict = "PASS" if r["speedup"] > 1.0 else "FAIL"
            print(f"# depth={depth}: continuous batching speedup "
                  f"{r['speedup']:.2f}x -> {verdict}")
    for r in rows:
        if r["kind"] == "paged" and r["method"] == "kappa":
            bd1, bdp = r["pr1_tick_breakdown_us"], r["paged_tick_breakdown_us"]
            print(f"# kappa depth={r['depth']}: per-tick controller cost "
                  f"{bd1['host']:.0f}us host (pr1: one dispatch+sync per "
                  f"request) -> {bdp['control']:.0f}us pooled dispatch + "
                  f"{bdp['host']:.0f}us host "
                  f"({r['paged_controller_dispatches']} dispatches / "
                  f"{r['paged_ticks']} ticks)")
    paged_rows = [r for r in rows if r["kind"] == "paged" and r["depth"] >= 8]
    for r in paged_rows:
        print(f"# {r['method']} depth={r['depth']}: paged+fused vs PR1 "
              f"contiguous {r['paged_speedup']:.2f}x "
              f"(fused sampling alone {r['fused_sampling_speedup']:.2f}x,"
              f" paging alone {r['paged_vs_contiguous']:.2f}x)")
    if paged_rows:
        best = max(paged_rows, key=lambda r: r["paged_speedup"])
        verdict = "PASS" if best["paged_speedup"] >= 1.5 else "FAIL"
        print(f"# acceptance: paged+batched-sampling vs PR1 contiguous at "
              f"queue depth >= 8: {best['paged_speedup']:.2f}x "
              f"({best['method']}, depth {best['depth']}; >=1.5 target) "
              f"-> {verdict}")
    for r in rows:
        if r["kind"] == "interleave":
            ratio = r["chunked_vs_baseline_itl_p99"]
            # "~1.2x": p99 over ~150 window samples rides 1-2 noise
            # spikes on the CPU container (±20% run-to-run), so the
            # hard gate sits at 1.35 and requires the one-shot stall to
            # actually reproduce (>=2x) for the comparison to mean much
            verdict = "PASS" if (ratio <= 1.35 and
                                 r["oneshot_vs_baseline_itl_p99"] >= 2.0) \
                else "FAIL"
            print(f"# interleave: long-prompt ({r['long_prompt_len']} tok) "
                  f"admission over {r['in_flight']} in-flight requests — "
                  f"in-flight ITL p99 {r['baseline_itl_p99_s'] * 1e3:.1f}ms "
                  f"baseline / {r['oneshot_itl_p99_s'] * 1e3:.1f}ms one-shot "
                  f"/ {r['chunked_itl_p99_s'] * 1e3:.1f}ms chunked "
                  f"({ratio:.2f}x baseline, <=1.2 target; one-shot "
                  f"{r['oneshot_vs_baseline_itl_p99']:.2f}x); long TTFT "
                  f"{r['chunked_ttft_long_s']:.3f}s chunked vs "
                  f"{r['oneshot_ttft_long_s']:.3f}s one-shot -> {verdict}")
    for r in rows:
        if r["kind"] == "prefix":
            verdict = "PASS" if (r["prefill_tokens_saved_frac"] >= 0.5
                                 and r["prefix_hit_rate"] > 0) else "FAIL"
            print(f"# prefix: {r['depth']} requests sharing a "
                  f"{r['preamble_len']}-token preamble — hit rate "
                  f"{r['prefix_hit_rate']:.2f}, "
                  f"{r['prefix_tokens_saved']}/{r['prompt_tokens']} prefill "
                  f"tokens saved ({r['prefill_tokens_saved_frac']:.0%}, "
                  f">=50% target), cached serving "
                  f"{r['cached_vs_uncached']:.2f}x uncached -> {verdict}")
    for r in rows:
        if r["kind"] == "overload":
            ratio = r["overload_vs_baseline_itl_p99"]
            verdict = "PASS" if ratio <= 1.5 else "FAIL"
            print(f"# overload: {r['burst']}-request burst over a "
                  f"{r['depth']}-deep unloaded pool (queue bound "
                  f"{r['max_queue']}, {r['tick_budget']}-tick budget) — "
                  f"{r['served_ok']} served, shed rate {r['shed_rate']:.0%}, "
                  f"deadline-miss rate {r['deadline_miss_rate']:.0%}, "
                  f"goodput {r['goodput_tokens_per_s']:.1f} tok/s; "
                  f"admitted ITL p99 {ratio:.2f}x unloaded "
                  f"(<=1.5 target) -> {verdict}")
    ol = [r for r in rows if r["kind"] == "openloop"]
    for r in ol:
        a, s = r["adaptive"], r["static"]
        print(f"# openloop {r['rate_x_capacity']:g}x capacity "
              f"({r['offered_rps']:.2f} req/s offered): admitted ITL p99 "
              f"{r['static_itl_vs_unloaded']:.2f}x (static) / "
              f"{r['adaptive_itl_vs_unloaded']:.2f}x (adaptive) unloaded; "
              f"goodput {s['goodput_tokens_per_s']:.1f} vs "
              f"{a['goodput_tokens_per_s']:.1f} tok/s "
              f"(under-SLO {s['goodput_under_slo_tokens_per_s']:.1f} vs "
              f"{a['goodput_under_slo_tokens_per_s']:.1f}); shed "
              f"{s['shed']} vs {a['shed']}")
    if ol:
        sep = [r for r in ol
               if r["static_itl_vs_unloaded"] > OPENLOOP_SLO_BOUND
               and r["adaptive_itl_vs_unloaded"] <= OPENLOOP_SLO_BOUND]
        verdict = "PASS" if sep else "FAIL"
        at = (f" at {sep[0]['rate_x_capacity']:g}x capacity"
              if sep else "")
        print(f"# acceptance: adaptive admission holds admitted ITL p99 "
              f"<= {OPENLOOP_SLO_BOUND}x unloaded at an offered rate "
              f"where static admission exceeds it{at} -> {verdict}")
    for r in rows:
        if r["kind"] == "int8":
            verdict = "PASS" if (r["admit_ratio"] >= 1.8
                                 and r["parity_ok"]) else "FAIL"
            print(f"# int8 KV: equal {r['page_budget_bytes'] // 1024}KiB "
                  f"budget holds {r['num_pages_int8']} int8 pages vs "
                  f"{r['num_pages_fp']} fp — peak "
                  f"{r['peak_concurrent_int8']} concurrent fan-out "
                  f"requests vs {r['peak_concurrent_fp']} "
                  f"({r['admit_ratio']:.1f}x, >=1.8 target); answer "
                  f"accuracy kappa {r['acc_kappa_int8']:.2f} vs "
                  f"{r['acc_kappa_fp']:.2f} fp, bon "
                  f"{r['acc_bon_int8']:.2f} vs {r['acc_bon_fp']:.2f} fp "
                  f"-> {verdict}")
    for r in rows:
        if r["kind"] == "fanout":
            print(f"# fanout N={r['fan_out']} depth={r['depth']}: served in "
                  f"{r['num_pages']} pages (broadcast needed "
                  f"{r['broadcast_worst_pages_per_req']}/request — would "
                  f"raise at submit), peak {r['page_peak']} pages, "
                  f"{r['shared_page_savings']:.0%} shared-page savings, "
                  f"{r['preemptions']} preemptions -> PASS")
