"""Streaming front-end semantics (DESIGN.md §9): per-rid event order
with exactly one terminal event, token-for-token equality between
streamed and batch ``run()`` serving across all three backends,
mid-stream cancellation, mixed-strategy concurrent streams vs the
sequential engine, the thread fallback backend, and zero-leak
shutdown. No pytest-asyncio: each async scenario runs under
``asyncio.run`` inside a sync test."""
import asyncio
import dataclasses
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving import engine
from repro.serving.frontend import ServingFrontend
from repro.serving.scheduler import (ContinuousBatchingScheduler,
                                     PagedScheduler)

MAX_SEQ = 32
PAGE_SIZE = 4
ROWS = 8
BACKENDS = ["contig", "paged", "paged+prefix"]


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=12, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    prompts = [
        np.array([tok.BOS, tok.PROB, 3, tok.PLUS, 4, tok.EQ, tok.QM]),
        np.array([tok.BOS, tok.PROB, 7, tok.PLUS, 2, tok.PLUS, 1, tok.EQ,
                  tok.QM]),
        np.array([tok.BOS, tok.PROB, 5, tok.PLUS, 5, tok.EQ, tok.QM]),
    ]
    return cfg, params, kcfg, prompts


def _mk(setup, backend, **kw):
    cfg, params, kcfg, _ = setup
    base = dict(rows=ROWS, max_seq=MAX_SEQ, method="kappa",
                eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)
    base.update(kw)
    if backend == "contig":
        return ContinuousBatchingScheduler(params, cfg, kcfg, **base)
    return PagedScheduler(params, cfg, kcfg, page_size=PAGE_SIZE,
                          num_pages=ROWS * MAX_SEQ // PAGE_SIZE,
                          prefix_cache=backend.endswith("prefix"), **base)


def _assert_no_leaks(sched):
    assert sorted(sched.free) == list(range(sched.rows))
    assert not sched.active and not sched.prefilling and not sched.queue
    if getattr(sched, "pcache", None) is not None:
        sched.pcache.drop()
    if hasattr(sched, "alloc"):
        assert sched.alloc.free_count == sched.num_pages, "leaked pages"
        assert int(sched.alloc.pinned.sum()) == 0, "leaked pins"


async def _consume(fe, prompt, i, **kw):
    """Stream one request; returns (events, token list, terminal result)."""
    evs = []
    async for ev in fe.submit_stream(prompt, jax.random.PRNGKey(i), **kw):
        evs.append(ev)
    toks = [e.token for e in evs if e.kind == "token"]
    return evs, toks, evs[-1].result


# ------------------------------------------------------- event contract

def test_event_order_and_single_terminal(setup):
    _, _, _, prompts = setup
    sched = _mk(setup, "paged")

    async def go():
        async with ServingFrontend(sched) as fe:
            return await asyncio.gather(
                *[_consume(fe, p, i) for i, p in enumerate(prompts)])

    for evs, toks, res in asyncio.run(go()):
        ends = [e for e in evs if e.kind == "end"]
        assert len(ends) == 1, "exactly one terminal event per rid"
        assert evs[-1] is ends[0], "terminal event ends the stream"
        assert res.status == "OK"
        # strict decode order: indices 0..n-1 with no gaps or repeats
        idx = [e.index for e in evs if e.kind == "token"]
        assert idx == list(range(len(toks)))
        assert ends[0].index == len(res.tokens)
        assert toks == res.tokens
        # every event belongs to this rid
        assert len({e.rid for e in evs}) == 1
    _assert_no_leaks(sched)


@pytest.mark.parametrize("backend", BACKENDS)
def test_stream_matches_batch_run(setup, backend):
    """The acceptance property: streamed requests are token-for-token
    equal to batch ``run()`` on the same seeds, for contiguous, paged,
    and paged+prefix-cache backends."""
    _, _, _, prompts = setup
    batch_sched = _mk(setup, backend)
    rids = [batch_sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(prompts)]
    batch = batch_sched.run()

    stream_sched = _mk(setup, backend)

    async def go():
        async with ServingFrontend(stream_sched) as fe:
            return await asyncio.gather(
                *[_consume(fe, p, i) for i, p in enumerate(prompts)])

    outs = asyncio.run(go())
    for rid, (evs, toks, res) in zip(rids, outs):
        assert toks == batch[rid].tokens, f"{backend} stream diverged"
        assert res.tokens == batch[rid].tokens
        assert res.chosen_branch == batch[rid].chosen_branch
        assert res.steps == batch[rid].steps
    _assert_no_leaks(stream_sched)


# ------------------------------------------------------------- cancel

def test_cancel_mid_stream_ends_iterator(setup):
    _, _, _, prompts = setup
    sched = _mk(setup, "paged")

    async def go():
        async with ServingFrontend(sched) as fe:
            rid = fe.submit_nowait(prompts[0], jax.random.PRNGKey(0),
                                   method="greedy", max_new=12)
            got = []
            async for ev in fe.events(rid):
                got.append(ev)
                if sum(e.kind == "token" for e in got) == 2:
                    fe.cancel(rid)
            res = await fe.result(rid)
            return got, res

    got, res = asyncio.run(go())
    assert got[-1].kind == "end" and got[-1].status == "CANCELLED"
    assert res.status == "CANCELLED"
    assert 0 < res.steps < 12            # genuinely cut short mid-decode
    # the partial stream is exactly the terminal result's tokens
    assert [e.token for e in got if e.kind == "token"] == res.tokens
    _assert_no_leaks(sched)


# ------------------------------------------------- mixed-strategy pool

def test_mixed_pool_concurrent_streams_match_sequential(setup):
    """Concurrent kappa + bon + greedy streams over one paged pool
    produce the same tokens as dedicated sequential engine runs."""
    cfg, params, kcfg, prompts = setup
    specs = [("kappa", 12), ("bon", 10), ("greedy", 12)]
    seq = []
    for i, (p, (m, mn)) in enumerate(zip(prompts, specs)):
        kc = dataclasses.replace(kcfg, max_new_tokens=mn)
        fn = getattr(engine, f"generate_{m}")
        seq.append(fn(params, cfg, kc, p, jax.random.PRNGKey(i),
                      eos_id=tok.EOS, bos_id=tok.BOS, max_seq=MAX_SEQ))

    sched = _mk(setup, "paged")

    async def go():
        async with ServingFrontend(sched) as fe:
            return await asyncio.gather(
                *[_consume(fe, p, i, method=m, max_new=mn)
                  for i, (p, (m, mn)) in enumerate(zip(prompts, specs))])

    outs = asyncio.run(go())
    for s, (evs, toks, res), (m, _) in zip(seq, outs, specs):
        assert toks == s.tokens, f"{m} stream diverged from sequential"
        assert res.chosen_branch == s.chosen_branch
        assert res.logical_tokens == s.logical_tokens
    _assert_no_leaks(sched)


# ------------------------------------------------------ thread backend

def test_thread_backend_stream_and_result(setup):
    _, _, _, prompts = setup
    sched = _mk(setup, "contig")
    with ServingFrontend(sched) as fe:
        r0 = fe.submit_nowait(prompts[0], jax.random.PRNGKey(0),
                              method="greedy")
        r1 = fe.submit_nowait(prompts[1], jax.random.PRNGKey(1))
        evs = list(fe.stream(r0, timeout=120))
        res0 = fe.wait_result(r0, timeout=120)
        res1 = fe.wait_result(r1, timeout=120)
    assert evs[-1].kind == "end" and res0.status == "OK"
    assert [e.token for e in evs if e.kind == "token"] == res0.tokens
    assert res1.status == "OK" and len(res1.tokens) > 0
    _assert_no_leaks(sched)


# --------------------------------------------------- shed + shutdown

def test_shed_stream_is_single_end_event(setup):
    """A request shed at the submit door (bounded queue) emits its
    terminal event synchronously inside ``submit`` — before the rid's
    channel exists — and the stream still sees exactly one SHED end."""
    _, _, _, prompts = setup
    sched = _mk(setup, "paged", max_queue=1)

    async def go():
        async with ServingFrontend(sched) as fe:
            rids = [fe.submit_nowait(p, jax.random.PRNGKey(i))
                    for i, p in enumerate(prompts)]
            outs = []
            for rid in rids:
                outs.append([ev async for ev in fe.events(rid)])
            return rids, outs

    rids, outs = asyncio.run(go())
    statuses = [evs[-1].status for evs in outs]
    assert statuses.count("SHED") == 2 and statuses.count("OK") == 1
    for evs in outs:
        if evs[-1].status == "SHED":
            assert [e.kind for e in evs] == ["end"], \
                "shed stream is exactly one terminal event"
            assert evs[-1].result.tokens == []
    assert sched.counters["shed"] == 2
    _assert_no_leaks(sched)


def test_shutdown_drains_zero_leaks(setup):
    """``aclose`` drains in-flight work before stopping the tick task:
    no leaked rows, pages, or pins, even with the prefix cache pinning
    prompt pages (dropped explicitly like the batch path does)."""
    _, _, _, prompts = setup
    sched = _mk(setup, "paged+prefix")

    async def go():
        fe = ServingFrontend(sched)
        fe.start_async()
        for i, p in enumerate(prompts):
            fe.submit_nowait(p, jax.random.PRNGKey(i))
        await fe.aclose()            # must drain, not abandon

    asyncio.run(go())
    assert len(sched.results) == len(prompts)
    assert all(r.status == "OK" for r in sched.results.values())
    assert sched.event_sink is None      # frontend detached cleanly
    _assert_no_leaks(sched)


# ------------------------------------------------------- failed tick

def _fail_after(monkeypatch, ticks: int):
    """Make every PagedScheduler tick past ``ticks`` raise."""
    real_step = PagedScheduler.step

    def step(self):
        if self.ticks >= ticks:
            raise RuntimeError("tick failed")
        return real_step(self)

    monkeypatch.setattr(PagedScheduler, "step", step)


def test_failed_tick_fails_frontend_serve(monkeypatch):
    """A tick that raises fails the streaming serve within seconds
    instead of hanging it: every stream, and the drain on exit, re-raise
    the tick's error. The serve runs on a daemon thread so that a
    regression fails this test rather than hanging the suite."""
    from repro.launch.serve import serve_eval
    _fail_after(monkeypatch, 3)
    raised = []

    def serve():
        try:
            serve_eval("deepseek-r1-distill-qwen-1.5b", "kappa", n=2,
                       problems=2, max_new=8, d_model=64, paged=True,
                       page_size=8, prefill_chunk=4, frontend_serve=True,
                       stream=True, verbose=False)
        except Exception as e:
            raised.append(e)

    t = threading.Thread(target=serve, daemon=True)
    t.start()
    t.join(timeout=120)
    assert not t.is_alive(), "frontend serve hung on a failed tick"
    assert len(raised) == 1 and isinstance(raised[0], RuntimeError)
    assert "tick failed" in str(raised[0])


def test_failed_tick_fails_thread_backend(setup, monkeypatch):
    """Thread backend twin: the blocked stream, the result wait, join()
    and close() re-raise the error, and later submissions refuse."""
    _, _, _, prompts = setup
    sched = _mk(setup, "paged")
    _fail_after(monkeypatch, 2)
    fe = ServingFrontend(sched)
    fe.start_thread()
    rid = fe.submit_nowait(prompts[0], jax.random.PRNGKey(0))
    with pytest.raises(RuntimeError, match="tick failed"):
        list(fe.stream(rid, timeout=60))
    with pytest.raises(RuntimeError, match="tick failed"):
        fe.wait_result(rid, timeout=60)
    with pytest.raises(RuntimeError, match="tick failed"):
        fe.submit_nowait(prompts[1], jax.random.PRNGKey(1))
    with pytest.raises(RuntimeError, match="tick failed"):
        fe.join(timeout_s=60)
    with pytest.raises(RuntimeError, match="tick failed"):
        fe.close()
    assert sched.event_sink is None


# ------------------------------------------------------- full width

def test_full_width_serves_config_as_published(monkeypatch):
    """``full_width`` serves the config itself — its layer count, widths
    and dtype — where the default cuts it to the 2-layer float32 toy."""
    from repro.launch import serve as serve_mod
    small = dataclasses.replace(
        get_config("deepseek-r1-distill-qwen-1.5b"), num_layers=3,
        d_model=96, num_heads=6, num_kv_heads=2, d_ff=128, vocab_size=512)
    monkeypatch.setattr(serve_mod, "get_config", lambda arch: small)
    kw = dict(n=2, problems=2, max_new=6, paged=True, page_size=8,
              prefill_chunk=4, frontend_serve=True, stream=True,
              verbose=False)
    out = serve_mod.serve_eval(small.name, "kappa", full_width=True, **kw)
    assert out["model"] == {
        "layers": 3, "d_model": 96, "heads": 6, "kv_heads": 2,
        "head_dim": 16, "d_ff": 128, "vocab": 512, "dtype": "bfloat16",
        "kv_cache_dtype": "model"}
    assert out["status_counts"] == {"OK": 2}
    toy = serve_mod.serve_eval(small.name, "kappa", **kw)["model"]
    assert (toy["layers"], toy["dtype"]) == (2, "float32")
