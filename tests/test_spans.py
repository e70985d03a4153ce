"""Phase spans of the serving tick (serving/spans.py): each phase of a
paged tick opens its ``serve.<phase>`` annotation in order and nesting,
and adds its host seconds to ``tick_time`` (a parent's at least its
children's); the front end's yield between ticks and full collections
are spans too; a real profiler trace on the CPU holds the spans on the
host plane; and under the profiler the scheduler serves the same
tokens with the same one blocking transfer per decode tick."""
import asyncio
import gc
import glob

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving import spans
from repro.serving.frontend import ServingFrontend
from repro.serving.scheduler import PagedScheduler

# a decode tick, in the order and nesting its spans open
DECODE_TICK = [(0, "serve.tick"), (1, "serve.admit"), (1, "serve.prefill"),
               (1, "serve.pages"), (1, "serve.step"), (1, "serve.keys"),
               (1, "serve.sample"), (1, "serve.control"), (1, "serve.sync"),
               (1, "serve.host"), (2, "serve.emit")]
CHILDREN = {"tick": ("admit", "prefill", "pages", "step", "keys",
                     "sample", "control", "sync", "host"),
            "host": ("emit",)}


class _Notes:
    """Stand-in for the profiler's annotations: logs what opens and
    closes, with the step number of a step annotation."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kw):
        log = self.log

        class Note:
            def __enter__(self):
                log.append(("enter", name, kw.get("step_num")))
                return self

            def __exit__(self, *exc):
                log.append(("exit", name, None))

        return Note()

    def tree(self, skip=("serve.gc",)):
        """(depth, name) of every span opened, in order."""
        out, depth = [], 0
        for ev, name, _ in self.log:
            if name in skip:
                continue
            if ev == "enter":
                out.append((depth, name))
                depth += 1
            else:
                depth -= 1
        assert depth == 0, "a span was left open"
        return out


@pytest.fixture
def notes(monkeypatch):
    n = _Notes()
    monkeypatch.setattr(spans, "TraceAnnotation", n)
    monkeypatch.setattr(spans, "StepTraceAnnotation", n)
    return n


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=10, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    prompts = [np.array([tok.BOS, tok.PROB, 3 + i, tok.PLUS, 4, tok.EQ,
                         tok.QM]) for i in range(3)]
    return cfg, params, kcfg, prompts


def _sched(setup):
    cfg, params, kcfg, _ = setup
    return PagedScheduler(params, cfg, kcfg, rows=8, max_seq=32,
                          page_size=4, num_pages=64, method="kappa",
                          eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)


def _serve(setup):
    sched = _sched(setup)
    rids = [sched.submit(p, jax.random.PRNGKey(i))
            for i, p in enumerate(setup[3])]
    res = sched.run()
    return sched, [res[r].tokens for r in rids]


def test_paged_tick_opens_each_phase_span_in_order(setup, notes):
    sched = _sched(setup)
    sched.submit(setup[3][0], jax.random.PRNGKey(0))
    while not sched.active:
        sched.tick()
    step = sched.ticks
    notes.log.clear()
    sched.tick()
    assert notes.tree() == DECODE_TICK
    assert notes.log[0] == ("enter", "serve.tick", step)
    tt = sched.tick_time
    assert set(tt) == set(spans.PHASES)
    for parent, kids in CHILDREN.items():
        assert sum(tt[k] for k in kids) <= tt[parent]
    assert tt["tick"] > 0 and tt["frontend"] == 0.0
    tp = sched.throughput()
    assert all(f"time_{k}_s" in tp for k in spans.PHASES)
    assert "time_model_s" not in tp and "time_controller_s" not in tp


def test_span_adds_seconds_and_rejects_unknown_phases(notes):
    tt = spans.PhaseTimes()
    with tt.span("host"):
        with tt.span("emit"):
            pass
    assert 0.0 < tt["emit"] <= tt["host"]
    with pytest.raises(KeyError):
        with tt.span("model"):
            pass
    assert notes.tree() == [(0, "serve.host"), (1, "serve.emit")]


@pytest.mark.parametrize("mode", ["asyncio", "thread"])
def test_frontend_yield_between_ticks_is_a_span(setup, notes, mode):
    sched = _sched(setup)
    prompt = setup[3][0]
    if mode == "asyncio":
        async def go():
            async with ServingFrontend(sched) as fe:
                return await fe.submit(prompt, jax.random.PRNGKey(0))
        res = asyncio.run(go())
    else:
        with ServingFrontend(sched) as fe:
            res = fe.wait_result(fe.submit_nowait(prompt,
                                                  jax.random.PRNGKey(0)),
                                 timeout=300)
    assert res.status == "OK"
    assert sched.tick_time["frontend"] > 0
    # the front end's spans lie between ticks, never inside one
    tree = notes.tree()
    assert (0, "serve.frontend") in tree
    assert all(d == 0 for d, n in tree if n == "serve.frontend")


def test_full_collections_are_spans(notes):
    spans.watch_gc()
    spans.watch_gc()
    assert gc.callbacks.count(spans._on_gc) == 1
    gc.collect(0)
    assert notes.log == []
    gc.collect()
    assert [n for _, n, _ in notes.log] == ["serve.gc", "serve.gc"]
    assert [e for e, _, _ in notes.log] == ["enter", "exit"]


def test_profiled_serving_is_token_equal_and_traced(setup, tmp_path):
    """Under the JAX profiler (CPU) the scheduler serves the same tokens
    with the same one blocking transfer per decode tick, and the trace's
    host plane holds every tick phase, nested inside its ``serve.tick``
    (which carries the tick's step number)."""
    plain, want = _serve(setup)
    with jax.profiler.trace(str(tmp_path)):
        traced, got = _serve(setup)
    assert got == want
    for s in (plain, traced):
        assert s.counters["host_syncs"] \
            == s.counters["sampler_dispatches"]
    assert traced.counters["host_syncs"] == plain.counters["host_syncs"]

    path, = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
            {k: v for k, v in ev.stats})
           for plane in jax.profiler.ProfileData.from_file(path).planes
           if plane.name.startswith("/host:")
           for line in plane.lines for ev in line.events
           if ev.name.startswith(spans.PREFIX)]
    names = {e[0] for e in evs}
    assert {n for _, n in DECODE_TICK} <= names
    ticks = [e for e in evs if e[0] == "serve.tick"]
    assert len(ticks) == traced.ticks
    assert sorted(e[3]["step_num"] for e in ticks) \
        == list(range(traced.ticks))
    for name, a, b, _ in evs:
        if name not in ("serve.tick", "serve.gc"):
            assert any(t[1] <= a and b <= t[2] for t in ticks), name
