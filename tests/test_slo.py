"""Windowed metrics + SLO-adaptive admission (DESIGN.md §9): the
scheduler's ``snapshot(reset_window=True)`` percentiles under a fake
clock, the controller's hysteretic escalation ladder against a stub
scheduler, and the closed loop on a real pool — a fake-clock-forced
ITL violation walks the knobs down (halve chunks, pause admits, shed)
and an idle pool walks them back up."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving.scheduler import PagedScheduler
from repro.serving.slo import SLOConfig, SLOController

MAX_SEQ = 32
PAGE_SIZE = 4


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=4, max_new_tokens=12, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    return cfg, params, kcfg


def _prompt(i, plen=7):
    body = np.random.default_rng(100 + i).integers(0, tok.MOD, size=plen - 2)
    return np.concatenate([[tok.BOS], body, [tok.QM]])


def _mk(setup, rows=8, **kw):
    cfg, params, kcfg = setup
    base = dict(rows=rows, max_seq=MAX_SEQ, method="kappa",
                eos_id=tok.EOS, bos_id=tok.BOS)
    base.update(kw)
    return PagedScheduler(params, cfg, kcfg, page_size=PAGE_SIZE,
                          num_pages=rows * MAX_SEQ // PAGE_SIZE, **base)


# --------------------------------------------------- windowed snapshot

def test_snapshot_windows_reset(setup, fake_clock):
    sched = _mk(setup, clock=fake_clock)
    rid = sched.submit(_prompt(0), jax.random.PRNGKey(0), method="greedy",
                       max_new=12)
    sched.tick()                       # admit + first decode at t=0
    for _ in range(4):
        fake_clock.advance(0.25)       # every later tick is 0.25s apart
        sched.tick()
    snap = sched.snapshot(reset_window=True)
    assert snap["window_s"] == pytest.approx(1.0)
    assert snap["window_ticks"] == 5
    assert snap["itl_count"] >= 4
    assert snap["itl_p50_s"] == pytest.approx(0.25)
    assert snap["itl_p99_s"] == pytest.approx(0.25)
    assert snap["ttft_count"] == 1 and snap["completed"] == 0

    # the reset actually reset: a fresh window sees only what's new
    fresh = sched.snapshot()
    assert fresh["itl_count"] == 0 and fresh["ttft_count"] == 0
    assert fresh["window_ticks"] == 0

    fake_clock.advance(2.0)
    out = sched.run()
    assert out[rid].status == "OK"
    final = sched.snapshot(reset_window=True)
    assert final["completed"] == 1 and final["ok"] == 1
    assert final["ok_tokens"] == out[rid].logical_tokens
    # goodput is OK tokens over the WINDOW clock, not run lifetime
    assert final["goodput_tokens_per_s"] == pytest.approx(
        final["ok_tokens"] / final["window_s"])


def test_snapshot_counts_shed(setup):
    sched = _mk(setup, max_queue=1)
    sched.submit(_prompt(0), jax.random.PRNGKey(0))
    sched.submit(_prompt(1), jax.random.PRNGKey(1))   # shed at the door
    snap = sched.snapshot()
    assert snap["shed"] == 1 and snap["completed"] == 1 and snap["ok"] == 0


# -------------------------------------------------- controller ladder

class _StubSched:
    """Knob surface the controller touches, with a scripted snapshot."""

    def __init__(self):
        self.prefill_chunk = 8
        self.prefill_budget = None
        self.max_queue = 16
        self.admit_paused = False
        self.ticks = 0
        self.queue = []
        self.snap = {}

    def snapshot(self, reset_window=False):
        return dict(self.snap)


def _stub_snap(itl_count=10, itl_p99=0.0, ttft_count=0, ttft_p99=0.0):
    return {"itl_count": itl_count, "itl_p99_s": itl_p99,
            "ttft_count": ttft_count, "ttft_p99_s": ttft_p99}


def test_controller_escalation_and_hysteresis():
    s = _StubSched()
    ctl = SLOController(s, SLOConfig(target_itl_p99_s=0.1,
                                     min_itl_samples=4))
    s.snap = _stub_snap(itl_p99=0.5)          # violated window
    ctl.update()
    assert ctl.level == 1
    assert s.prefill_chunk == 4 and not s.admit_paused
    assert s.prefill_budget == 8              # paced to one base chunk
    ctl.update()
    assert ctl.level == 2 and s.admit_paused
    assert s.max_queue == 16                  # queue untouched until 3
    ctl.update()
    assert ctl.level == 3 and s.max_queue == 8
    ctl.update()
    assert ctl.level == 3                     # clamped at max_level

    # in-between window (under target, above recover_frac*target): hold
    s.snap = _stub_snap(itl_p99=0.09)
    ctl.update()
    assert ctl.level == 3

    # clearly-healthy windows de-escalate one level each
    s.snap = _stub_snap(itl_p99=0.01)
    ctl.update()
    assert ctl.level == 2 and s.max_queue == 16
    ctl.update()
    assert ctl.level == 1 and not s.admit_paused
    ctl.update()
    assert ctl.level == 0 and s.prefill_chunk == 8
    assert s.prefill_budget is None           # pacing lifted at level 0
    assert len(ctl.history) == 8


def test_controller_unwedges_on_idle():
    """Too few samples to judge must read as healthy: a paused, drained
    pool produces no ITL samples, and staying paused forever would
    wedge admission shut."""
    s = _StubSched()
    ctl = SLOController(s, SLOConfig(target_itl_p99_s=0.1,
                                     min_itl_samples=4))
    s.snap = _stub_snap(itl_p99=9.0)
    ctl.update()
    ctl.update()
    assert s.admit_paused
    s.snap = _stub_snap(itl_count=0)          # idle: nothing to measure
    ctl.update()
    ctl.update()
    assert ctl.level == 0 and not s.admit_paused


def test_controller_ttft_target_escalates():
    s = _StubSched()
    ctl = SLOController(s, SLOConfig(target_itl_p99_s=1.0,
                                     target_ttft_p99_s=0.2,
                                     min_itl_samples=4))
    s.snap = _stub_snap(itl_p99=0.01, ttft_count=6, ttft_p99=0.9)
    ctl.update()
    assert ctl.level == 1                     # TTFT alone can escalate


# ----------------------------------------------- admission pacing knob

def test_prefill_budget_paces_admission(setup):
    """``prefill_budget`` spreads a burst of arrivals across ticks: one
    admission per tick with budget < prompt length, instead of all
    three riding the first tick's dispatch — and nothing is lost."""
    sched = _mk(setup, prefill_chunk=4, method="greedy")
    sched.prefill_budget = 1
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i),
                         method="greedy", max_new=4) for i in range(3)]
    sched.tick()
    assert len(sched.prefilling) + len(sched.active) == 1
    assert len(sched.queue) == 2
    sched.tick()
    assert len(sched.prefilling) + len(sched.active) == 2
    assert len(sched.queue) == 1
    out = sched.run()
    assert all(out[r].status == "OK" for r in rids)
    assert sorted(sched.free) == list(range(sched.rows))


# ------------------------------------------------------- closed loop

def test_slo_loop_degrades_then_recovers(setup, fake_clock):
    """Real pool, fake time: 0.5s ticks blow a 0.1s ITL p99 target, so
    the controller walks the full ladder (halve chunk → pause admits →
    shrink queue until a submit sheds); freezing the clock makes every
    window healthy and the ladder walks back to level 0, after which
    the queued work drains normally."""
    sched = _mk(setup, rows=2, prefill_chunk=2, max_queue=8,
                method="greedy", clock=fake_clock)
    ctl = SLOController(sched, SLOConfig(target_itl_p99_s=0.1,
                                         window_ticks=4,
                                         min_itl_samples=2))
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i),
                         method="greedy", max_new=20)
            for i in range(6)]                # 2 admit, 4 queue behind

    def drive(n, dt):
        for _ in range(n):
            fake_clock.advance(dt)
            if sched.has_work:
                sched.tick()
            ctl.on_tick()

    drive(4, 0.5)       # warmup window: chunked prefill, no ITL samples
    assert ctl.level == 0                     # nothing to judge yet
    drive(4, 0.5)
    assert ctl.level == 1 and sched.prefill_chunk == 1
    drive(4, 0.5)
    assert ctl.level == 2 and sched.admit_paused
    drive(4, 0.5)
    assert ctl.level == 3 and sched.max_queue == 4
    # the shrunken queue sheds at the door now
    shed_rid = sched.submit(_prompt(9), jax.random.PRNGKey(9),
                            method="greedy")
    assert sched.results[shed_rid].status == "SHED"

    drive(12, 0.0)                            # healthy windows: recover
    assert ctl.level == 0
    assert not sched.admit_paused
    assert sched.prefill_chunk == 2 and sched.max_queue == 8

    out = sched.run()                         # queued work drains
    assert all(out[r].status == "OK" for r in rids)
    assert sorted(sched.free) == list(range(sched.rows))
    assert sched.alloc.free_count == sched.num_pages, "leaked pages"
    assert int(sched.alloc.pinned.sum()) == 0, "leaked pins"
    assert any(h["violated"] for h in ctl.history)
    assert any(h["healthy"] for h in ctl.history)
