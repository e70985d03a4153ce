"""Request lifecycle & fault handling (DESIGN.md §8): cancellation in
every lifecycle state, Unservable rejection, deadline/tick-budget
timeouts, bounded-queue shedding, fault-retry quarantine, FaultPlan
determinism, and the pooled controller's finite-guard."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.core import kappa as K
from repro.core import signals
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving.faults import FaultPlan, InjectedStepFault, parse_fault_spec
from repro.serving.scheduler import PagedScheduler, Unservable

MAX_SEQ = 32
PAGE_SIZE = 4
ROWS = 8
TERMINAL = {"OK", "CANCELLED", "TIMEOUT", "FAILED", "SHED"}
# pool configurations, by parameter id: one-shot admission; chunked
# admission; chunked admission with the radix prefix cache, so that
# teardown on cancel, timeout and fault publishes prompt pages
MODES = {
    "paged-oneshot": {},
    "paged-chunked": {"prefill_chunk": 4},
    "paged+prefix": {"prefill_chunk": 4, "prefix_cache": True},
}


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


def _mk(setup, mode, **kw):
    cfg, params, kcfg = setup
    base = dict(rows=ROWS, max_seq=MAX_SEQ, method="kappa",
                eos_id=tok.EOS, bos_id=tok.BOS, page_size=PAGE_SIZE,
                num_pages=ROWS * MAX_SEQ // PAGE_SIZE, **MODES[mode])
    base.update(kw)
    return PagedScheduler(params, cfg, kcfg, **base)


def _assert_no_leaks(sched):
    assert sorted(sched.free) == list(range(sched.rows))
    assert not sched.active and not sched.prefilling and not sched.queue
    if sched.pcache is not None:
        sched.pcache.drop()
    assert sched.alloc.free_count == sched.num_pages, "leaked pages"
    assert int(sched.alloc.pinned.sum()) == 0, "leaked pins"


# ------------------------------------------------------------- cancel

@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_cancel_queued(setup, mode):
    sched = _mk(setup, mode)
    r0 = sched.submit(_prompt(0), jax.random.PRNGKey(0))
    r1 = sched.submit(_prompt(1), jax.random.PRNGKey(1))
    res1 = sched.cancel(r1)          # never admitted: no partial tokens
    assert res1.status == "CANCELLED" and res1.tokens == []
    assert res1.chosen_branch == -1
    assert sched.cancel(r1) is res1  # idempotent once terminal
    with pytest.raises(KeyError):
        sched.cancel(999)
    out = sched.run()
    assert out[r0].status == "OK" and out[r1].status == "CANCELLED"
    assert sched.counters["cancelled"] == 1
    assert sched.throughput()["status_counts"] == {"OK": 1, "CANCELLED": 1}
    _assert_no_leaks(sched)


@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_cancel_active_returns_partial_tokens(setup, mode):
    sched = _mk(setup, mode)
    rid = sched.submit(_prompt(0), jax.random.PRNGKey(0), method="greedy",
                       max_new=12)
    for _ in range(5):
        sched.tick()
    assert rid in sched.active
    res = sched.cancel(rid)
    assert res.status == "CANCELLED"
    assert 0 < res.steps < 12           # truncated, not complete
    # partial decode came back: prefill's sampled token + one per tick
    assert len(res.tokens) == res.steps + 1
    assert sched.run()[rid] is res
    _assert_no_leaks(sched)


@pytest.mark.parametrize("mode", ["paged+prefix", "paged-chunked"])
def test_cancel_mid_prefill(setup, mode):
    sched = _mk(setup, mode, prefill_chunk=2)
    rid = sched.submit(_prompt(0, plen=7), jax.random.PRNGKey(0))
    sched.tick()                        # admits; 7-token prompt > one chunk
    assert rid in sched.prefilling
    res = sched.cancel(rid)
    assert res.status == "CANCELLED" and res.tokens == []
    sched.run()
    _assert_no_leaks(sched)


@pytest.mark.parametrize("mode", ["paged-chunked", "paged+prefix"])
def test_cancellation_storm_zero_leak(setup, mode):
    """Cancel everything — queued, PREFILLING, active — mid-flight; the
    pool must come back empty with every page/pin/slot returned."""
    sched = _mk(setup, mode, prefill_chunk=3)
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i))
            for i in range(6)]
    for _ in range(3):
        sched.tick()
    for rid in rids:
        res = sched.cancel(rid)
        assert res.status in ("CANCELLED", "OK")
    out = sched.run()
    assert set(out) == set(rids)
    assert all(out[r].status in TERMINAL for r in rids)
    _assert_no_leaks(sched)


# --------------------------------------------------------- unservable

class _WideFanOut:
    """Strategy stub whose fan-out can never fit the pool."""

    def rows(self, kcfg):
        return ROWS + 1


def test_unservable_is_typed_and_early(setup):
    sched = _mk(setup, "paged-oneshot")
    assert issubclass(Unservable, ValueError)   # old callers keep working
    with pytest.raises(Unservable, match="max_seq"):
        sched.submit(_prompt(0, plen=MAX_SEQ), jax.random.PRNGKey(0))
    with pytest.raises(Unservable, match="rows"):
        sched.submit(_prompt(0), jax.random.PRNGKey(0),
                     strategy_factory=_WideFanOut)
    assert not sched.queue              # rejected at the door, not queued


def test_unservable_paged_page_budget(setup):
    cfg, params, kcfg = setup
    sched = PagedScheduler(params, cfg, kcfg, rows=ROWS, max_seq=MAX_SEQ,
                           page_size=PAGE_SIZE, num_pages=6, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS)
    with pytest.raises(Unservable, match="pages"):
        sched.submit(_prompt(0, plen=8), jax.random.PRNGKey(0))
    assert not sched.queue              # rejected at the door, not queued


# ----------------------------------------------------------- deadlines

@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_tick_budget_truncates_active(setup, mode):
    sched = _mk(setup, mode)
    rid = sched.submit(_prompt(0), jax.random.PRNGKey(0), method="greedy",
                       max_new=12, max_wall_ticks=4)
    out = sched.run()
    res = out[rid]
    assert res.status == "TIMEOUT"
    assert 0 < res.steps < 12           # truncate-and-return kept partials
    assert sched.counters["timeouts"] == 1
    _assert_no_leaks(sched)


@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_tick_budget_expires_queued(setup, mode):
    # 4-row pool: the kappa request (fan-out 4) saturates it, the queued
    # greedy request's one-tick budget expires before it can admit
    sched = _mk(setup, mode, rows=4)
    r0 = sched.submit(_prompt(0), jax.random.PRNGKey(0))
    r1 = sched.submit(_prompt(1), jax.random.PRNGKey(1), method="greedy",
                      max_wall_ticks=1)
    out = sched.run()
    assert out[r0].status == "OK"
    assert out[r1].status == "TIMEOUT" and out[r1].tokens == []
    assert sorted(sched.free) == list(range(4))


def test_wall_clock_deadline_truncates_active(setup, fake_clock):
    """Deadline crossing is observed through the injectable clock — no
    real sleeping: decode a few ticks, jump time past the deadline, and
    the next tick's watchdog truncates with the partial tokens kept."""
    sched = _mk(setup, "paged+prefix", clock=fake_clock)
    rid = sched.submit(_prompt(0), jax.random.PRNGKey(0), method="greedy",
                       max_new=12, deadline_s=5.0)
    for _ in range(3):
        sched.tick()
    assert rid in sched.active
    fake_clock.advance(6.0)              # cross the deadline, zero wall time
    sched.tick()
    res = sched.results[rid]
    assert res.status == "TIMEOUT"
    assert 0 < res.steps < 12
    assert len(res.tokens) == res.steps + 1   # truncate-and-return
    assert sched.counters["timeouts"] == 1
    _assert_no_leaks(sched)


@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_wall_clock_deadline_expires_queued(setup, mode, fake_clock):
    # 4-row pool: the kappa request saturates it; the queued greedy
    # request's wall deadline expires (via the fake clock) before a row
    # frees up, so the watchdog sheds it from the queue with no tokens
    sched = _mk(setup, mode, rows=4, clock=fake_clock)
    r0 = sched.submit(_prompt(0), jax.random.PRNGKey(0))
    r1 = sched.submit(_prompt(1), jax.random.PRNGKey(1), method="greedy",
                      deadline_s=2.0)
    sched.tick()
    assert r0 in sched.active or r0 in sched.prefilling
    fake_clock.advance(3.0)
    sched.tick()
    assert sched.results[r1].status == "TIMEOUT"
    assert sched.results[r1].tokens == []
    out = sched.run()
    assert out[r0].status == "OK"
    _assert_no_leaks(sched)


# ---------------------------------------------------------------- shed

@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_bounded_queue_sheds(setup, mode):
    sched = _mk(setup, mode, max_queue=2)
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i), method="greedy")
            for i in range(3)]
    assert rids[2] in sched.results     # shed at submit time, terminal
    assert sched.results[rids[2]].status == "SHED"
    assert sched.counters["shed"] == 1
    out = sched.run()
    assert out[rids[0]].status == "OK" and out[rids[1]].status == "OK"
    sc = sched.throughput()["status_counts"]
    assert sc == {"OK": 2, "SHED": 1}
    _assert_no_leaks(sched)


# --------------------------------------------------- retry / quarantine

@pytest.mark.faults
@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_step_fault_quarantine_after_max_retries(setup, mode):
    """A permanently-faulting device step burns each request's retry
    budget and quarantines it as FAILED — the pool never wedges."""
    plan = FaultPlan(seed=0, p_step=1.0, p_alloc=0.0, p_nan=0.0)
    sched = _mk(setup, mode, faults=plan, max_retries=1, retry_backoff=1)
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i), method="greedy")
            for i in range(2)]
    out = sched.run()
    for rid in rids:
        assert out[rid].status == "FAILED"
        assert out[rid].tokens == []    # post-fault state is suspect
        assert out[rid].n_retries == 1
    assert sched.counters["failures"] == 2
    assert sched.counters["retries"] == 2
    assert sched.counters["faults_injected"] > 0
    _assert_no_leaks(sched)


@pytest.mark.faults
@pytest.mark.parametrize("mode", ["paged+prefix", "paged-oneshot"])
def test_nan_fault_replay_token_equal(setup, mode):
    """NaN-poisoned rows are torn down and replayed from the original
    submission RNG: the survivors' tokens match a fault-free run."""
    clean = _mk(setup, mode)
    rids_c = [clean.submit(_prompt(i), jax.random.PRNGKey(i))
              for i in range(3)]
    ref = clean.run()
    plan = FaultPlan(seed=11, p_step=0.0, p_alloc=0.0, p_nan=0.4,
                     nan_rows=2, max_faults=4)
    sched = _mk(setup, mode, faults=plan, max_retries=8)
    rids = [sched.submit(_prompt(i), jax.random.PRNGKey(i))
            for i in range(3)]
    out = sched.run()
    assert sched.counters["retries"] > 0, "the plan never fired — tune it"
    for rc, rf in zip(rids_c, rids):
        assert out[rf].status == "OK"
        assert out[rf].tokens == ref[rc].tokens
        assert out[rf].chosen_branch == ref[rc].chosen_branch
    _assert_no_leaks(sched)


# ------------------------------------------------------------ FaultPlan

def test_fault_plan_deterministic_and_memoized():
    a = FaultPlan(seed=7)
    b = FaultPlan(seed=7)
    sched_a = [(a.step_fault(t), a.page_holdback(t),
                a.nan_rows_for(t, 8).tolist()) for t in range(60)]
    sched_b = [(b.step_fault(t), b.page_holdback(t),
                b.nan_rows_for(t, 8).tolist()) for t in range(60)]
    assert sched_a == sched_b           # pure function of (seed, site, tick)
    assert any(x or y or z for x, y, z in sched_a), "defaults too quiet"
    # re-consulting a tick replays the memo without re-counting
    fired = a.fired
    assert [(a.step_fault(t), a.page_holdback(t),
             a.nan_rows_for(t, 8).tolist()) for t in range(60)] == sched_a
    assert a.fired == fired
    # a different seed gives a different schedule
    c = FaultPlan(seed=8)
    assert sched_a != [(c.step_fault(t), c.page_holdback(t),
                        c.nan_rows_for(t, 8).tolist()) for t in range(60)]


def test_fault_plan_max_faults_cap():
    plan = FaultPlan(seed=1, p_step=1.0, p_alloc=1.0, p_nan=1.0,
                     max_faults=5)
    for t in range(50):
        plan.step_fault(t)
        plan.page_holdback(t)
        plan.nan_rows_for(t, 8)
    assert plan.fired == 5
    assert not plan.step_fault(100)     # quiet once the cap is spent


def test_parse_fault_spec():
    plan = parse_fault_spec("seed:7,step:0.1,alloc:0.2,nan:0.05,"
                            "holdback:4,rows:3,max:20")
    assert (plan.seed, plan.p_step, plan.p_alloc, plan.p_nan) \
        == (7, 0.1, 0.2, 0.05)
    assert (plan.holdback, plan.nan_rows, plan.max_faults) == (4, 3, 20)
    assert parse_fault_spec("seed:3").seed == 3
    with pytest.raises(ValueError, match="seed"):
        parse_fault_spec("step:0.5")
    with pytest.raises(ValueError, match="bad fault spec"):
        parse_fault_spec("seed:7,bogus:1")
    assert issubclass(InjectedStepFault, RuntimeError)


# ------------------------------------------------- kappa finite-guard

def _guard_cfg(**kw):
    base = dict(num_branches=4, adaptive_cutoff=False, draft_cutoff=1,
                horizon=8, window=8, mom_buckets=4, max_new_tokens=64)
    base.update(kw)
    return KappaConfig(**base)


def _state_after(steps, logits, cfg, state=None):
    log_q = signals.reference_log_q(jnp.zeros(64))
    state = K.init_state(cfg) if state is None else state
    for _ in range(steps):
        state = K.kappa_step(state, logits, jnp.arange(4, dtype=jnp.int32),
                             log_q, cfg)
    return state


def test_finite_guard_kills_poisoned_branch_only():
    cfg = _guard_cfg()
    clean = jax.random.normal(jax.random.PRNGKey(2), (4, 64))
    state = _state_after(3, clean, cfg)
    poisoned = clean.at[2].set(jnp.nan)
    nxt = _state_after(1, poisoned, cfg, state)
    assert not bool(nxt.alive[2]), "poisoned branch must be pruned"
    # the poison never reaches sibling statistics: every state leaf
    # stays finite, and decoding can continue cleanly afterwards
    for leaf in jax.tree.leaves(nxt):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.all(np.isfinite(arr))
    cont = _state_after(3, clean, cfg, nxt)
    assert int(K.num_alive(cont)) >= 1
    for leaf in jax.tree.leaves(cont):
        arr = np.asarray(leaf)
        if np.issubdtype(arr.dtype, np.floating):
            assert np.all(np.isfinite(arr))


def test_finite_guard_never_kills_everyone():
    cfg = _guard_cfg()
    state = _state_after(
        3, jax.random.normal(jax.random.PRNGKey(2), (4, 64)), cfg)
    all_bad = jnp.full((4, 64), jnp.nan)
    nxt = _state_after(1, all_bad, cfg, state)
    # an all-poisoned step cannot prune the request to zero branches —
    # the guard falls back to the pre-guard alive set
    assert int(K.num_alive(nxt)) >= 1


def test_finite_guard_applies_during_draft():
    """The kill is outside the gating window: a branch poisoned while
    the controller is still drafting (no pruning yet) dies immediately
    instead of contributing NaN history to later scoring steps."""
    cfg = _guard_cfg(draft_cutoff=6)
    clean = jax.random.normal(jax.random.PRNGKey(4), (4, 64))
    state = _state_after(2, clean, cfg)         # still in draft
    assert int(K.num_alive(state)) == 4
    nxt = _state_after(1, clean.at[1].set(jnp.inf), cfg, state)
    assert not bool(nxt.alive[1])
    assert int(K.num_alive(nxt)) == 3
