"""Sampling keys derived on the device (serving/sampler.py ``RowStreams``):
the fused tick's sampler program advances each active request's stream
in a device table and derives every row's key itself. Over many ticks
of a paged scheduler the keys, the advanced streams and the tokens are
bitwise what ``RequestState.step_keys`` gives on a host copy of each
stream — through fan-out 1 and 5, KAPPA compaction, preemption with
replay, and raw and typed submission keys; the table is refused when
``jax_threefry_partitionable`` is off; and a fused tick makes one
sampler dispatch and one blocking transfer, with no per-request key
work, whatever the number of active requests."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tokenizer as tok
from repro.models import init_params
from repro.serving import engine, sampler, strategies
from repro.serving.scheduler import PagedScheduler


@pytest.fixture(scope="module")
def setup():
    cfg = get_config("deepseek-r1-distill-qwen-1.5b").reduced(
        num_layers=2, d_model=64, vocab_size=tok.VOCAB_SIZE)
    params = init_params(jax.random.PRNGKey(0), cfg)
    kcfg = KappaConfig(num_branches=5, max_new_tokens=16, max_cutoff=4,
                       horizon=6, window=8, mom_buckets=4)
    prompts = [np.array([tok.BOS, tok.PROB, 3 + i % 6, tok.PLUS, 4 + i % 3,
                         tok.EQ, tok.QM]) for i in range(8)]
    return cfg, params, kcfg, prompts


class _HostStream:
    """What ``step_keys`` reads of a request: its stream and live rows."""

    def __init__(self, rng, n):
        self.rng, self.branch_ids = rng, np.arange(n)


def _watch_keys(sched, monkeypatch):
    """Check every fused sampler call of ``sched`` against host copies
    of the active requests' streams, taken at activation; returns the
    list of checked calls (one entry of active-request count each)."""
    host = {}
    orig_activate = sched._activate

    def activate(rid, rs, slots):
        host[rid] = rs.rng          # the stream as it enters the table
        orig_activate(rid, rs, slots)

    monkeypatch.setattr(sched, "_activate", activate)
    orig = sampler.sample_rows
    checked = []

    def sample_rows(keys, logits, greedy_mask, kcfg, **kw):
        if not isinstance(keys, sampler.RowStreams):
            return orig(keys, logits, greedy_mask, kcfg, **kw)
        table = np.asarray(keys.table)
        ops = [np.asarray(a) for a in keys[1:]]
        want_keys = np.zeros((sched.rows, 2), np.uint32)
        want_next = {}
        for rid, (rs, slots) in sched.active.items():
            st = _HostStream(host[rid], len(slots))
            want_keys[slots] = np.asarray(
                strategies.RequestState.step_keys(st))
            host[rid] = st.rng
            want_next[sched._stream_of[rid]] = np.asarray(
                strategies.raw_key(st.rng))
        live = np.zeros((sched.rows,), bool)
        for _, slots in sched.active.values():
            live[slots] = True
        # the derivation the program runs, on a copy of its operands
        _, got_keys = sampler._stream_keys(table, *ops)
        assert np.array_equal(np.asarray(got_keys)[live], want_keys[live])
        toks, lp, new_table = orig(keys, logits, greedy_mask, kcfg, **kw)
        new_table = np.asarray(new_table)
        for s, want in want_next.items():
            assert np.array_equal(new_table[s], want)
        idle = ~ops[2]
        assert np.array_equal(new_table[idle], table[idle])
        # the program's tokens are those of the host keys
        ref = orig(jax.numpy.asarray(want_keys), logits, greedy_mask, kcfg,
                   **kw)
        ref_toks = ref[0] if kw.get("want_picked_lp") else ref
        assert np.array_equal(np.asarray(toks)[live],
                              np.asarray(ref_toks)[live])
        checked.append(len(sched.active))
        return toks, lp, new_table

    monkeypatch.setattr(sampler, "sample_rows", sample_rows)
    return checked


@pytest.mark.parametrize("typed", [False, True], ids=["prngkey", "key"])
def test_device_keys_match_host_streams(setup, monkeypatch, typed):
    """Fan-out 5 (kappa, compacting; bon, reading picked log-probs) and
    fan-out 1 (greedy) side by side in a page-starved pool, so lazy
    growth preempts and replays: every tick's keys and streams match
    the host copies, and every request serves the sequential engine's
    tokens."""
    cfg, params, kcfg, prompts = setup
    make = jax.random.key if typed else jax.random.PRNGKey
    methods = ["kappa", "greedy", "bon", "kappa", "greedy", "kappa"]
    sched = PagedScheduler(params, cfg, kcfg, rows=12, max_seq=32,
                           page_size=4, num_pages=40, method="kappa",
                           eos_id=tok.EOS, bos_id=tok.BOS, prefill_chunk=4)
    checked = _watch_keys(sched, monkeypatch)
    rids = [sched.submit(p, make(100 + i), method=m)
            for i, (p, m) in enumerate(zip(prompts, methods))]
    res = sched.run()
    assert len(checked) == sched.counters["sampler_dispatches"] > 0
    assert max(checked) >= 3
    assert sched.counters["preemptions"] >= 1
    assert any(res[r].compactions for r, m in zip(rids, methods)
               if m == "kappa")
    for i, (rid, m) in enumerate(zip(rids, methods)):
        want = getattr(engine, f"generate_{m}")(
            params, cfg, kcfg, prompts[i], make(100 + i), eos_id=tok.EOS,
            bos_id=tok.BOS, max_seq=sched.max_seq)
        assert res[rid].tokens == want.tokens, m
        assert res[rid].logical_tokens == want.logical_tokens
    assert sorted(sched._free_streams) == list(range(sched.rows))
    assert not sched._stream_adv.any() and sched._n_want_lp == 0


def test_stream_table_needs_partitionable_threefry(setup):
    """With ``jax_threefry_partitionable`` off a key of ``split(k, n)``
    depends on ``n``, so the fused scheduler refuses to build its
    stream table; the host-keyed path needs no such flag."""
    cfg, params, kcfg, _ = setup
    was = jax.config.jax_threefry_partitionable
    jax.config.update("jax_threefry_partitionable", False)
    try:
        with pytest.raises(RuntimeError, match="jax_threefry_partitionable"):
            PagedScheduler(params, cfg, kcfg, rows=8, max_seq=32,
                           page_size=4, eos_id=tok.EOS, bos_id=tok.BOS)
        PagedScheduler(params, cfg, kcfg, rows=8, max_seq=32, page_size=4,
                       eos_id=tok.EOS, bos_id=tok.BOS, fused_sampling=False)
    finally:
        jax.config.update("jax_threefry_partitionable", was)


@pytest.mark.parametrize("n_requests", [1, 8])
def test_one_sampler_dispatch_and_one_transfer_per_tick(setup, monkeypatch,
                                                        n_requests):
    """A fault-free fused tick: one sampler dispatch and one blocking
    transfer, and no ``step_keys`` call beyond each admission's first
    tokens — with one active request or eight."""
    cfg, params, kcfg, prompts = setup
    calls = {"step_keys": 0}
    orig_step_keys = strategies.RequestState.step_keys

    def step_keys(self):
        calls["step_keys"] += 1
        return orig_step_keys(self)

    monkeypatch.setattr(strategies.RequestState, "step_keys", step_keys)
    sched = PagedScheduler(params, cfg, kcfg, rows=40, max_seq=32,
                           page_size=4, method="kappa", eos_id=tok.EOS,
                           bos_id=tok.BOS)
    for i in range(n_requests):
        sched.submit(prompts[i], jax.random.PRNGKey(i))
    peak = decode_ticks = 0
    while sched.has_work:
        before = (dict(sched.counters), sampler.DISPATCHES["sample_rows"],
                  calls["step_keys"])
        sched.tick()
        c, disp, keys = before
        started = calls["step_keys"] - keys        # first_tokens calls
        if sched.counters["sampler_dispatches"] == c["sampler_dispatches"]:
            assert sched.counters["host_syncs"] == c["host_syncs"]
            continue
        decode_ticks += 1
        peak = max(peak, len(sched.active))
        assert sched.counters["sampler_dispatches"] \
            == c["sampler_dispatches"] + 1
        assert sched.counters["host_syncs"] == c["host_syncs"] + 1
        assert sampler.DISPATCHES["sample_rows"] == disp + 1 + started
    assert decode_ticks > 0 and peak == n_requests
    assert calls["step_keys"] == n_requests
    assert sched.counters["host_syncs"] == decode_ticks
