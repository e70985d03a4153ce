"""The continuous-batching multi-request scheduler (DESIGN.md §4–§5).

The sequential engine serves one prompt at a time: N branch rows, pruned
to 1 by KAPPA/ST-BoN, then a long single-row tail to EOS — poor device
utilization exactly when pruning succeeds. :class:`PagedScheduler`
turns freed capacity into throughput, the serving-level payoff the
early-pruning papers point at (ST-BoN, Wang et al. 2025; Bi et al.
2025). Its KV lives in one paged pool (DESIGN.md §5): global attention
layers share a page pool, rows hold ``(max_pages,)`` block tables,
fan-out branches share the prompt pages copy-on-write, decode pages are
allocated lazily at page-boundary crossings (with youngest-admitted
preemption when the pool runs dry), pruning drops page references the
moment it happens, and queued requests are admitted shortest-job-first
with bounded bypass among those that fit. With ``prefix_cache=True`` a
cross-request radix tree (DESIGN.md §7) pins completed requests'
prompt/winner pages so later admissions alias them and prefill only the
uncached tail; under pressure, least-recently-hit cached pages are
evicted before any request is preempted.

Per tick:

  * admit whatever fits. With ``prefill_chunk`` set, admission enters a
    **PREFILLING** state (DESIGN.md §6): the request owns its row slots
    and the pages written so far and advances one prompt chunk per tick
    — inside the fused decode dispatch itself — so decode rows never
    stall for more than one chunk's latency on a long-prompt admission;
    the final chunk's logits are bitwise-equal to the one-shot prefill
    and feed the same strategy start path. Without chunking (or for
    frontend/enc-dec requests) admission falls back to a one-shot
    batch-1 prefill through a transient cache sized to the prompt, whose
    prompt pages are aliased copy-on-write across the N branch block
    tables;
  * one fused decode step over the whole pool with per-row positions;
  * ONE fused sampler dispatch for every active request's rows
    (per-row RNG keys — :func:`repro.serving.sampler.sample_rows`).
    Each active request's RNG stream lives in a device table while it
    decodes, and the sampler program derives every row's key from it,
    so the tick has no per-request key work on the host;
  * ONE pooled KAPPA-controller dispatch for every active kappa request
    (:class:`repro.serving.strategies.PooledKappaController`): the
    stacked controller state consumes the pool logits and just-sampled
    tokens device-to-device, and its alive/traj/cutoff outputs ride the
    tick's single blocking transfer (dispatch/sync counters in
    ``counters`` assert the ≤1-per-tick contract; ``tick_time`` records
    host seconds per tick phase, each also a ``serve.<phase>`` profiler
    span — ``serving/spans.py``);
  * per-request strategies (repro.serving.strategies) drive pruning and
    compaction decisions on their own row groups (host-side, from the
    published controller mirrors); freed capacity is backfilled by
    queued prefills on the next tick;
  * per-request ``GenResult``s emitted on completion with the same
    accounting as sequential serving. ``submit(..., method=...)`` lets
    one pool serve mixed kappa/bon/stbon/greedy traffic with
    per-request ``max_new``.

Equivalence guarantee: the batched decode step is row-independent, the
per-row-keyed sampler is row-independent, and the host-side per-request
logic is shared verbatim with the engine loop — so with the same
per-request keys and the same ``max_seq`` the scheduler reproduces the
sequential engine token for token (tests/test_scheduler.py,
tests/test_paged.py).

Request lifecycle (DESIGN.md §8): every submission reaches exactly one
terminal status — ``OK`` (normal completion), ``CANCELLED``
(:meth:`cancel` from any state, partial tokens returned), ``TIMEOUT``
(per-request ``deadline_s`` / ``max_wall_ticks`` watchdog,
truncate-and-return), ``FAILED`` (quarantined after ``max_retries``
fault-triggered replays), or ``SHED`` (bounded admission queue
overflowed at submit time). Injected faults (``serving.faults``) are
answered with the preemption-replay machinery: tear down, requeue with
exponential backoff, replay token-for-token from the original
submission RNG.

Streaming surface (DESIGN.md §9): every tick emits :class:`TokenEvent`s
through ``event_sink`` (or collects them per-:meth:`step` call) — one
``kind="token"`` event per newly *committed* generated token (a token
whose membership in the final output can no longer change, per the
strategy's ``decided_branch``) and exactly one ``kind="end"`` terminal
event per submission, carrying the ``GenResult``. All wall-clock reads
(submit stamps, deadlines, TTFT/ITL stamps, run elapsed) go through the
injectable ``clock=`` callable (default ``time.monotonic``) so latency
behaviour is testable without sleeping; retry backoff stays tick-counted
and needs no clock. :meth:`snapshot` reads the per-window TTFT/ITL
percentiles and goodput counters the SLO controller (``serving.slo``)
consumes.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import KappaConfig, ModelConfig
from repro.models import decode_step, init_cache, init_paged_cache
from repro.serving import cache as cache_lib
from repro.serving import engine
from repro.serving import faults as faults_lib
from repro.serving import sampler
from repro.serving import spans
from repro.serving import strategies
from repro.serving.strategies import GenResult


class Unservable(ValueError):
    """Raised at ``submit()`` time for a request this scheduler can NEVER
    serve (too many positions, too much fan-out, worst-case pages beyond
    the whole pool) — as opposed to transient pressure, which queues.
    Subclasses ValueError so callers that guarded the old assertions
    keep working."""

_install_shared = jax.jit(cache_lib.install_paged_shared,
                          static_argnums=(0, 6), donate_argnums=(1,))
_paged_step = jax.jit(decode_step, static_argnums=(1,), donate_argnums=(4,))
_copy_pages = jax.jit(cache_lib.copy_pages, static_argnums=(0,),
                      donate_argnums=(1,))
_install_aux = jax.jit(cache_lib.install_rows_aux, static_argnums=(0,),
                       donate_argnums=(1,))
_put_stream = jax.jit(lambda table, s, key: table.at[s].set(key),
                      donate_argnums=(0,))


@dataclasses.dataclass
class _Queued:
    rid: int
    prompt: np.ndarray
    rng: object
    kcfg: KappaConfig          # per-request (max_new may be overridden)
    need: int                  # prompt + n_prefix + max_new token slots
    fan_out: int
    factory: Callable[[], strategies.DecodeStrategy]  # per-request strategy
    bypasses: int = 0          # times a younger request was admitted first
    deadline_s: Optional[float] = None   # wall-clock budget from submit
    max_wall_ticks: Optional[int] = None  # tick budget from submit
    n_retries: int = 0         # fault-triggered replays so far
    not_before: int = 0        # backoff: earliest tick for re-admission
    submit_tick: int = 0       # tick at submission (max_wall_ticks base)


@dataclasses.dataclass
class _Prefill:
    """A request in the PREFILLING state (DESIGN.md §6): it owns its row
    slots and the pages written so far through slot[0]'s block table,
    and advances one prompt chunk per tick inside the same scheduler
    tick as the active decode rows."""
    item: _Queued
    slots: List[int]
    filled: int = 0            # prompt tokens written so far
    aux: object = None         # batch-1 per-row-family state


@dataclasses.dataclass
class TokenEvent:
    """One streaming event for one request (DESIGN.md §9).

    ``kind="token"``: one committed generated token (``token`` /
    ``index`` — indices are strictly increasing per rid and match the
    final ``GenResult.tokens`` positions). ``kind="end"``: the terminal
    event, exactly one per submission, carrying ``status`` and the full
    ``result``; ``index`` is the total token count. ``t`` is a
    scheduler-clock stamp."""
    rid: int
    kind: str                              # "token" | "end"
    t: float
    index: int = 0
    token: Optional[int] = None
    status: Optional[str] = None           # terminal status on "end"
    result: Optional[GenResult] = None


class PagedScheduler:
    """The serving scheduler (DESIGN.md §4–§5): the request queue, the
    row-slot lifecycle and the fused tick over one paged KV pool.

    Global-attention KV lives in a shared page pool; each row addresses
    it through a ``(max_pages,)`` block table. Fan-out branches *share*
    the fully-written prompt pages copy-on-write: admission allocates
    them once, aliases them into all N branch tables, and gives each
    branch a private copy of the partially-written boundary page (where
    divergent decode writes land) plus one decode page — so admission
    costs ``prompt_pages + N × (1 + boundary)`` pages instead of
    ``N × ceil(need / page_size)``. Decode pages are acquired *lazily*,
    one page per row as its position crosses a page boundary; when the
    free list runs dry the scheduler preempts the youngest-admitted
    request (pages freed, request requeued and replayed from its
    original RNG — token-for-token identical to an un-preempted run)
    instead of deadlocking. Pruning a branch drops its page references
    immediately; a page returns to the free heap when its last
    reference goes.

    Queued requests are admitted shortest-job-first among those whose
    rows *and* initial pages fit (FIFO tie-break on equal need), with
    bounded bypass: once the queue head has been bypassed
    ``max_bypass`` times, it is admitted next or nothing is — a steady
    stream of short submissions can no longer starve a long request.

    Parameters
    ----------
    rows : row slots (block tables / position vector entries). Must be
        >= the fan-out of a single request (``strategy.rows(kcfg)``).
    max_seq : upper bound on any request's ``prompt + n_prefix + max_new``
        (rounded up to a page multiple internally).
    page_size : token slots per page, the allocation unit. The paged
        kernel sizes its tile of pages from a VMEM budget, not from this.
    num_pages : allocatable pages in the pool — the real memory knob.
        Defaults to ``rows * max_seq / page_size`` (no page pressure);
        set lower to serve more rows than reserving ``max_seq`` slots
        for every row would allow.
    page_budget_bytes : alternative memory knob — an HBM byte budget
        for the global-layer page pool, converted to ``num_pages`` via
        allocator-truth :func:`cache.page_bytes` (so an int8
        ``kv_cache_dtype`` yields ≈2× the pages of fp32/bf16 under the
        same budget). Mutually exclusive with ``num_pages``.
    method : one of "greedy" | "bon" | "stbon" | "kappa"; or pass
        ``strategy_factory`` for custom construction (e.g. ST-BoN with a
        non-default buffer window).
    max_bypass : SJF aging bound (see above).
    prefix_cache : enable the cross-request radix prefix cache
        (DESIGN.md §7). Completed/preempted requests publish their
        fully-written prompt pages (and the winner's generated prefix)
        into a radix tree that pins them in the allocator; later
        admissions alias every matched page and chunk-prefill only the
        uncached tail. Requires chunked admission (``prefill_chunk``)
        and an all-global layer pattern — anything else silently keeps
        the cache off (aux ring/recurrent state cannot be recovered
        from pages, and only the chunked path can resume a prefill at a
        nonzero offset).
    """

    def __init__(self, params, cfg: ModelConfig, kcfg: KappaConfig, *,
                 rows: int, max_seq: int, page_size: int = 64,
                 num_pages: Optional[int] = None, method: str = "kappa",
                 eos_id: int, bos_id: int = 0, frontend=None,
                 strategy_factory: Optional[Callable[
                     [], strategies.DecodeStrategy]] = None,
                 max_bypass: int = 4, prefill_chunk: Optional[int] = None,
                 prefix_cache: bool = False,
                 faults: Optional[faults_lib.FaultPlan] = None,
                 max_retries: int = 3, retry_backoff: int = 2,
                 max_queue: Optional[int] = None,
                 clock: Optional[Callable[[], float]] = None,
                 event_sink: Optional[Callable[[TokenEvent], None]] = None,
                 page_budget_bytes: Optional[int] = None):
        max_seq = -(-max_seq // page_size) * page_size
        if page_budget_bytes is not None:
            if num_pages is not None:
                raise ValueError("pass num_pages or page_budget_bytes, "
                                 "not both")
            num_pages = page_budget_bytes \
                // cache_lib.page_bytes(cfg, page_size)
            if num_pages < 1:
                raise ValueError(
                    f"page_budget_bytes={page_budget_bytes} below one "
                    f"page ({cache_lib.page_bytes(cfg, page_size)}B)")
        self.params = params
        self.cfg = cfg
        self.kcfg = kcfg
        self.rows = rows
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.bos_id = bos_id
        self.frontend = frontend
        self.strategy_factory = strategy_factory or (
            lambda: strategies.make_strategy(method))
        self.n_prefix = engine._n_prefix(cfg)

        need = self.strategy_factory().rows(kcfg)
        if rows < need:
            raise ValueError(f"pool rows={rows} < request fan-out {need}")
        if cfg.is_moe and cfg.moe_capacity_factor > 0:
            # capacity-limited MoE routing drops tokens *per batch*, so
            # pool rows are not independent: one request's rows (and the
            # free rows' garbage tokens) would contend for expert capacity
            # with another's, breaking the equivalence guarantee. Dropless
            # routing (capacity_factor <= 0) is exact and row-independent.
            raise ValueError(
                "continuous batching requires dropless MoE routing "
                "(cfg.moe_capacity_factor <= 0): capacity-limited dispatch "
                "couples pool rows across requests")

        if prefill_chunk is not None and prefill_chunk < 1:
            raise ValueError(f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = prefill_chunk
        # chunked admission needs a chunkable token stream; frontend /
        # enc-dec requests keep the one-shot prefill path
        self._chunked_ok = (prefill_chunk is not None
                            and engine.chunkable(cfg, frontend))
        self.row_token = np.zeros((rows,), np.int32)
        self.row_pos = np.zeros((rows,), np.int32)
        self.free: List[int] = list(range(rows))
        self.queue: deque = deque()          # _Queued items
        self.prefilling: Dict[int, _Prefill] = {}  # rid -> PREFILLING state
        self._fused_rids: List[int] = []     # chunks riding this tick's
        self._fused_chunk_out = None         # fused decode dispatch
        self.active: Dict[int, tuple] = {}   # rid -> (RequestState, slots)
        self._slots_dev: Dict[int, object] = {}  # rid -> device slot idx
        # each active request's RNG stream lives in one slot of a device
        # table (raw threefry key data) while it decodes; the sampler
        # program advances it and derives the row keys. The per-row
        # operands change only when rows do.
        sampler.check_partitionable()
        self._streams = jnp.zeros((rows, 2), jnp.uint32)
        self._free_streams: List[int] = list(range(rows))
        self._stream_of: Dict[int, int] = {}  # rid -> stream slot
        self._stream_adv = np.zeros((rows,), bool)
        self._row_stream = np.zeros((rows,), np.int32)
        self._row_branch = np.zeros((rows,), np.int32)
        self._row_greedy = np.ones((rows,), bool)  # free rows: argmax
        self._n_want_lp = 0                  # active requests reading lp
        self._items: Dict[int, _Queued] = {}  # rid -> original submission
        self._admit_seq: Dict[int, int] = {}  # rid -> admission order
        self._admit_counter = 0
        self.results: Dict[int, GenResult] = {}
        self._next_rid = 0
        self.ticks = 0
        self._occupied_ticks = 0             # Σ occupied rows over ticks
        # pooled KAPPA controller (lazily built on first kappa admission;
        # shared by every kappa request whose controller-relevant kcfg
        # matches — per-request max_new overrides still share it)
        self._kappa_pool: Optional[strategies.PooledKappaController] = None
        self._ctrl_key = strategies.controller_key(kcfg)
        # dispatch / blocking-transfer counters (the batched-controller
        # contract: ≤1 controller dispatch and ≤1 controller-carrying
        # blocking transfer per tick, independent of active-request count)
        self.counters: Dict[str, int] = {
            "controller_dispatches": 0, "controller_syncs": 0,
            "sampler_dispatches": 0, "host_syncs": 0, "preemptions": 0,
            "retries": 0, "failures": 0, "cancelled": 0, "timeouts": 0,
            "shed": 0, "faults_injected": 0,
            "prefix_hits": 0, "prefix_misses": 0,
            "prefix_tokens_saved": 0, "prefix_evictions": 0,
            "fused_chunks": 0,
            # pages the decode kernel walks (each row's up to its
            # position) against its block-table slots (rows x max_pages)
            "kv_pages_live": 0, "kv_pages_table": 0,
        }
        # request-lifecycle hardening (DESIGN.md §8): fault plan, bounded
        # retry-with-backoff, and the bounded admission queue
        self.faults = faults
        self.max_retries = max_retries
        self.retry_backoff = retry_backoff
        self.max_queue = max_queue
        self._fault_tick = False     # an alloc embargo is live this tick
        self._has_deadlines = False  # sticky: any submit set a deadline
        # host seconds per tick phase, cumulative over the run; each
        # phase is also a ``serve.<phase>`` span in a profiler trace
        self.tick_time = spans.PhaseTimes()
        spans.watch_gc()
        # admission-side peak: bytes of the largest transient prefill
        # structure (prompt-sized side cache / chunked aux state) — the
        # regression knob for the old max_seq-sized throwaway cache
        self.admit_peak_bytes = 0
        # injectable monotonic clock: every user-visible latency read
        # (submit stamps, deadlines, TTFT/ITL, run elapsed) goes through
        # it so tests advance time without sleeping. The tick_time
        # phase spans keep the real host clock — they measure compute
        # cost, not request-visible latency.
        self.clock: Callable[[], float] = clock or time.monotonic
        # latency bookkeeping: submit walltime, time-to-first-token and
        # per-tick token emission stamps (ITL = consecutive diffs)
        self._submit_t: Dict[int, float] = {}
        self.ttft: Dict[int, float] = {}
        self.token_times: Dict[int, List[float]] = {}
        # streaming surface (DESIGN.md §9): per-event callback, per-step
        # capture list, and the per-rid count of already-emitted tokens
        self.event_sink = event_sink
        self._tick_events: Optional[List[TokenEvent]] = None
        self._streamed: Dict[int, int] = {}
        # SLO-controller admission knob: while True, _admit_one admits
        # nothing (queued work waits; the bounded queue still sheds at
        # the door) — serving.slo flips it per latency window
        self.admit_paused = False
        # admission pacing knob: at most this many NEW prompt tokens
        # enter PREFILLING per tick (None = unbounded). k same-tick
        # admissions each ride a full chunk through the fused dispatch,
        # k-fold inflating every active request's ITL for that tick —
        # the budget spreads bursts across ticks instead. Greedy-spend:
        # admission proceeds while budget remains, so the last admit may
        # overshoot by one prompt; a budget >= 1 always admits when idle.
        self.prefill_budget: Optional[int] = None
        self._admit_left: Optional[int] = None
        # windowed latency/goodput accounting read by snapshot()
        self._win_t0 = self.clock()
        self._win_tick0 = 0
        self._win_ttft: List[float] = []
        self._win_itl: List[float] = []
        self._win_counts = {"completed": 0, "ok": 0, "ok_tokens": 0,
                            "shed": 0}
        self.page_size = page_size
        self.max_pages = max_seq // page_size
        self.num_pages = num_pages if num_pages is not None \
            else rows * self.max_pages
        self.max_bypass = max_bypass
        self.alloc = cache_lib.PageAllocator(self.num_pages, page_size,
                                             rows, self.max_pages,
                                             fault_plan=self.faults)
        self.pool = init_paged_cache(cfg, rows, self.num_pages, page_size,
                                     max_seq)
        # radix prefix cache: only sound when every layer's KV is page-
        # resident (all-global) and admission can resume a prefill at
        # the cached extent (chunked)
        self.pcache: Optional[cache_lib.RadixPrefixCache] = None
        if prefix_cache and self._chunked_ok \
                and all(bt == "global" for bt in cfg.block_types()):
            self.pcache = cache_lib.RadixPrefixCache(self.alloc, page_size)
        self._page_ticks = 0                 # Σ pages in use over ticks
        self._page_peak = 0                  # max pages in use at any tick
        self._bt_dev = None                  # device block tables (cached)

    # ------------------------------------------ admission prefill sizing

    def _has_local(self) -> bool:
        return any(bt == "local" for bt in self.cfg.block_types())

    def _ring_window(self) -> int:
        """Pool rows' ring-cache window — the transient prefill cache
        must match it so ring layouts line up at install time."""
        return min(self.cfg.window_size, self.max_seq) \
            if self._has_local() else 0

    def _prefill_seq(self, item: _Queued) -> int:
        """Sequence capacity of the one-shot admission's transient
        prefill cache: the prompt itself (not max_seq), floored at the
        pool's ring window so ring layouts stay identical, and rounded
        up to whole pages for the install scatter."""
        s = max(len(item.prompt) + self.n_prefix, self._ring_window(), 1)
        return -(-s // self.page_size) * self.page_size

    # ------------------------------------------------------------ submit

    def submit(self, prompt: np.ndarray, rng, *,
               max_new: Optional[int] = None,
               method: Optional[str] = None,
               strategy_factory: Optional[Callable[
                   [], strategies.DecodeStrategy]] = None,
               deadline_s: Optional[float] = None,
               max_wall_ticks: Optional[int] = None) -> int:
        """Queue one prompt with its own RNG stream; returns request id.
        ``max_new`` overrides ``kcfg.max_new_tokens`` for this request
        (mixed-length serving — the pool sizes its reservation to the
        request's own need). ``method`` / ``strategy_factory``
        override the scheduler-level strategy for this request, so one
        pool can serve mixed kappa/bon/greedy/stbon traffic.

        ``deadline_s`` (wall-clock seconds from submission) and
        ``max_wall_ticks`` (scheduler ticks from submission — the
        deterministic twin for tests) bound the request's lifetime: the
        watchdog truncates it to a TIMEOUT result instead of raising.
        Raises :class:`Unservable` for a request no amount of waiting
        can serve; a full bounded queue (``max_queue``) sheds the
        request immediately with a SHED result instead."""
        kcfg = self.kcfg if max_new is None else dataclasses.replace(
            self.kcfg, max_new_tokens=max_new)
        need = len(prompt) + self.n_prefix + kcfg.max_new_tokens
        if need > self.max_seq:
            raise Unservable(
                f"prompt needs {need} positions > pool max_seq={self.max_seq}")
        if strategy_factory is None:
            strategy_factory = (self.strategy_factory if method is None
                                else lambda: strategies.make_strategy(method))
        fan_out = strategy_factory().rows(kcfg)
        if fan_out > self.rows:
            raise Unservable(
                f"request fan-out {fan_out} > pool rows={self.rows}")
        rid = self._next_rid
        self._next_rid += 1
        item = _Queued(rid, np.asarray(prompt), rng, kcfg, need, fan_out,
                       strategy_factory, deadline_s=deadline_s,
                       max_wall_ticks=max_wall_ticks,
                       submit_tick=self.ticks)
        self._check_servable(item)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # graceful overload degradation: reject at the door with a
            # terminal SHED result rather than queueing into certain
            # deadline misses (the admitted requests' ITL is protected)
            self.counters["shed"] += 1
            self._record_result(rid, self._empty_result(item, "SHED"))
            return rid
        if deadline_s is not None or max_wall_ticks is not None:
            self._has_deadlines = True
        self._submit_t.setdefault(rid, self.clock())
        self.queue.append(item)
        return rid

    # ------------------------------------------------- request lifecycle

    def _empty_result(self, item: _Queued, status: str) -> GenResult:
        """Terminal result for a request that returns no tokens (shed,
        cancelled while queued, timed out while queued, quarantined)."""
        n = item.fan_out
        return GenResult(
            tokens=[], chosen_branch=-1,
            all_tokens=np.full((n, 1), -1, np.int32),
            lengths=np.zeros((n,), np.int64),
            logical_tokens=0, compute_tokens=0, peak_cache_bytes=0,
            steps=0, status=status, n_retries=item.n_retries)

    # ---------------------------------------------------- event emission

    @property
    def _emitting(self) -> bool:
        return self.event_sink is not None or self._tick_events is not None

    def _emit(self, ev: TokenEvent) -> None:
        if self._tick_events is not None:
            self._tick_events.append(ev)
        if self.event_sink is not None:
            self.event_sink(ev)

    def _emit_committed(self, rid: int, now: float) -> None:
        """Emit TokenEvents for an active request's newly *committed*
        tokens: tokens on the strategy's ``decided_branch`` — the branch
        certain to be the final choice (greedy always, kappa once pruned
        to one survivor, ST-BoN once truncated; BoN stays undecided until
        the terminal flush). A preempted/faulted request replays
        token-identically, so the streamed prefix stays valid across
        teardown: ``_streamed`` survives requeue and emission resumes
        past it."""
        if not self._emitting:
            return
        rs, _ = self.active[rid]
        b = rs.strategy.decided_branch(rs.branch_ids, rs.done)
        if b is None:
            return
        hi = int(rs.log.len[b])
        start = self._streamed.get(rid, 0)
        if hi <= start:
            return
        buf = rs.log.buf[b]
        for i in range(start, hi):
            self._emit(TokenEvent(rid=rid, kind="token", t=now, index=i,
                                  token=int(buf[i])))
        self._streamed[rid] = hi

    def _record_result(self, rid: int, res: GenResult) -> GenResult:
        """Single funnel for terminal results: store, window-account,
        flush any not-yet-streamed tokens (the committed prefix already
        emitted is always a prefix of ``res.tokens``), and emit the
        exactly-once terminal event."""
        assert rid not in self.results, f"duplicate terminal result {rid}"
        self.results[rid] = res
        self._win_counts["completed"] += 1
        if res.status == "OK":
            self._win_counts["ok"] += 1
            self._win_counts["ok_tokens"] += res.logical_tokens
        elif res.status == "SHED":
            self._win_counts["shed"] += 1
        start = self._streamed.pop(rid, 0)
        if self._emitting:
            now = self.clock()
            for i in range(start, len(res.tokens)):
                self._emit(TokenEvent(rid=rid, kind="token", t=now,
                                      index=i, token=int(res.tokens[i])))
            self._emit(TokenEvent(rid=rid, kind="end", t=now,
                                  index=len(res.tokens), status=res.status,
                                  result=res))
        return res

    def _finalize(self, rid: int, status: str) -> GenResult:
        """Terminal teardown for an ADMITTED request (mid-PREFILLING or
        mid-decode): emit its result under ``status`` and release every
        resource, in the completion path's exact order — result() reads
        the pooled controller mirrors, the prefix publication adopts
        live page refs, and only then do the pool slot and pages go
        away. An active request returns its partial tokens; a
        PREFILLING one has produced none yet."""
        item = self._items.pop(rid)
        self._admit_seq.pop(rid, None)
        if rid in self.prefilling:
            pf = self.prefilling.pop(rid)
            self._publish_prompt_pages(item.prompt, pf.slots[0], pf.filled)
            self._release(pf.slots)
            res = self._empty_result(item, status)
        else:
            rs, slots = self._deactivate(rid)
            res = rs.result()            # BEFORE release_pool (mirrors)
            res.status = status
            res.n_retries = item.n_retries
            self._publish_prefix(item, rs, slots)
            rs.strategy.release_pool()
            self._release(slots)
        return self._record_result(rid, res)

    def _requeue(self, rid: int) -> _Queued:
        """Non-terminal teardown: free an admitted request's rows and
        pages and hand back its original submission for replay. The
        fully-written prompt extent is pinned into the prefix cache
        first, so the replay aliases it back as a hit. The replay
        decodes from the original submission RNG stream —
        token-for-token identical to a never-disturbed run."""
        if rid in self.prefilling:
            pf = self.prefilling.pop(rid)
            self._publish_prompt_pages(pf.item.prompt, pf.slots[0],
                                       pf.filled)
            self._release(pf.slots)
        else:
            rs, slots = self._deactivate(rid)
            item = self._items[rid]
            self._publish_prompt_pages(item.prompt, slots[0],
                                       len(item.prompt))
            rs.strategy.release_pool()
            self._release(slots)
        self._admit_seq.pop(rid, None)
        # latency stamps restart with the replay
        self.ttft.pop(rid, None)
        self.token_times.pop(rid, None)
        return self._items.pop(rid)

    def _retry_or_quarantine(self, item: _Queued) -> None:
        """Requeue a fault-hit request for replay with exponential
        backoff; after ``max_retries`` replays quarantine it as FAILED
        (post-fault partial state is suspect, so no tokens are
        returned) instead of letting one poisoned request grind the
        pool forever."""
        if item.n_retries >= self.max_retries:
            self.counters["failures"] += 1
            self._record_result(item.rid, self._empty_result(item, "FAILED"))
            return
        item.n_retries += 1
        self.counters["retries"] += 1
        item.not_before = self.ticks \
            + self.retry_backoff * 2 ** (item.n_retries - 1)
        self.queue.appendleft(item)

    def _youngest_started(self) -> int:
        """Youngest-admitted request holding pool resources — decoding
        OR still PREFILLING (a half-written prefill is the cheapest
        thing to evict: no decoded tokens are thrown away)."""
        cands = list(self.active) + list(self.prefilling)
        return max(cands, key=lambda r: self._admit_seq[r])

    def _recover_step_fault(self) -> None:
        """A device-step fault aborted the tick before any pool or
        allocator mutation (the injection point is ahead of page growth
        and the dispatch, and the donated buffers were never consumed).
        Tear down ONE victim — youngest-started, matching the
        preemption policy — and route it through the retry budget;
        everyone else simply retries the tick."""
        victim = self._youngest_started()
        self._retry_or_quarantine(self._requeue(victim))

    def _watchdog(self) -> None:
        """Deadline enforcement at tick entry: expire requests past
        their wall-clock deadline or tick budget. Truncate-and-return —
        an expired active request keeps the tokens it already has."""
        if not self._has_deadlines:
            return
        now = self.clock()

        def expired(item: _Queued) -> bool:
            if item.max_wall_ticks is not None \
                    and self.ticks - item.submit_tick >= item.max_wall_ticks:
                return True
            return item.deadline_s is not None \
                and now - self._submit_t[item.rid] >= item.deadline_s

        for rid in [r for r in list(self.active) + list(self.prefilling)
                    if expired(self._items[r])]:
            self._finalize(rid, "TIMEOUT")
            self.counters["timeouts"] += 1
        if any(expired(i) for i in self.queue):
            keep: deque = deque()
            for item in self.queue:
                if expired(item):
                    self._record_result(item.rid,
                                        self._empty_result(item, "TIMEOUT"))
                    self.counters["timeouts"] += 1
                else:
                    keep.append(item)
            self.queue = keep

    def cancel(self, rid: int) -> GenResult:
        """Tear down ``rid`` wherever it is in its lifecycle: a queued
        request is removed outright, a PREFILLING or active one is
        finalized with its resources released (rows, pages, pooled
        controller slot) under the publish-before-release protocol.
        Returns the terminal result — partial tokens if the request was
        mid-decode. Idempotent once terminal; unknown rids raise
        KeyError."""
        if rid in self.results:
            return self.results[rid]
        if rid in self.active or rid in self.prefilling:
            self.counters["cancelled"] += 1
            return self._finalize(rid, "CANCELLED")
        for i, item in enumerate(self.queue):
            if item.rid == rid:
                del self.queue[i]
                self.counters["cancelled"] += 1
                return self._record_result(
                    rid, self._empty_result(item, "CANCELLED"))
        raise KeyError(f"unknown request id {rid}")

    # --------------------------------------------------------- admission

    def _admit_one(self) -> bool:
        if self.admit_paused:
            return False
        if self._admit_left is not None and self._admit_left <= 0:
            return False            # this tick's prefill budget is spent
        idx = self._select_admit()
        if idx is None:
            return False
        item = self.queue[idx]
        del self.queue[idx]
        if self._admit_left is not None:
            self._admit_left -= len(item.prompt)
        n = item.fan_out
        slots = sorted(self.free[:n])
        del self.free[:n]
        self._items[item.rid] = item        # kept for preemption requeue
        self._admit_seq[item.rid] = self._admit_counter
        self._admit_counter += 1

        if self._chunked_ok:
            # PREFILLING state: the request owns its slots now and
            # advances one chunk per tick; decode rows never wait
            self.prefilling[item.rid] = self._begin_prefill(item, slots)
            return True

        # one-shot fallback: whole prompt in one dispatch, through a
        # transient cache sized to the PROMPT (not max_seq)
        pf_logits, cache1 = engine._prefill_one(
            self.params, self.cfg, item.prompt, self._prefill_seq(item),
            self.frontend)
        self.admit_peak_bytes = max(self.admit_peak_bytes,
                                    cache_lib.cache_bytes(cache1))
        # the batch-1 prefill installs directly, its shared prompt pages
        # aliased — no N-row broadcast_batch tile on this path
        self._install(slots, item, cache1)
        self._start_request(item, slots, pf_logits)
        return True

    def _start_request(self, item: _Queued, slots: List[int],
                       pf_logits) -> None:
        """Shared admission tail: build the RequestState, sample the
        fan-out's first tokens from the prefill logits, and either
        activate the request or (already finished) emit its result.
        Identical for one-shot and chunked admissions — the bitwise
        equality of the final chunk's logits makes the two paths
        token-for-token interchangeable."""
        rs = strategies.RequestState(
            item.factory(), self.params, self.cfg, item.kcfg,
            len(item.prompt), item.rng, eos_id=self.eos_id,
            bos_id=self.bos_id, max_seq=self.max_seq,
            n_prefix=self.n_prefix, frontend=self.frontend)
        self._maybe_pool_controller(rs, item)
        rs.first_tokens(pf_logits)
        now = self.clock()
        self.ttft[item.rid] = now - self._submit_t[item.rid]
        self._win_ttft.append(self.ttft[item.rid])
        self.token_times[item.rid] = [now]
        if rs.finished:  # e.g. greedy whose first token is already EOS
            res = rs.result()
            res.n_retries = item.n_retries
            self._record_result(item.rid, res)
            self._publish_prefix(item, rs, slots)
            rs.strategy.release_pool()
            self._release(slots)
            self._items.pop(item.rid, None)
            self._admit_seq.pop(item.rid, None)
        else:
            self._activate(item.rid, rs, slots)
            self.row_token[slots] = rs.cur
            self.row_pos[slots] = rs.pos

    def _activate(self, rid: int, rs: strategies.RequestState,
                  slots: List[int]) -> None:
        """Make ``rid`` active on ``slots``, moving its RNG
        stream (advanced past the first tokens on the host) into a
        device stream slot; from here on ``rs.rng`` is stale."""
        s = self._free_streams.pop()
        self._stream_of[rid] = s
        self._streams = _put_stream(self._streams, s,
                                    strategies.raw_key(rs.rng))
        self._stream_adv[s] = True
        self._n_want_lp += rs.strategy.wants_picked_lp
        self._set_rows(rid, rs, slots)

    def _set_rows(self, rid: int, rs: strategies.RequestState,
                  slots: List[int]) -> None:
        """Point ``slots`` (the request's live rows, in branch order) at
        its stream, as admission and compaction leave them."""
        self.active[rid] = (rs, slots)
        self._slots_dev[rid] = jnp.asarray(slots)
        self._row_stream[slots] = self._stream_of[rid]
        self._row_branch[slots] = np.arange(len(slots))
        self._row_greedy[slots] = rs.strategy.greedy

    def _deactivate(self, rid: int):
        """Take ``rid`` out of the active set and free its stream slot
        (nothing is copied back: a replay restarts from the submission
        RNG). Returns its ``(RequestState, slots)``."""
        rs, slots = self.active.pop(rid)
        self._slots_dev.pop(rid, None)
        s = self._stream_of.pop(rid)
        self._stream_adv[s] = False
        self._free_streams.append(s)
        self._n_want_lp -= rs.strategy.wants_picked_lp
        return rs, slots

    def _advance_one_prefill(self, rid: int) -> None:
        """One standalone chunk for ``rid`` (absent = already preempted
        by a sibling's page growth), with finalize + activation when it
        was the prompt's last chunk."""
        pf = self.prefilling.get(rid)
        if pf is None:
            return
        logits = self._prefill_step(pf)
        if logits is not None and rid in self.prefilling:
            if self._finish_prefill(pf):
                del self.prefilling[rid]
                self._start_request(pf.item, pf.slots, logits)

    def _advance_prefills(self) -> None:
        """Advance every PREFILLING request by one chunk (admission
        order). A request whose final chunk just ran is finalized and
        activated in the same tick, so its rows join this tick's fused
        decode step exactly like a one-shot admission would. Fuse
        candidates are skipped here — their chunks run inside the decode
        dispatch and complete in ``_post_tick_prefill``."""
        self._fused_rids = self._fuse_candidates()
        fused = set(self._fused_rids)
        for rid in sorted(list(self.prefilling),
                          key=lambda r: self._admit_seq[r]):
            if rid not in fused:
                self._advance_one_prefill(rid)

    def _release(self, slots: List[int]) -> None:
        for s in slots:
            self.alloc.free_row(s)
        self._bt_dev = None
        self.row_token[slots] = 0
        self.row_pos[slots] = 0
        self._row_greedy[slots] = True
        self.free.extend(slots)
        self.free.sort()

    def _maybe_pool_controller(self, rs: strategies.RequestState,
                               item: _Queued) -> None:
        """Attach a pooled-controller slot to a kappa request. Pooling
        needs a controller-compatible kcfg; any other keeps the
        per-request local controller, which stays correct — just
        slower."""
        if not (isinstance(rs.strategy, strategies.KappaStrategy)
                and strategies.controller_key(item.kcfg) == self._ctrl_key):
            return
        if self._kappa_pool is None:
            # slots = rows: every concurrent kappa request holds >= 1 pool
            # row, so this bounds the slot count with ONE compiled tick
            # shape. Inactive slots ride the dispatch (gather row 0, result
            # discarded) — wasted compute is bounded by rows x fan_out x V
            # and avoids a bucketed-shape retrace chain; revisit if pools
            # grow to where idle-slot compute shows in the tick breakdown.
            self._kappa_pool = strategies.PooledKappaController(
                self.params, self.cfg, self.kcfg, slots=self.rows,
                bos_id=self.bos_id, frontend=self.frontend)
        slot = self._kappa_pool.acquire(rs.n)
        rs.strategy.attach_pool(self._kappa_pool, slot, rs.n)

    # -------------------------------------------------------------- tick

    def _pooled_kappa_dispatch(self, logits, toks_dev):
        """Build the slot→pool-row gather map for every pooled kappa
        request and advance ALL their controllers in one device dispatch.
        Returns the device (alive, traj, cutoff) tuple, or None when no
        pooled kappa request is active."""
        pool = self._kappa_pool
        if pool is None:
            return None
        pooled = [(rs, slots) for rs, slots in self.active.values()
                  if getattr(rs.strategy, "pool", None) is pool]
        if not pooled:
            return None
        gather_idx = np.zeros((pool.slots, pool.nmax), np.int32)
        done_prev = np.ones((pool.slots, pool.nmax), bool)
        for rs, slots in pooled:
            st = rs.strategy
            gather_idx[st.slot, st.ctrl_rows] = slots
            done_prev[st.slot, st.ctrl_rows] = rs.done[rs.branch_ids]
        self.counters["controller_dispatches"] += 1
        return pool.dispatch(logits, toks_dev, gather_idx, done_prev,
                             self.eos_id)

    def tick(self) -> None:
        """Admit what fits, advance every PREFILLING request one chunk,
        run one fused decode step over the pool, one fused sampler
        dispatch over all active rows, one fused pooled kappa-controller
        dispatch, ONE blocking device transfer carrying tokens +
        controller outputs, then advance every active request on its own
        rows (pure host work). Decode rows therefore never wait for a
        whole admission prefill — at most one chunk of it runs inside
        their tick. Each phase runs in its ``tick_time`` span."""
        tt = self.tick_time
        with tt.span("tick", step=self.ticks):
            with tt.span("admit"):
                self._watchdog()
                self._fault_tick = self._begin_fault_tick()
                self._admit_left = self.prefill_budget
                while self._admit_one():
                    pass
            with tt.span("prefill"):
                self._advance_prefills()
            if not self.active:
                # pure-backoff and embargo-blocked ticks still count as
                # progress: the tick index must advance for `not_before`
                # stamps to expire and for the next tick's fault draw
                progressed = bool(self.prefilling) \
                    or any(i.not_before > self.ticks for i in self.queue) \
                    or (self._fault_tick and bool(self.queue)) \
                    or (self.admit_paused and bool(self.queue)) \
                    or (self._admit_left is not None
                        and self._admit_left <= 0 and bool(self.queue))
                if self._fused_rids:
                    # the decode dispatch these chunks were to ride
                    # vanished (a sibling's page growth preempted the
                    # whole pool) — run them standalone so no prefill
                    # loses its turn
                    rids, self._fused_rids = self._fused_rids, []
                    with tt.span("prefill"):
                        for rid in rids:
                            self._advance_one_prefill(rid)
                if progressed:
                    # PREFILLING requests hold rows and pages —
                    # account them so utilization metrics stay honest
                    # over chunked-admission-heavy stretches
                    self._occupied_ticks += self.rows - len(self.free)
                    self._account_pages_tick()
                    self.ticks += 1
                return
            self._occupied_ticks += self.rows - len(self.free)

            try:
                logits = self._decode_tick()
            except faults_lib.InjectedStepFault:
                # the injection point is BEFORE any pool/allocator
                # mutation, so the tick simply didn't happen: tear one
                # victim down through the retry budget and let everyone
                # else retry
                self.counters["faults_injected"] += 1
                self._recover_step_fault()
                self.ticks += 1
                return
            finite_dev = None
            if self.faults is not None:
                bad = self.faults.nan_rows_for(self.ticks, self.rows)
                if bad.size:
                    self.counters["faults_injected"] += 1
                    logits = logits.at[jnp.asarray(bad)].set(jnp.nan)
                # detection is device-side (a fused finite-mask riding
                # the tick's blocking transfer), not host knowledge of
                # `bad` — the same path a real numerics blowup would take
                finite_dev = engine.rows_finite(logits)

            # one fused sampling dispatch for the whole pool, which
            # advances every active stream and derives the row keys on
            # the device; free rows ride along as masked argmax (ignored)
            with tt.span("keys"):
                streams = sampler.RowStreams(
                    self._streams, jnp.asarray(self._row_stream),
                    jnp.asarray(self._row_branch),
                    jnp.asarray(self._stream_adv))
                gmask = jnp.asarray(self._row_greedy)
            with tt.span("sample"):
                # picked-token log-probs fused into the sampling dispatch
                # so BoN-style strategies do zero device work per request
                toks_dev, lp_dev, self._streams = sampler.sample_rows(
                    streams, logits, gmask, self.kcfg,
                    want_picked_lp=self._n_want_lp > 0)
            self.counters["sampler_dispatches"] += 1

            # the pooled controller consumes the pool logits and the
            # just-sampled tokens device-to-device — no host round-trip
            with tt.span("control"):
                ctrl_dev = self._pooled_kappa_dispatch(logits, toks_dev)

            # ONE blocking transfer for sampled tokens, picked log-probs
            # AND all pooled controller outputs (alive/traj/cutoff of
            # every kappa request), independent of active-request count
            with tt.span("sync"):
                toks, picked, ctrl_host, finite = jax.device_get(
                    (toks_dev, lp_dev, ctrl_dev, finite_dev))
                self.counters["host_syncs"] += 1
                if ctrl_host is not None:
                    self.counters["controller_syncs"] += 1
                    self._kappa_pool.publish(ctrl_host)

            with tt.span("host"):
                self._advance_rows(logits, toks, picked, finite)
            self.ticks += 1

    def _advance_rows(self, logits, toks, picked, finite) -> None:
        """The tick's host work after its blocking transfer: tear down
        NaN-poisoned rows, advance every active request on its own rows,
        finalize chunks that completed, and stamp and emit the committed
        tokens."""
        if finite is not None and not bool(np.all(finite)):
            # NaN-poisoned rows: tear the owning requests down BEFORE
            # the advance loop, so poisoned tokens never reach a token
            # log or a result. The pooled controller consumed the
            # poison for one dispatch, but its finite-guard
            # (core/kappa.py) kept it out of sibling branches' scores,
            # the victim's slot is reset on re-acquire, and other slots
            # are untouched (vmap independence).
            for rid in [r for r, (_, s) in list(self.active.items())
                        if not bool(np.all(finite[s]))]:
                self._retry_or_quarantine(self._requeue(rid))
        stamped = list(self.active)
        for rid in list(self.active):
            rs, slots = self.active[rid]
            lp = picked[slots] if (picked is not None
                                   and rs.strategy.wants_picked_lp) else None
            # skip the per-request device gather when the strategy won't
            # read the logits (greedy; BoN once lp is fused; pooled kappa
            # — its signals come from the pool logits)
            if rs.strategy.needs_step_logits and lp is None:
                req_logits = logits[self._slots_dev[rid]]
            else:
                req_logits = None
            dec = rs.advance(req_logits, toks[slots], picked_lp=lp)
            if dec.keep is not None:
                kept = [slots[i] for i in dec.keep]
                self._release(sorted(set(slots) - set(kept)))
                slots = kept
                self._set_rows(rid, rs, slots)
            self.row_token[slots] = rs.cur
            self.row_pos[slots] = rs.pos
            if rs.finished:
                # publish-before-release ordering lives in _finalize:
                # the radix pin must adopt live refs, and kappa's winner
                # check reads the pooled controller mirrors
                self._finalize(rid, "OK")
        self._post_tick_prefill()
        with self.tick_time.span("emit"):
            now = self.clock()
            for rid in stamped:
                times = self.token_times.get(rid)
                if times is not None:      # absent iff preempted mid-tick
                    self._win_itl.append(now - times[-1])
                    times.append(now)
                if rid in self.active:     # finalized rids flushed already
                    self._emit_committed(rid, now)

    # --------------------------------------------------------------- run

    def run(self) -> Dict[int, GenResult]:
        """Drive queue + pool to completion; returns rid -> GenResult."""
        t0 = self.clock()

        def state():
            return (len(self.queue), len(self.active), len(self.prefilling),
                    sum(pf.filled for pf in self.prefilling.values()))

        while self.queue or self.active or self.prefilling:
            before = state()
            pre = self.ticks
            self.tick()
            if not self.active and not self.prefilling and self.queue \
                    and state() == before:
                # compare backoff stamps against the PRE-tick counter: an
                # item whose not_before equals the new tick index was
                # still backing off during the tick that just ran and
                # deserves one more tick to be admitted. When the tick
                # made no progress (counter unchanged) pre == self.ticks
                # and this degenerates to the strict stall check.
                if self._fault_tick \
                        or any(i.not_before > pre
                               for i in self.queue):
                    continue   # backoff / embargo, not a stall: the
                    #              tick advanced, the next one re-draws
                raise RuntimeError(
                    "scheduler stalled: queued request cannot be admitted "
                    f"(free={len(self.free)} rows, "
                    f"admit_paused={self.admit_paused})")
        self._end_run()
        self.elapsed = self.clock() - t0
        return dict(sorted(self.results.items()))

    # ------------------------------------------------ incremental surface

    @property
    def has_work(self) -> bool:
        """True while anything is queued, prefilling, or decoding."""
        return bool(self.queue or self.active or self.prefilling)

    def step(self) -> List[TokenEvent]:
        """One incremental tick with event capture: returns the
        ``TokenEvent``s emitted during that tick (committed streamed
        tokens plus terminal events), in emission order.  This is the
        front-end's drive surface — unlike ``run()`` it never blocks past
        a single tick, and it makes no stall judgment (an idle step on a
        backed-off or paused queue just returns ``[]``; the caller owns
        liveness).  ``event_sink`` still fires for every captured event,
        so push and pull consumers see the same stream."""
        self._tick_events = []
        try:
            if self.has_work:
                self.tick()
            return self._tick_events
        finally:
            self._tick_events = None

    def snapshot(self, reset_window: bool = False) -> Dict[str, float]:
        """Windowed latency/throughput counters accumulated since the
        last ``snapshot(reset_window=True)`` (or construction).  The SLO
        controller reads per-window percentiles here instead of the
        run-lifetime aggregates in ``latency_stats()``/``throughput()``,
        so a transient overload is visible the window it happens rather
        than diluted over the run."""
        now = self.clock()
        win_s = max(now - self._win_t0, 1e-9)
        ttft, itl = self._win_ttft, self._win_itl
        out = {
            "window_s": win_s,
            "window_ticks": self.ticks - self._win_tick0,
            "queued": len(self.queue),
            "active": len(self.active),
            "prefilling": len(self.prefilling),
            "admit_paused": bool(self.admit_paused),
            "prefill_budget": self.prefill_budget,
            "ttft_count": len(ttft),
            "itl_count": len(itl),
            "completed": self._win_counts["completed"],
            "ok": self._win_counts["ok"],
            "shed": self._win_counts["shed"],
            "ok_tokens": self._win_counts["ok_tokens"],
            "goodput_tokens_per_s": self._win_counts["ok_tokens"] / win_s,
            "ttft_p50_s": float(np.percentile(ttft, 50)) if ttft else 0.0,
            "ttft_p99_s": float(np.percentile(ttft, 99)) if ttft else 0.0,
            "itl_p50_s": float(np.percentile(itl, 50)) if itl else 0.0,
            "itl_p99_s": float(np.percentile(itl, 99)) if itl else 0.0,
        }
        if reset_window:
            self._win_t0 = now
            self._win_tick0 = self.ticks
            self._win_ttft = []
            self._win_itl = []
            self._win_counts = {"completed": 0, "ok": 0,
                                "ok_tokens": 0, "shed": 0}
        return out

    # ----------------------------------------------------------- metrics

    def request_bytes(self) -> Dict[int, int]:
        """Per-request bytes from allocator truth: pages the request's
        rows reference — shared prompt pages charged ONCE — times the
        per-page byte cost, plus the analytic per-row cost of the
        non-paged leaf families (ring / recurrent / rwkv6 / cross-KV)."""
        pb = cache_lib.page_bytes(self.cfg, self.page_size)
        out = {}
        for rid, (rs, slots) in self.active.items():
            pages = {int(p) for s in slots for p in self.alloc.row_pages(s)}
            out[rid] = len(pages) * pb + cache_lib.used_cache_bytes(
                self.cfg, len(slots), rs.pos, self.max_seq, skip_global=True)
        return out

    def throughput(self) -> Dict[str, float]:
        """Aggregate serving metrics over a completed ``run()``."""
        total_logical = sum(r.logical_tokens for r in self.results.values())
        total_compute = sum(r.compute_tokens for r in self.results.values())
        elapsed = max(getattr(self, "elapsed", 0.0), 1e-9)
        out = {
            "requests": len(self.results),
            "ticks": self.ticks,
            "time_s": elapsed,
            "logical_tokens": total_logical,
            "compute_tokens": total_compute,
            "tokens_per_s": total_logical / elapsed,
            "requests_per_s": len(self.results) / elapsed,
            "row_utilization": (self._occupied_ticks
                                / max(self.ticks * self.rows, 1)),
            "page_utilization": (self._page_ticks
                                 / max(self.ticks * self.num_pages, 1)),
            "page_peak": self._page_peak,
        }
        # host seconds per tick phase (serving/spans.py): "step" and
        # "sample"/"control" time the enqueue of the device programs,
        # "keys" the hand-off of the sampler's per-row operands, "sync"
        # the one blocking transfer, and "host" the per-request advance
        # loop (which absorbs UNPOOLED controller dispatch + sync — the
        # regression the breakdown exists to make visible)
        for k, v in self.tick_time.items():
            out[f"time_{k}_s"] = v
        # the counters include the prefix cache's (zeros when it is off)
        out.update(self.counters)
        status_counts: Dict[str, int] = {}
        for r in self.results.values():
            status_counts[r.status] = status_counts.get(r.status, 0) + 1
        out["status_counts"] = status_counts
        out["admit_peak_bytes"] = self.admit_peak_bytes
        looked = (self.counters["prefix_hits"]
                  + self.counters["prefix_misses"])
        out["prefix_hit_rate"] = self.counters["prefix_hits"] / max(looked, 1)
        out["prefix_pinned_pages"] = (self.pcache.pinned_count
                                      if self.pcache is not None else 0)
        out.update(self.latency_stats())
        return out

    def latency_stats(self) -> Dict[str, float]:
        """TTFT / inter-token-latency percentiles over every request
        served so far (per-request stamps stay in ``token_times`` for
        finer-grained windows)."""
        ttft = np.asarray(sorted(self.ttft.values()) or [0.0])
        itl = np.asarray([d for ts in self.token_times.values()
                          for d in np.diff(ts)] or [0.0])
        return {
            "ttft_p50_s": float(np.percentile(ttft, 50)),
            "ttft_p99_s": float(np.percentile(ttft, 99)),
            "itl_p50_s": float(np.percentile(itl, 50)),
            "itl_p99_s": float(np.percentile(itl, 99)),
            "itl_max_s": float(itl.max()),
        }

    # --------------------------------------------------- page accounting

    def _prompt_pos(self, item: _Queued) -> int:
        """First decode-write position (= installed prompt length)."""
        return len(item.prompt) + self.n_prefix

    def _shared_pages(self, item: _Queued) -> int:
        """Prompt pages installed once from the prefill. With fan-out
        N > 1 these are the fully-written pages all branches alias
        read-only; a single-branch request has no sibling to share with,
        so its partially-written boundary page is installed directly too
        (it is refcount-1 either way — no COW copy needed)."""
        pos0 = self._prompt_pos(item)
        if item.fan_out == 1:
            return self.alloc.pages_for(pos0)
        return pos0 // self.page_size

    def _boundary(self, item: _Queued) -> int:
        """1 if each branch needs a private COW copy of a mid-page
        prompt boundary, else 0 (page-aligned prompt, or fan-out 1 —
        see :meth:`_shared_pages`)."""
        if item.fan_out == 1:
            return 0
        return 1 if self._prompt_pos(item) % self.page_size else 0

    def _priv_worst(self, item: _Queued) -> int:
        """Private pages one branch can grow to (its ``need`` positions
        minus the shared prompt pages)."""
        return self.alloc.pages_for(item.need) - self._shared_pages(item)

    def _initial_priv(self, item: _Queued) -> int:
        """Private pages per branch at admission: the boundary COW copy
        (if any) plus one decode page, capped at the branch's worst case
        (a short request may never leave its boundary page)."""
        return min(1 + self._boundary(item), self._priv_worst(item))

    def _initial_pages(self, item: _Queued) -> int:
        """Pages allocated at admission: shared prompt pages once, plus
        each branch's initial private pages."""
        return self._shared_pages(item) \
            + item.fan_out * self._initial_priv(item)

    def _worst_pages(self, item: _Queued) -> int:
        """Lifetime peak with lazy growth: shared prompt pages once plus
        each branch's private pages grown to cover ``need`` positions."""
        return self._shared_pages(item) \
            + item.fan_out * self._priv_worst(item)

    # ----------------------------------------------------------- storage

    def _check_servable(self, item: _Queued) -> None:
        # worst case must fit the pool ALONE: this is what guarantees
        # preemption always unblocks growth (see _ensure_pages)
        total = self._worst_pages(item)
        if total > self.num_pages:
            raise Unservable(
                f"request needs {total} pages > pool num_pages="
                f"{self.num_pages} (page_size={self.page_size})")

    def _admissible(self, item: _Queued) -> bool:
        # pin-only cached pages count as free capacity: admission may
        # rely on eviction (see _reclaim) — without this slack a pool
        # whose free heap is all pinned prefixes would refuse every
        # admission and stall run() with nothing active to preempt.
        # avail_count (not free_count): an injected allocator embargo
        # must gate admission and growth consistently within the tick
        slack = self.pcache.evictable_count if self.pcache is not None else 0
        return (len(self.free) >= item.fan_out
                and self.alloc.avail_count + slack
                >= self._initial_pages(item))

    def _select_admit(self) -> Optional[int]:
        # shortest-job-first among fitting requests, FIFO tie-break —
        # with bounded bypass so a steady short stream cannot starve the
        # oldest request: after max_bypass bypasses the head is admitted
        # next-fit-or-nothing (admission pauses until it fits). Items
        # backing off after a fault retry are skipped until their
        # not_before tick; the aged head keeps its fast path only once
        # it is ready itself.
        if not self.queue:
            return None
        head = self.queue[0]
        if head.not_before <= self.ticks \
                and head.bypasses >= self.max_bypass:
            return 0 if self._admissible(head) else None
        best, best_need = None, None
        for i, item in enumerate(self.queue):
            if item.not_before > self.ticks:
                continue
            if self._admissible(item) and (best is None
                                           or item.need < best_need):
                best, best_need = i, item.need
        if best is not None:
            for i in range(best):
                self.queue[i].bypasses += 1
        return best

    def _install(self, slots, item, sub1) -> None:
        """Install a one-shot admission's batch-1 prefill cache ``sub1``
        into the row slots: shared prompt pages once, aliased into every
        branch table, plus each branch's private boundary copy and first
        decode page."""
        full = self._shared_pages(item)
        boundary = self._boundary(item)
        n_priv = self._initial_priv(item)
        shared = self.alloc.alloc_pages(full)
        # (src logical page -> dst physical page) scatter map: shared
        # prompt pages once, the boundary page once per branch (its COW
        # copy), nothing for the empty first decode page
        src = list(range(full))
        phys = list(shared)
        for s in slots:
            priv = self.alloc.alloc_pages(n_priv)
            if boundary:
                src.append(full)
                phys.append(priv[0])
            self.alloc.set_row_pages(s, list(shared) + priv)
        self._bt_dev = None
        self.pool = _install_shared(
            self.cfg, self.pool, jnp.asarray(slots),
            jnp.asarray(np.asarray(src, np.int32)),
            jnp.asarray(np.asarray(phys, np.int32)), sub1, self.page_size)

    # ------------------------------------------- lazy growth / preemption

    def _begin_fault_tick(self) -> bool:
        """Consult the fault plan for tick-scoped allocator faults; True
        while an embargo is live (preemptions this tick are charged to
        the victim's retry budget)."""
        hb = self.alloc.begin_tick(self.ticks)
        if hb:
            self.counters["faults_injected"] += 1
        return hb > 0

    def _end_run(self) -> None:
        """Post-run: clear any tick-scoped fault state so leak checks
        and later manual ticks see a clean pool."""
        self.alloc.holdback = 0

    def _publish_prompt_pages(self, prompt: np.ndarray, slot: int,
                              upto: int) -> None:
        """Pin the fully-written pages covering ``prompt[:upto]`` (row
        ``slot``'s block-table prefix) into the radix tree — the
        preemption-side publication point: the pages are about to lose
        their table references, and re-prefilling them on re-admission
        (or by any sharer) would be pure waste."""
        if self.pcache is None:
            return
        k = upto // self.page_size
        if k:
            pages = [int(p) for p in self.alloc.block[slot, :k]]
            self.pcache.publish(np.asarray(prompt)[:k * self.page_size],
                                pages)

    def _preempt(self, rid: int) -> None:
        """Evict ``rid`` (active or mid-PREFILLING): free its pages and
        rows (:meth:`_requeue` — fully-written prompt pages are
        published into the prefix cache first, so the replay aliases
        them back as a hit), return its original submission to the
        queue head. On re-admission it replays prefill and decode from
        its original RNG stream, so the final tokens are identical to a
        never-preempted run. Preemptions forced by an injected
        allocator embargo are charged to the victim's retry budget —
        genuine pressure requeues for free."""
        item = self._requeue(rid)
        self.counters["preemptions"] += 1
        if self._fault_tick:
            self._retry_or_quarantine(item)
        else:
            self.queue.appendleft(item)

    def _reclaim(self, n: int) -> bool:
        """Make ``n`` pages allocatable by evicting least-recently-hit
        pin-only pages from the prefix cache. Eviction is ordered BEFORE
        preemption at every allocation site: dropping cached-but-idle
        prefix pages only costs a future re-prefill, while preemption
        throws away live decode progress — and without this ordering
        pinned pages could hold the heap dry forever (nothing ever
        unpins them) and deadlock admission. Returns False when the free
        heap is still short and nothing is evictable (the caller falls
        through to preemption)."""
        while not self.alloc.can_alloc(n):
            if self.pcache is None or self.pcache.evict_one() is None:
                return False
            self.counters["prefix_evictions"] += 1
        return True

    def _ensure_pages(self) -> None:
        """Lazy growth: before the fused decode step, every active row
        whose position has crossed into an unallocated logical page
        acquires the next page from the free heap (evicting cached
        prefix pages first — :meth:`_reclaim`). Requests grow in
        admission order (oldest first); when nothing more is evictable
        the youngest-admitted request is preempted — possibly the grower
        itself, when everything younger is already gone."""
        for rid in sorted(self.active, key=lambda r: self._admit_seq[r]):
            if rid not in self.active:       # preempted below
                continue
            rs, slots = self.active[rid]
            evicted = False
            for s in slots:
                lp = int(self.row_pos[s]) // self.page_size
                while int(self.alloc.owned[s]) <= lp:
                    if self._reclaim(1):
                        self.alloc.append_page(s)
                        self._bt_dev = None
                        continue
                    victim = self._youngest_started()
                    self._preempt(victim)
                    if victim == rid:
                        evicted = True
                        break
                if evicted:
                    break

    # ------------------------------------------------- chunked prefill
    #
    # Chunk K/V goes STRAIGHT into allocator-owned pages through
    # slot[0]'s block table — no batch-1 side cache for the global
    # layers, no install scatter for the prompt phase. Only the O(window)
    # / O(1) per-row families (ring / recurrent / rwkv6) ride a tiny
    # batch-1 aux cache, installed per-branch at completion (they cannot
    # be shared copy-on-write anyway). Pages are acquired lazily chunk by
    # chunk; the heap running dry preempts the youngest-started request,
    # possibly this prefill itself.

    def _begin_prefill(self, item, slots) -> _Prefill:
        aux = init_cache(self.cfg, 1, max(self._ring_window(), 1))
        self.admit_peak_bytes = max(self.admit_peak_bytes,
                                    cache_lib.cache_bytes(aux))
        pf = _Prefill(item=item, slots=slots, aux=aux)
        if self.pcache is not None:
            # alias every cached prefix page into slot[0]'s table and
            # start the chunked prefill at the first uncached token.
            # Cap: the LAST prompt token always re-prefills — sampling
            # needs the final position's logits, which only a live
            # prefill chunk produces — so a "full hit" still runs one
            # short tail chunk (and, page-aligned, rewrites the final
            # page; its fresh copy doubles as the COW write target)
            plen = len(item.prompt)
            pages = self.pcache.lookup(item.prompt)
            pages = pages[:(plen - 1) // self.page_size]
            if pages:
                self.alloc.set_row_pages(slots[0], pages)
                pf.filled = len(pages) * self.page_size
                self._bt_dev = None
                self.counters["prefix_hits"] += 1
                self.counters["prefix_tokens_saved"] += pf.filled
            else:
                self.counters["prefix_misses"] += 1
        return pf

    # compile-count bound for long prompts: the chunk's block-table
    # prefix width is bucketed to a page multiple, so a P-page prompt
    # compiles ~P/_BT_BUCKET chunk shapes instead of one per chunk.
    # Padding entries alias the trash page; their view positions trail
    # every chunk query, so the bitwise-equality argument is unchanged.
    _BT_BUCKET = 8

    def _grow_for_chunk(self, pf: _Prefill) -> Optional[int]:
        """Acquire the pages covering the next chunk (preempting the
        youngest-started request when the heap is dry). Returns the
        chunk length, or None if ``pf`` itself had to be evicted."""
        item, s0 = pf.item, pf.slots[0]
        c = min(self.prefill_chunk, len(item.prompt) - pf.filled)
        need = self.alloc.pages_for(pf.filled + c)
        while int(self.alloc.owned[s0]) < need:
            if self._reclaim(1):
                if int(self.alloc.owned[s0]) == 0:
                    self.alloc.set_row_pages(s0, self.alloc.alloc_pages(1))
                else:
                    self.alloc.append_page(s0)
                self._bt_dev = None
                continue
            victim = self._youngest_started()
            self._preempt(victim)
            if victim == item.rid:
                return None          # self-evicted; replay from the queue
        return c

    def _chunk_args(self, pf: _Prefill, c: int):
        """Device operands for one chunk: tokens, per-row pos0, the
        bucketed PREFIX of slot[0]'s block table (attention cost scales
        with the filled prompt, not max_seq), and the physical page of
        every chunk token."""
        item, s0 = pf.item, pf.slots[0]
        piece = item.prompt[pf.filled:pf.filled + c]
        qpos = np.arange(pf.filled, pf.filled + c)
        cpages = self.alloc.block[s0][qpos // self.page_size]
        need = self.alloc.pages_for(pf.filled + c)
        width = min(self.max_pages,
                    -(-need // self._BT_BUCKET) * self._BT_BUCKET)
        return (jnp.asarray(piece)[None],
                jnp.full((1,), pf.filled, jnp.int32),
                jnp.asarray(self.alloc.block[s0:s0 + 1, :width]),
                jnp.asarray(cpages.astype(np.int32))[None])

    def _prefill_step(self, pf: _Prefill):
        """Standalone chunk dispatch — used when no decode tick runs
        this tick (empty pool) or for PREFILLING requests beyond the
        fused candidate."""
        c = self._grow_for_chunk(pf)
        if c is None:
            return None
        toks, pos0, bt, cpages = self._chunk_args(pf, c)
        logits, self.pool, pf.aux = engine._prefill_chunk_paged(
            self.params, self.cfg, toks, pos0, 0, self.pool, bt, cpages,
            pf.aux)
        pf.filled += c
        return logits[0] if pf.filled >= len(pf.item.prompt) else None

    def _finish_prefill(self, pf: _Prefill) -> bool:
        """Share the fully-written prompt pages across the fan-out:
        slot[0] keeps its table (it wrote the pages), siblings alias the
        full prompt pages read-only and get a private device copy of the
        mid-page boundary (their COW write target); the per-row aux
        state broadcasts into every branch row. Decode pages then grow
        lazily exactly as for one-shot admissions."""
        item, s0 = pf.item, pf.slots[0]
        n = item.fan_out
        pos0 = self._prompt_pos(item)
        full = pos0 // self.page_size
        boundary = 1 if (n > 1 and pos0 % self.page_size) else 0
        if n > 1:
            need = boundary * (n - 1)
            while not self._reclaim(need):
                victim = self._youngest_started()
                self._preempt(victim)
                if victim == item.rid:
                    return False
            shared = [int(p) for p in self.alloc.block[s0, :full]]
            copies: List[int] = []
            if boundary:
                b_src = int(self.alloc.block[s0, full])
                copies = self.alloc.alloc_pages(need)
                self.pool = _copy_pages(
                    self.cfg, self.pool,
                    jnp.asarray(np.full((need,), b_src, np.int32)),
                    jnp.asarray(np.asarray(copies, np.int32)))
            for i, s in enumerate(pf.slots[1:]):
                self.alloc.set_row_pages(
                    s, shared + ([copies[i]] if boundary else []))
        self.pool = _install_aux(self.cfg, self.pool,
                                 jnp.asarray(pf.slots), pf.aux)
        pf.aux = None
        self._bt_dev = None
        return True

    def _fuse_candidates(self) -> List[int]:
        """rids of the PREFILLING requests whose next chunks ride the
        tick's fused decode dispatch instead of their own standalone
        dispatches, in admission order (none when no row decodes)."""
        # EVERY prefilling request rides the decode dispatch: one tick =
        # one fused device program = decode + all concurrent prompt
        # chunks (PR 5 fused only the oldest; with prefix-cache hits
        # shortening prefills, several short tails per tick are the
        # common case, and each younger one used to dispatch standalone)
        if not self.active or not self.prefilling:
            return []
        return sorted(self.prefilling, key=lambda r: self._admit_seq[r])

    def _account_pages_tick(self) -> None:
        """Pages in use this tick, summed over ticks and maxed."""
        self._page_ticks += self.alloc.used_count
        self._page_peak = max(self._page_peak, self.alloc.used_count)

    def _decode_tick(self):
        # page growth, COW certification and the step's operands, then
        # the one fused step dispatch
        with self.tick_time.span("pages"):
            fused, wp = self._step_operands()
        with self.tick_time.span("step"):
            if fused:
                self.counters["fused_chunks"] += len(fused)
                chunks, auxs_in = [], []
                for rid, pf, c, args in fused:
                    chunks.append(args)
                    auxs_in.append(pf.aux)
                logits, clogits, self.pool, auxs = \
                    engine._fused_decode_chunks(
                        self.params, self.cfg, jnp.asarray(self.row_token),
                        jnp.asarray(self.row_pos), self.pool, self._bt_dev,
                        jnp.asarray(wp), tuple(chunks), tuple(auxs_in))
                out = {}
                for (rid, pf, c, _), cl, aux in zip(fused, clogits, auxs):
                    pf.filled += c
                    pf.aux = aux
                    out[rid] = cl
                self._fused_chunk_out = out
                return logits
            logits, self.pool = _paged_step(
                self.params, self.cfg, jnp.asarray(self.row_token),
                jnp.asarray(self.row_pos), self.pool, self._bt_dev,
                jnp.asarray(wp))
        return logits

    def _step_operands(self):
        """Grow every fused chunk's pages and every active row's, certify
        the rows' write pages and upload the block table; returns the
        surviving fused chunks ``(rid, prefill, length, operands)`` and
        the write pages."""
        # step-fault injection point: BEFORE chunk growth and
        # _ensure_pages, so a fault aborts the tick with the allocator
        # and pool untouched (retry is then trivially sound — the
        # donated device buffers were never consumed either)
        engine.check_step_fault(self.faults, self.ticks)
        # grow every fused chunk's pages FIRST — growth can evict or
        # preempt, which must settle before write pages are certified
        # below (growth runs in admission order, matching the order of
        # standalone chunk dispatches)
        fused = []                           # (rid, pf, chunk_len)
        for rid in self._fused_rids:
            pf = self.prefilling.get(rid)
            if pf is None:
                continue                     # preempted by an older grower
            c = self._grow_for_chunk(pf)
            if c is not None:
                fused.append((rid, pf, c))
        self._ensure_pages()
        # a younger fused chunk may have been preempted by a LATER
        # grower or by active-row growth — keep only survivors
        fused = [f for f in fused if f[0] in self.prefilling]
        self._fused_rids = [f[0] for f in fused]
        # COW guard: every active row's write page must be refcount-1
        # (allocator truth); the certified pages are pinned into the
        # decode step so a write physically cannot land on a shared page
        wp = np.full((self.rows,), self.alloc.trash, np.int32)
        occ = np.array([s for _, slots in self.active.values()
                        for s in slots], np.int64)
        if occ.size:
            wp[occ] = self.alloc.write_page(occ, self.row_pos[occ])
        self._account_pages_tick()
        self.counters["kv_pages_live"] += int(
            (self.row_pos // self.page_size + 1).sum())
        self.counters["kv_pages_table"] += self.rows * self.max_pages
        if self._bt_dev is None:
            self._bt_dev = jnp.asarray(self.alloc.block)
        return [(rid, pf, c, self._chunk_args(pf, c))
                for rid, pf, c in fused], wp

    def _post_tick_prefill(self) -> None:
        """Finalize the fused chunks that completed their prompts this
        tick (the activated requests join the NEXT decode tick)."""
        rids, self._fused_rids = self._fused_rids, []
        out, self._fused_chunk_out = self._fused_chunk_out, None
        if not rids or out is None:
            return
        for rid in rids:
            pf = self.prefilling.get(rid)
            # absent = preempted by an older sibling's finalize below
            if pf is None or pf.filled < len(pf.item.prompt):
                continue
            if self._finish_prefill(pf):
                del self.prefilling[rid]
                # rows join the NEXT decode tick (the chunk's logits
                # only materialized with this tick's compute)
                self._start_request(pf.item, pf.slots, out[rid][0])

    # ------------------------------------------- prefix-cache publication

    def _winner_extent(self, rs) -> Optional[int]:
        """Index into ``rs.branch_ids``/slots of the branch whose
        fed-token sequence is exactly reconstructible from the token log
        (prompt ++ logged tokens ++ forced-EOS tail), or None → publish
        the prompt extent only. Reconstruction fails when the chosen
        branch's rows were already released (BoN's eager EOS freeing) or
        when kappa chose a pruned-but-uncompacted branch (its post-prune
        fed tokens were sampled, not EOS, and never logged)."""
        chosen = rs.strategy.choose(rs.branch_ids, rs.done)
        where = np.nonzero(rs.branch_ids == chosen)[0]
        if where.size == 0:
            return None
        idx = int(where[0])
        if isinstance(rs.strategy, strategies.KappaStrategy):
            alive, _ = rs.strategy._alive_traj()
            if not bool(alive[idx]):
                return None
        return idx

    def _publish_prefix(self, item: _Queued, rs, slots) -> None:
        """Completion-side publication, called BEFORE the request's
        pages are released (the Path-Consistency scenario): pin the
        winner's full fully-written extent — prompt AND
        surviving generated prefix — into the radix tree, so a later
        sampling of the same problem that extends this prefix aliases
        the winner's pages instead of re-prefilling them. The fed
        sequence is prompt ++ log[:-1] (the last logged token was
        sampled but never fed) padded with the forced-EOS feeds of
        post-done ticks; when that reconstruction isn't certain
        (:meth:`_winner_extent`) only the prompt pages are published."""
        if self.pcache is None or not slots:
            return
        prompt = item.prompt    # already a host ndarray (submit())
        idx = self._winner_extent(rs)
        if idx is None:
            self._publish_prompt_pages(prompt, slots[0], len(prompt))
            return
        chosen = int(rs.branch_ids[idx])
        L = int(rs.log.len[chosen])
        fed = rs.log.buf[chosen, :max(L - 1, 0)]
        gap = int(rs.pos) - len(prompt) - len(fed)
        seq = np.concatenate(
            [prompt, fed,
             np.full((max(gap, 0),), self.eos_id)])[:int(rs.pos)]
        k = len(seq) // self.page_size
        if k:
            pages = [int(p) for p in self.alloc.block[slots[idx], :k]]
            self.pcache.publish(seq[:k * self.page_size], pages)
