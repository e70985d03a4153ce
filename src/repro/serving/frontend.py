"""Async streaming front-end over the tick scheduler (DESIGN.md §9).

``ServingFrontend`` owns a scheduler (any strategy mix) and drives its
incremental ``step()`` surface from a background task, streaming each
request's committed tokens back as
:class:`~repro.serving.scheduler.TokenEvent` objects the moment the
tick that produced them retires. Two interchangeable drive backends:

* **asyncio** (``async with ServingFrontend(sched) as fe``): the tick
  loop runs as an event-loop task. After every tick it yields once
  (``await asyncio.sleep(0)``), which deterministically runs every
  consumer woken by that tick's events *before* the next tick starts —
  streams interleave with decoding without threads.
* **thread** (``with ServingFrontend(sched) as fe``): for callers
  without an event loop. The tick loop runs on a daemon thread, events
  flow through thread-safe queues, and the sync twins
  (``stream()`` / ``wait_result()``) block instead of awaiting.

Either way the scheduler itself is single-threaded: every scheduler
touch (submit, cancel, tick, metrics) happens under one re-entrant
lock, and the SLO controller's ``on_tick`` runs inside it.

Equivalence contract: an undisturbed streamed request yields exactly
the token sequence batch ``run()`` produces on the same seed — the
committed-prefix emission rule guarantees every streamed prefix is a
prefix of the final ``GenResult.tokens``, and the terminal event flushes
the rest.

A tick that raises (a device program that fails to compile or run, a
scheduler bug) stops the tick loop and fails the front-end: the error
is re-raised from every waiter — ``drain()``, ``join()``, ``events()`` /
``stream()``, ``result()`` / ``wait_result()`` and later submissions —
instead of leaving them to wait on work no tick will ever finish.
"""
from __future__ import annotations

import asyncio
import queue as _queue
import threading
import time
from typing import AsyncIterator, Dict, Iterator, List, Optional

from .scheduler import GenResult, TokenEvent

# wakes a consumer blocked on its event channel when the tick loop fails
_FAILED = object()


class ServingFrontend:
    """Streaming front-end over one scheduler instance.

    The scheduler must be exclusively owned: the frontend installs
    itself as the scheduler's ``event_sink`` and drives every tick.
    """

    def __init__(self, sched, *, slo=None, idle_sleep_s: float = 0.001):
        if sched.event_sink is not None:
            raise ValueError("scheduler already has an event_sink")
        self.sched = sched
        self.slo = slo
        self.idle_sleep_s = idle_sleep_s
        sched.event_sink = self._on_event
        self._lock = threading.RLock()
        self._done_cv = threading.Condition(self._lock)
        self._chan: Dict[int, object] = {}      # rid -> event queue
        self._futures: Dict[int, asyncio.Future] = {}
        # events emitted synchronously inside sched.submit (SHED at the
        # door) land here before the rid has a channel; submit_nowait
        # drains them under the same lock, so none are ever dropped
        self._pending: List[TokenEvent] = []
        self._mode: Optional[str] = None        # "asyncio" | "thread"
        self._stop = False
        self._task: Optional[asyncio.Task] = None
        self._thread: Optional[threading.Thread] = None
        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._error: Optional[BaseException] = None   # a failed tick

    # ------------------------------------------------------------ lifecycle

    async def __aenter__(self) -> "ServingFrontend":
        self.start_async()
        return self

    async def __aexit__(self, *exc) -> None:
        await self.aclose()

    def __enter__(self) -> "ServingFrontend":
        self.start_thread()
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def start_async(self) -> None:
        """Start the tick loop as a task on the running event loop."""
        assert self._mode is None, "frontend already started"
        self._mode = "asyncio"
        self._loop = asyncio.get_running_loop()
        self._task = self._loop.create_task(self._tick_loop_async())

    def start_thread(self) -> None:
        """Start the tick loop on a background daemon thread."""
        assert self._mode is None, "frontend already started"
        self._mode = "thread"
        self._thread = threading.Thread(
            target=self._tick_loop_thread, name="serving-tick", daemon=True)
        self._thread.start()

    async def aclose(self) -> None:
        """Drain all in-flight work, then stop the tick task."""
        try:
            await self.drain()
        finally:
            self._stop = True
            if self._task is not None:
                await self._task
                self._task = None
            self._shutdown()

    def close(self) -> None:
        """Thread-backend twin of :meth:`aclose`."""
        try:
            self.join()
        finally:
            self._stop = True
            if self._thread is not None:
                self._thread.join()
                self._thread = None
            self._shutdown()

    def _shutdown(self) -> None:
        with self._lock:
            self.sched._end_run()      # clear tick-scoped fault state so
            self.sched.event_sink = None   # leak checks see a clean pool

    # ------------------------------------------------------------ tick loop

    def _tick_once(self) -> bool:
        """One locked scheduler tick (+ SLO window check); returns
        whether there was work."""
        with self._lock:
            if not self.sched.has_work:
                return False
            self.sched.step()
            if self.slo is not None:
                self.slo.on_tick()
            return True

    def _fail(self, err: BaseException) -> None:
        """Record a failed tick and wake every waiter to re-raise it."""
        with self._lock:
            self._error = err
            for fut in self._futures.values():
                if not fut.done():
                    fut.set_exception(err)
            self._futures.clear()
            for ch in self._chan.values():
                ch.put_nowait(_FAILED)
            self._done_cv.notify_all()

    def _check_failed(self) -> None:
        if self._error is not None:
            raise self._error

    async def _tick_loop_async(self) -> None:
        while not self._stop:
            try:
                worked = self._tick_once()
            except Exception as e:
                self._fail(e)
                return
            # sleep(0) after a working tick: consumers woken by this
            # tick's put_nowait calls were queued on the loop BEFORE
            # this continuation, so they all run before the next tick —
            # deterministic stream/tick interleaving without threads.
            # Their host time is the "frontend" phase.
            with self.sched.tick_time.span("frontend"):
                await asyncio.sleep(0 if worked else self.idle_sleep_s)

    def _tick_loop_thread(self) -> None:
        while not self._stop:
            try:
                worked = self._tick_once()
            except Exception as e:
                self._fail(e)
                return
            if not worked:
                # idle pacing of a live OS thread: wall-clock by nature,
                # never observable in tokens (replay is RNG-driven).
                # Consumers run on their own threads, so this pause is
                # the loop's only host time outside a tick.
                with self.sched.tick_time.span("frontend"):
                    # repro-lint: disable-next-line=replay-determinism
                    time.sleep(self.idle_sleep_s)

    # ------------------------------------------------------------- events

    def _on_event(self, ev: TokenEvent) -> None:
        # always called under self._lock (submit and tick both hold it)
        ch = self._chan.get(ev.rid)
        if ch is not None:
            ch.put_nowait(ev)
        else:
            self._pending.append(ev)
        if ev.kind == "end":
            fut = self._futures.pop(ev.rid, None)
            if fut is not None and not fut.done():
                fut.set_result(ev.result)
            self._done_cv.notify_all()

    def _new_channel(self):
        return asyncio.Queue() if self._mode == "asyncio" \
            else _queue.Queue()

    # ------------------------------------------------------------- submit

    def submit_nowait(self, prompt, rng, **kw) -> int:
        """Submit without waiting; returns the rid. Thread-safe. The
        rid's event channel is registered under the same lock as the
        submit, so even a synchronous SHED terminal event is captured."""
        with self._lock:
            self._check_failed()
            rid = self.sched.submit(prompt, rng, **kw)
            ch = self._new_channel()
            self._chan[rid] = ch
            mine = [e for e in self._pending if e.rid == rid]
            if mine:
                self._pending = [e for e in self._pending if e.rid != rid]
                for e in mine:
                    ch.put_nowait(e)
            return rid

    async def submit(self, prompt, rng, **kw) -> GenResult:
        """Submit and await the terminal :class:`GenResult`."""
        rid = self.submit_nowait(prompt, rng, **kw)
        return await self.result(rid)

    async def submit_stream(self, prompt, rng, **kw) \
            -> AsyncIterator[TokenEvent]:
        """Submit and stream the request's events: committed tokens in
        strict decode order, then exactly one terminal ``kind="end"``
        event (carrying the full ``GenResult``), after which the
        iterator ends."""
        rid = self.submit_nowait(prompt, rng, **kw)
        async for ev in self.events(rid):
            yield ev

    # ------------------------------------------------------------ consume

    async def events(self, rid: int) -> AsyncIterator[TokenEvent]:
        """Async-iterate a submitted rid's events through its terminal
        event."""
        ch = self._chan[rid]
        try:
            while True:
                ev = await ch.get()
                if ev is _FAILED:
                    self._check_failed()
                yield ev
                if ev.kind == "end":
                    return
        finally:
            with self._lock:
                self._chan.pop(rid, None)

    def stream(self, rid: int, timeout: Optional[float] = None) \
            -> Iterator[TokenEvent]:
        """Sync twin of :meth:`events` for the thread backend."""
        ch = self._chan[rid]
        try:
            while True:
                ev = ch.get(timeout=timeout)
                if ev is _FAILED:
                    self._check_failed()
                yield ev
                if ev.kind == "end":
                    return
        finally:
            with self._lock:
                self._chan.pop(rid, None)

    async def result(self, rid: int) -> GenResult:
        """Await the terminal result of a submitted rid."""
        with self._lock:
            res = self.sched.results.get(rid)
            if res is not None:
                return res
            self._check_failed()
            fut = self._futures.get(rid)
            if fut is None:
                fut = self._loop.create_future()
                self._futures[rid] = fut
        return await fut

    def wait_result(self, rid: int,
                    timeout: Optional[float] = None) -> GenResult:
        """Sync twin of :meth:`result` for the thread backend."""
        with self._done_cv:
            if not self._done_cv.wait_for(
                    lambda: rid in self.sched.results
                    or self._error is not None, timeout):
                raise TimeoutError(f"rid {rid} not terminal in {timeout}s")
            if rid not in self.sched.results:
                self._check_failed()
            return self.sched.results[rid]

    def cancel(self, rid: int) -> None:
        """Cancel a request anywhere in its lifecycle; its stream ends
        with a CANCELLED terminal event."""
        with self._lock:
            self.sched.cancel(rid)

    # -------------------------------------------------------------- drain

    async def drain(self) -> None:
        """Wait until the scheduler has no queued/prefilling/active
        work (all submitted requests reached a terminal event)."""
        while True:
            with self._lock:
                self._check_failed()
                if not self.sched.has_work:
                    return
            await asyncio.sleep(0)

    def join(self, timeout_s: Optional[float] = None) -> None:
        """Sync twin of :meth:`drain`."""
        # join() guards a LIVE thread against hanging: the timeout must
        # follow real wall-clock even when the scheduler runs on a fake
        # clock, and the pacing sleep yields the GIL to the tick thread
        deadline = None
        if timeout_s is not None:
            # repro-lint: disable-next-line=replay-determinism
            deadline = time.monotonic() + timeout_s
        while True:
            with self._lock:
                self._check_failed()
                if not self.sched.has_work:
                    return
            # repro-lint: disable-next-line=replay-determinism
            if deadline is not None and time.monotonic() > deadline:
                raise TimeoutError("scheduler still has work")
            # repro-lint: disable-next-line=replay-determinism
            time.sleep(self.idle_sleep_s)

    def snapshot(self, reset_window: bool = False) -> Dict:
        """Locked passthrough to the scheduler's windowed metrics."""
        with self._lock:
            return self.sched.snapshot(reset_window=reset_window)
