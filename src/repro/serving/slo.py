"""SLO-adaptive admission controller over the tick schedulers.

Closes the loop the ROADMAP's async-front-end item asks for: watch the
windowed TTFT/ITL percentiles the scheduler accumulates (DESIGN.md §9,
``snapshot(reset_window=True)``) and adapt the admission/prefill knobs
each window to hold a target ITL p99, degrading to SHED before latency
collapses instead of after.

The controller is a small hysteretic state machine over an escalation
``level``; each level turns one more knob:

======  ======================================================
level   action (cumulative)
======  ======================================================
0       steady state — base ``prefill_chunk``, unbounded
        ``prefill_budget``, base ``max_queue``
1       pace admission: set ``sched.prefill_budget`` to one base
        chunk of prompt tokens per tick, so burst arrivals are
        admitted one per tick instead of riding the same fused
        dispatch (k same-tick chunks k-fold inflate every active
        request's ITL for that tick); also halve
        ``prefill_chunk`` (smaller chunks interleave finer with
        decode ticks) unless ``min_prefill_chunk`` pins it
2       pause admission (``sched.admit_paused``) — queued work
        waits, active requests drain
3       halve the effective ``max_queue`` — the bounded queue now
        sheds at the door (terminal SHED) rather than queueing
        into certain deadline misses
======  ======================================================

A *violated* window (``itl_p99 > target``, with enough samples to
trust the percentile) escalates one level; a *healthy* window
(``itl_p99 <= recover_frac * target``, or too few samples to judge —
an idle/draining pool must not stay wedged shut) de-escalates one
level; anything in between holds (hysteresis). Every evaluation is
appended to ``history``, the record of the controller's path.

Windows are tick-counted (``window_ticks``), not wall-timed: the
driving loop calls ``on_tick()`` after every scheduler tick and the
controller evaluates every N ticks through the injectable scheduler
clock — fully deterministic under a fake clock in tests.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional


@dataclasses.dataclass
class SLOConfig:
    """Targets + controller shape. ``target_itl_p99_s`` is the held
    SLO; ``target_ttft_p99_s`` optionally escalates on TTFT too."""
    target_itl_p99_s: float
    target_ttft_p99_s: Optional[float] = None
    window_ticks: int = 32          # evaluate every N scheduler ticks
    min_itl_samples: int = 8        # below this a percentile is noise
    recover_frac: float = 0.7       # healthy when p99 <= frac * target
    max_level: int = 3
    min_prefill_chunk: int = 1
    # conservative start: begin at this escalation level and let healthy
    # windows relax it — a controller that only reacts AFTER a violated
    # window has already served that window's burst at full blast
    start_level: int = 0


class SLOController:
    """Attach to a scheduler and call :meth:`on_tick` after every tick
    (the :class:`~repro.serving.frontend.ServingFrontend` does this for
    you). ``update()`` may also be called directly to force a window
    evaluation — the unit tests drive it that way."""

    def __init__(self, sched, cfg: SLOConfig):
        self.sched = sched
        self.cfg = cfg
        self.level = cfg.start_level
        self.history: List[Dict] = []
        # base knob values to restore on de-escalation
        self._base_chunk: Optional[int] = sched.prefill_chunk
        self._base_budget: Optional[int] = getattr(
            sched, "prefill_budget", None)
        self._base_queue: Optional[int] = sched.max_queue
        self._shed_queue: Optional[int] = None
        self._last_eval_tick = sched.ticks
        if self.level:
            self._apply()

    # ------------------------------------------------------------ driving

    def on_tick(self) -> Optional[Dict]:
        """Window boundary check; evaluates every ``window_ticks``."""
        if self.sched.ticks - self._last_eval_tick < self.cfg.window_ticks:
            return None
        return self.update()

    def update(self) -> Dict:
        """Evaluate one window: read-and-reset the scheduler's windowed
        percentiles, move the escalation level, apply the knobs."""
        cfg = self.cfg
        snap = self.sched.snapshot(reset_window=True)
        self._last_eval_tick = self.sched.ticks

        enough = snap["itl_count"] >= cfg.min_itl_samples
        violated = enough and snap["itl_p99_s"] > cfg.target_itl_p99_s
        if cfg.target_ttft_p99_s is not None \
                and snap["ttft_count"] >= cfg.min_itl_samples:
            violated = violated or (snap["ttft_p99_s"]
                                    > cfg.target_ttft_p99_s)
        # healthy = clearly under target, or nothing to measure (an
        # idle/drained pool must unwedge a paused admission gate)
        healthy = (not violated
                   and (not enough
                        or snap["itl_p99_s"]
                        <= cfg.recover_frac * cfg.target_itl_p99_s))

        if violated:
            self.level = min(self.level + 1, cfg.max_level)
        elif healthy:
            self.level = max(self.level - 1, 0)
        self._apply()

        snap.update({"level": self.level, "violated": violated,
                     "healthy": healthy,
                     "prefill_chunk": self.sched.prefill_chunk,
                     "prefill_budget": getattr(self.sched,
                                               "prefill_budget", None),
                     "max_queue": self.sched.max_queue})
        self.history.append(snap)
        return snap

    # ------------------------------------------------------------- knobs

    def _apply(self) -> None:
        s = self.sched
        # level >= 1: pace admission to one base chunk of new prompt
        # tokens per tick and halve the chunks themselves (both only
        # meaningful when the scheduler prefills chunked at all)
        if self._base_chunk is not None:
            s.prefill_budget = (self._base_budget if self.level < 1
                                else max(1, self._base_chunk))
            s.prefill_chunk = (self._base_chunk if self.level < 1 else
                               max(self.cfg.min_prefill_chunk,
                                   self._base_chunk // 2))
        # level >= 2: stop admitting — active requests drain first
        s.admit_paused = self.level >= 2
        # level >= 3: shrink the bounded queue so overload sheds at the
        # door; sized once per episode off the base (or current) depth
        if self.level >= self.cfg.max_level:
            if self._shed_queue is None:
                base = (self._base_queue if self._base_queue is not None
                        else len(s.queue))
                self._shed_queue = max(1, base // 2)
            s.max_queue = self._shed_queue
        else:
            s.max_queue = self._base_queue
            self._shed_queue = None
