"""Named phase spans of the serving tick, on the profiler's clock.

Every phase of a scheduler tick (and the front end's yield between
ticks) runs inside ``times.span(name)``. A span does two things:

* it opens ``jax.profiler.TraceAnnotation("serve." + name)``, so a
  profiler trace holds the phase on the host plane, on the same clock
  as the device's programs, and each device idle gap can be named by
  the phase the host was in;
* it adds the phase's host seconds to ``times`` (:class:`PhaseTimes`,
  the scheduler's ``tick_time``), cumulative over the run.

A parent span's seconds include its children's. There is no switch:
with no profiler running a span costs one annotation object and two
clock reads, and a tick opens about fifteen, one per phase, never one
per request. Spans neither wait for the device nor change what runs.

Full collections of the garbage collector (generation 2) are annotated
as ``serve.gc`` once :func:`watch_gc` has been called, in whatever
phase they interrupt.
"""
from __future__ import annotations

import gc
import time
from typing import Dict, Iterable, List, Optional

from jax.profiler import StepTraceAnnotation, TraceAnnotation

PREFIX = "serve."

# the scheduler's phases, in the order a tick opens them ("frontend" is
# the front end's yield after a tick)
PHASES = ("tick", "admit", "prefill", "pages", "step", "keys",
          "sample", "control", "sync", "host", "emit", "frontend")


def _now() -> float:
    """The one clock of the span totals: real host time, also where the
    scheduler's request-visible clock is injected, since the totals
    measure cost and never feed a decision."""
    return time.perf_counter()


class _Span:
    __slots__ = ("times", "name", "note", "t0")

    def __init__(self, times: Dict[str, float], name: str,
                 step: Optional[int]):
        self.times, self.name = times, name
        self.note = TraceAnnotation(PREFIX + name) if step is None \
            else StepTraceAnnotation(PREFIX + name, step_num=step)

    def __enter__(self) -> "_Span":
        self.note.__enter__()
        self.t0 = _now()
        return self

    def __exit__(self, *exc) -> None:
        self.times[self.name] += _now() - self.t0
        self.note.__exit__(*exc)


class PhaseTimes(dict):
    """Host seconds per phase, cumulative; ``span(name)`` times one
    phase and annotates it in the profiler's trace (``step``: the tick
    index, for a step annotation)."""

    def __init__(self, names: Iterable[str] = PHASES):
        super().__init__(dict.fromkeys(names, 0.0))

    def span(self, name: str, step: Optional[int] = None) -> _Span:
        if name not in self:
            raise KeyError(f"no phase {name!r}")
        return _Span(self, name, step)


# the open annotation of a collection in progress (collections do not
# nest, and start and stop come on one thread)
_gc_open: List[TraceAnnotation] = []


def _on_gc(phase: str, info: dict) -> None:
    if info.get("generation", 0) < 2:
        return
    if phase == "start":
        note = TraceAnnotation(PREFIX + "gc")
        note.__enter__()
        _gc_open.append(note)
    elif _gc_open:
        _gc_open.pop().__exit__(None, None, None)


def watch_gc() -> None:
    """Annotate full collections as ``serve.gc``; once per process,
    however often it is called."""
    if _on_gc not in gc.callbacks:
        gc.callbacks.append(_on_gc)
