"""Seeded deterministic fault injection for the serving scheduler
(DESIGN.md §8).

A :class:`FaultPlan` is a pure function of ``(seed, site, tick)``: every
draw comes from ``np.random.default_rng([seed, site_id, tick])``, so a
fault schedule is reproducible across runs, idempotent if a site is
consulted twice in one tick, and independent of consultation *order*
(the property that lets pool configurations whose tick counts differ
each get a deterministic schedule from one seed).

Three injection sites, mirroring the real failure classes a serving
pool sees:

  * ``alloc`` — transient allocator exhaustion: for a faulting tick the
    :class:`~repro.serving.cache.PageAllocator` embargoes ``holdback``
    free pages (``can_alloc`` sees a smaller heap; raw ``free_count``
    accounting is untouched so leak checks stay exact). The scheduler's
    existing eviction/preemption machinery reacts exactly as it would
    to genuine pressure; evictions forced while the embargo is active
    are charged to the victim's retry budget.
  * ``step`` — a device-step failure: :class:`InjectedStepFault` raised
    at the top of the fused decode dispatch, BEFORE any pool mutation,
    modeling a failed dispatch whose donated buffers were never
    consumed. The scheduler catches it, tears down a victim request and
    replays it from its original submission RNG.
  * ``nan`` — NaN-poisoned logits: a deterministic subset of pool rows
    gets non-finite logits after the model step. The scheduler detects
    the poisoned rows from a fused finite-mask and replays the owning
    requests; the pooled KAPPA controller's finite-guard
    (``core/kappa.py``) keeps the poison out of sibling branches'
    z-scores for the one dispatch that consumed it.

``max_faults`` caps the total number of fires (a storm that never ends
would starve every request past its retry budget); the cap consumes
fires in tick order so it is deterministic for a fixed tick sequence.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np


class InjectedStepFault(RuntimeError):
    """A FaultPlan-scheduled device-step failure (never raised by real
    device code — the scheduler's recovery path catches exactly this)."""


_SITE_IDS = {"step": 1, "alloc": 2, "nan": 3}


@dataclasses.dataclass
class FaultPlan:
    """Deterministic per-tick fault schedule. Default probabilities are
    tuned so a ``FaultPlan(seed=N)`` built from a bare ``seed:N`` CLI
    spec injects all three fault classes within a ~100-tick serve run."""

    seed: int
    p_step: float = 0.04       # device-step exception per tick
    p_alloc: float = 0.08      # allocator-exhaustion embargo per tick
    p_nan: float = 0.04        # NaN/Inf-poisoned logits per tick
    holdback: int = 2          # pages embargoed when an alloc fault fires
    nan_rows: int = 1          # pool rows poisoned when a nan fault fires
    max_faults: Optional[int] = None   # total fires before the plan goes quiet
    fired: int = 0             # fires so far (mutable bookkeeping)
    history: dict = dataclasses.field(default_factory=dict)

    def _rng(self, site: str, tick: int) -> np.random.Generator:
        return np.random.default_rng([self.seed, _SITE_IDS[site], tick])

    def _fire(self, site: str, tick: int, p: float) -> bool:
        # per-(site, tick) memo: a re-consulted tick (the scheduler may
        # re-enter a tick that didn't advance) replays the recorded
        # outcome without double-counting toward max_faults
        key = (site, tick)
        if key in self.history:
            return self.history[key]
        hit = False
        if p > 0.0 and (self.max_faults is None
                        or self.fired < self.max_faults):
            hit = bool(self._rng(site, tick).random() < p)
            if hit:
                self.fired += 1
        self.history[key] = hit
        return hit

    def step_fault(self, tick: int) -> bool:
        """Whether a device-step exception is scheduled for ``tick``."""
        return self._fire("step", tick, self.p_step)

    def page_holdback(self, tick: int) -> int:
        """Pages the allocator must embargo this tick (0 = no fault)."""
        return self.holdback if self._fire("alloc", tick, self.p_alloc) \
            else 0

    def nan_rows_for(self, tick: int, rows: int) -> np.ndarray:
        """Pool rows whose logits get poisoned this tick (possibly
        empty). Row choice is part of the same deterministic draw."""
        if not self._fire("nan", tick, self.p_nan):
            return np.empty((0,), np.int64)
        rng = self._rng("nan", tick)
        k = min(self.nan_rows, rows)
        return rng.choice(rows, size=k, replace=False)


def parse_fault_spec(spec: str) -> FaultPlan:
    """Build a FaultPlan from a CLI spec like ``seed:7`` or
    ``seed:7,step:0.1,alloc:0.2,nan:0.05,holdback:4,max:20``."""
    kw: dict = {}
    keys = {"seed": ("seed", int), "step": ("p_step", float),
            "alloc": ("p_alloc", float), "nan": ("p_nan", float),
            "holdback": ("holdback", int), "rows": ("nan_rows", int),
            "max": ("max_faults", int)}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        k, _, v = part.partition(":")
        if k not in keys or not v:
            raise ValueError(f"bad fault spec entry {part!r} "
                             f"(known keys: {sorted(keys)})")
        field_name, conv = keys[k]
        kw[field_name] = conv(v)
    if "seed" not in kw:
        raise ValueError(f"fault spec {spec!r} needs a seed (e.g. 'seed:7')")
    return FaultPlan(**kw)
