"""Arithmetic of the end-to-end metrics, from what the clients saw:
due times, delivery times, and how many tokens each delivery carried."""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple



def percentile(values: Sequence[float], p: float) -> Optional[float]:
    """Nearest-rank percentile (the smallest value with at least p% of
    the sample at or below it); None for an empty sample."""
    v = sorted(values)
    if not v:
        return None
    k = max(1, math.ceil(p / 100.0 * len(v)))
    return float(v[k - 1])


def deliveries(events: List[Tuple[float, float, int]]):
    """Group one stream's token events ``(emitted_at, received_at,
    n_tokens)`` into deliveries: tokens the server emitted together
    (one tick, or KAPPA's flush at decision) are one delivery, at the
    time the client received the first of them."""
    out: List[List[float]] = []
    for emitted, received, n in events:
        if out and out[-1][0] == emitted:
            out[-1][2] += n
        else:
            out.append([emitted, received, n])
    return [(r, n) for _, r, n in out]


def window_tokens(streams: Dict[int, list], t0: float, t1: float) -> int:
    return sum(n for ds in streams.values() for r, n in ds if t0 <= r < t1)


def itl_gaps(streams: Dict[int, list], t0: float, t1: float) -> List[float]:
    """Gaps between successive deliveries of one stream, after its
    first, that end inside the window."""
    gaps = []
    for ds in streams.values():
        for (a, _), (b, _) in zip(ds, ds[1:]):
            if t0 <= b < t1:
                gaps.append(b - a)
    return gaps


def ttfts(due: Dict[int, float], streams: Dict[int, list],
          t0: float, t1: float) -> Tuple[List[float], int]:
    """Time from when each request was due (inside the window) to its
    first delivered token; requests with no token count as failed."""
    out, failed = [], 0
    for rid, d in due.items():
        if not t0 <= d < t1:
            continue
        ds = streams.get(rid)
        if ds:
            out.append(ds[0][0] - d)
        else:
            failed += 1
    return out, failed


# ----------------------------------------------- helpers for the readers

def client_streams(run) -> Dict[int, list]:
    """Deliveries of every stream the window's clients could see (the
    warm-up's requests left out)."""
    return {rid: deliveries(ev) for rid, ev in run.client.events.items()
            if run.tags.get(rid) != "warmup"}


def window_ticks(run) -> List[dict]:
    """The ticks that began inside the window, each with the change in
    the program's cumulative counters across it (``d_<key>``)."""
    out = []
    prev = None
    for rec in run.rec.ticks:
        if prev is not None and run.w0 <= rec["t0"] < run.w1:
            r = dict(rec)
            for k in ("host_s", "preemptions", "page_ticks"):
                r["d_" + k] = rec[k] - prev[k]
            out.append(r)
        prev = rec
    return out
