"""Which program phase the host was in while the device sat idle:

  python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

runs one cell as ``bench/run.py --trace 1`` does and prints one JSON
line: the harness's result (``result``) and ``phases``, what the
trace's ``serve.<phase>`` spans (``repro/serving/spans.py``) and the
scheduler's per-phase host seconds (``tick_time``) say of it: host ms
per window tick in each phase (with ``prep_host_ms`` and
``frontend_ms``), device-idle ms per traced tick under the sampler's
key phases (``keys_idle_ms``), the window's longest tick with its
phases, the window's full collections, traced against untraced tick
time, and the host cost of one span with no profiler running.

The reduction itself (:func:`reduce_phases`) reads the same device
events as ``bench/trace.py`` and the host spans named ``bench.*`` and
``serve.*``. It gives the device-idle seconds under each innermost
span, each idle gap cut at span boundaries (``idle_by_span``); the ten
longest gaps named by the innermost span at their midpoint
(``idle_gaps``, ``bench/trace.py``'s rule with the program's spans
added), and the longest gap's own split; a check of the two clocks
(``clock_skew_ms``: how far the last sampler or controller program that
started before a tick's ``serve.sync`` ended runs past the end of that
blocking wait, which it cannot, largest over the traced ticks; 0 is
none); and the traced ticks in which no device operation ran at all
(``dark_ticks``), which is what a device trace that misses ticks looks
like. Times are in seconds unless a name says otherwise.
"""
from __future__ import annotations

import argparse
import bisect
import gc
import glob
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from bench import trace  # noqa: E402

# the programs a tick's second blocking transfer waits for
SYNCED = ("_sample_rows", "_pooled_kappa_tick")
KEYS_PHASES = ("serve.keys", "serve.keys_wait", "serve.sample")
PREP_PHASES = ("admit", "prefill", "pages")
NONE = "no host span"


def read_events(profile_dir: str):
    """Device events ``{plane: [(line, name, start, end)]}`` and host
    spans ``[(name, start, end)]`` of the one trace under
    ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, "
                           f"found {len(paths)}")
    device: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in ProfileData.from_file(paths[0]).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name in ("XLA Ops", "XLA Modules"):
                    for ev in line.events:
                        a = ev.start_ns * 1e-9
                        evs.append((line.name, ev.name, a,
                                    a + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", "serve.")):
                        a = ev.start_ns * 1e-9
                        host.append((ev.name, a, a + ev.duration_ns * 1e-9))
    return device, host


def _segments(host: List[tuple]) -> Tuple[List[float], List[str]]:
    """Cut time at every span boundary; each piece is named by its
    innermost span (the latest started of those open over it, the
    shortest on a tie). Returns the cut points and the name of each
    piece between consecutive points."""
    cuts = sorted({t for _, a, b in host for t in (a, b)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        open_ = [s for s in host if s[1] <= a and b <= s[2]]
        best = max(open_, key=lambda s: (s[1], -s[2]), default=None)
        names.append(best[0] if best else NONE)
    return cuts, names


def _split(a: float, b: float, cuts, names) -> Dict[str, float]:
    """Seconds of ``[a, b]`` under each innermost span."""
    out: Dict[str, float] = {}
    i = max(bisect.bisect_right(cuts, a) - 1, 0)
    t = a
    if not cuts or a < cuts[0]:
        end = min(b, cuts[0]) if cuts else b
        out[NONE] = end - a
        t = end
    while t < b and i < len(names):
        end = min(b, cuts[i + 1])
        if end > t:
            out[names[i]] = out.get(names[i], 0.0) + end - t
            t = end
        i += 1
    if t < b:
        out[NONE] = out.get(NONE, 0.0) + b - t
    return out


def _label(t: float, cuts, names) -> str:
    """The innermost span at ``t``."""
    i = bisect.bisect_right(cuts, t) - 1
    return names[i] if 0 <= i < len(names) else NONE


def clock_skew_ms(device: Dict[str, List[tuple]], host: List[tuple],
                  t0: float, t1: float) -> Optional[float]:
    """Largest overshoot, in ms, of the last sampler or controller
    program that started before a traced ``serve.sync`` ended past the
    end of that span; None without such a span and program."""
    progs = sorted((a, b) for evs in device.values()
                   for line, name, a, b in evs
                   if line == "XLA Modules" and any(p in name for p in SYNCED))
    starts = [a for a, _ in progs]
    worst = None
    for name, a, b in host:
        if name != "serve.sync" or not t0 <= a <= t1:
            continue
        i = bisect.bisect_left(starts, b) - 1
        if i < 0:
            continue
        over = max(0.0, progs[i][1] - b)
        worst = over if worst is None else max(worst, over)
    return None if worst is None else 1e3 * worst


def reduce_phases(device: Dict[str, List[tuple]], host: List[tuple],
                  t0: float, t1: float) -> dict:
    """Idle seconds per innermost span over ``[t0, t1]`` (mean over
    devices), the ten longest gaps with the span at their midpoint, the
    longest gap's split, the clock check, and the traced ticks with no
    device operation."""
    cuts, names = _segments(host)
    by_span: Dict[str, float] = {}
    gaps: List[Tuple[float, float, float]] = []
    busy_iv: List[Tuple[float, float]] = []
    for evs in device.values():
        iv = [(max(a, t0), min(b, t1)) for line, _, a, b in evs
              if line == "XLA Ops"]
        merged = trace._merge([x for x in iv if x[1] > x[0]])
        busy_iv += merged
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((b - a, a, b))
                for k, v in _split(a, b, cuts, names).items():
                    by_span[k] = by_span.get(k, 0.0) + v
    n = max(len(device), 1)
    gaps.sort(reverse=True)
    longest = None
    if gaps:
        w, a, b = gaps[0]
        longest = {"seconds": w, "from_stretch_start": a - t0,
                   "split": dict(sorted(_split(a, b, cuts, names).items(),
                                        key=lambda kv: -kv[1]))}
    busy = trace._merge(busy_iv)
    starts = [a for a, _ in busy]
    ticks = [s for s in host if s[0] == "serve.tick" and t0 <= s[1] <= t1]
    dark = 0
    for _, a, b in ticks:
        i = bisect.bisect_right(starts, b) - 1
        if i < 0 or busy[i][1] < a:
            dark += 1
    return {
        "idle_by_span": {k: v / n for k, v in
                         sorted(by_span.items(), key=lambda kv: -kv[1])},
        "idle_gaps": [[_label(0.5 * (a + b), cuts, names), w]
                      for w, a, b in gaps[:10]],
        "longest_gap": longest,
        "clock_skew_ms": clock_skew_ms(device, host, t0, t1),
        "serve_ticks": len(ticks),
        "dark_ticks": dark,
        "gc": [b - a for name, a, b in host
               if name == "serve.gc" and t0 <= a <= t1],
    }


def read(profile_dir: str) -> dict:
    """Reduce the trace under ``profile_dir`` over the stretch that
    ``bench/trace.py`` reads (first to last ``bench.tick``)."""
    device, host = read_events(profile_dir)
    ticks = [s for s in host if s[0] == "bench.tick"]
    if not ticks:
        return {}
    t0, t1 = min(s[1] for s in ticks), max(s[2] for s in ticks)
    out = reduce_phases(device, host, t0, t1)
    out["window_s"] = t1 - t0
    return out


def tick_phases(rec_ticks: List[dict], snaps: List[dict], w0: float,
                w1: float) -> List[Tuple[dict, Dict[str, float]]]:
    """Each window tick's record with its host seconds per
    ``tick_time`` phase, from the snapshot taken after every tick (the
    ticks of ``bench/stats.py``'s window: begun inside it, after the
    first)."""
    return [(rec_ticks[i], {k: v - snaps[i - 1].get(k, 0.0)
                            for k, v in snaps[i].items()})
            for i in range(1, len(rec_ticks))
            if w0 <= rec_ticks[i]["t0"] < w1]


def window_phase_ms(per_tick) -> Dict[str, float]:
    """Host ms per window tick in each phase."""
    tot: Dict[str, float] = {}
    for _, d in per_tick:
        for k, v in d.items():
            tot[k] = tot.get(k, 0.0) + v
    return {k: 1e3 * v / len(per_tick) for k, v in tot.items()}


def longest_tick(per_tick, w0: float) -> Optional[dict]:
    """The window's longest tick on the host clock, with its phases."""
    if not per_tick:
        return None
    rec, d = max(per_tick, key=lambda td: td[0]["t1"] - td[0]["t0"])
    return {"ms": 1e3 * (rec["t1"] - rec["t0"]), "traced": rec["traced"],
            "from_window_open_s": rec["t0"] - w0,
            "phases_ms": {k: 1e3 * v for k, v in
                          sorted(d.items(), key=lambda kv: -kv[1])}}


def span_cost_us(n: int = 20000) -> float:
    """Host cost of one phase span with no profiler running, in us."""
    from repro.serving.spans import PhaseTimes
    tt = PhaseTimes()
    t = time.perf_counter()
    for _ in range(n):
        with tt.span("host"):
            pass
    return 1e6 * (time.perf_counter() - t) / n


def run(cell: str, seed: int, seconds: float, **kw) -> dict:
    """One ``--trace 1`` run of ``cell`` through the harness (``kw``
    goes to ``harness.run_cell``), with the phase reduction of its trace,
    a ``tick_time`` snapshot after every tick, and the full collections
    in the window on the host clock."""
    from bench import harness, model, stats
    snaps: List[dict] = []
    kept: Dict[str, dict] = {}
    make, read_trace = model.make_scheduler, harness.trace_lib.read

    def make_scheduler(*args, **kw):
        sched = make(*args, **kw)
        tick = sched.tick

        def tick_and_snapshot():
            tick()
            snaps.append(dict(sched.tick_time))

        sched.tick = tick_and_snapshot
        return sched

    def read_both(profile_dir):
        kept.update(read(profile_dir))
        return read_trace(profile_dir)

    pauses: List[List[float]] = []     # full collections, host clock

    def on_gc(phase, info):
        if info["generation"] == 2:
            if phase == "start":
                pauses.append([time.perf_counter(), 0.0])
            elif pauses:
                pauses[-1][1] = time.perf_counter()

    model.make_scheduler = make_scheduler
    harness.trace_lib.read = read_both
    gc.callbacks.append(on_gc)
    try:
        r = harness.run_cell(cell, seed, seconds, True, **kw)
    finally:
        model.make_scheduler, harness.trace_lib.read = make, read_trace
        gc.callbacks.remove(on_gc)
    ph = dict(kept)
    per_tick = tick_phases(r.rec.ticks, snaps, r.w0, r.w1)
    ms = window_phase_ms(per_tick) if per_tick else {}
    ph["window_phase_ms"] = ms
    ph["longest_tick"] = longest_tick(per_tick, r.w0)
    ph["gc_window_ms"] = [1e3 * (b - a) for a, b in pauses
                          if b and r.w0 <= a < r.w1]
    if ms:
        ph["prep_host_ms"] = sum(ms.get(k, 0.0) for k in PREP_PHASES)
        ph["frontend_ms"] = ms.get("frontend")
    traced = [t for t in r.rec.ticks if t["traced"]]
    if traced and "idle_by_span" in ph:
        ph["keys_idle_ms"] = 1e3 * sum(
            ph["idle_by_span"].get(k, 0.0) for k in KEYS_PHASES) / len(traced)
    window = stats.window_ticks(r)
    for key, ticks in (("traced", [t for t in window if t["traced"]]),
                       ("untraced", [t for t in window if not t["traced"]])):
        if ticks:
            ph[f"tick_ms_{key}"] = 1e3 * sum(
                t["t1"] - t["t0"] for t in ticks) / len(ticks)
            ph[f"ticks_{key}"] = len(ticks)
    ph["span_cost_us"] = span_cost_us()
    return {"result": r.result, "phases": ph}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        out = run(args.workload, args.seed, args.seconds)
    except harness.NoAccelerator as e:
        print(f"phases: {e}", file=sys.stderr)
        return 2
    print(f"phases: clock_skew_ms {out['phases'].get('clock_skew_ms')!r}",
          file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
