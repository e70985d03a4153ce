"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read: device busy time, device time per operation and
per compiled program, and the longest idle gaps with the host span that
was open during each.

Device planes are those named ``/device:TPU:<n>``; their operations are
the events of the line ``XLA Ops`` (named here by their HLO instruction
name, with their output shape) and their programs those of
``XLA Modules``. An op that contains others (a layer scan's ``while``)
counts its whole span, so op times overlap; busy time is their union.
Host spans are the ``TraceAnnotation``s the harness opens (``bench.*``)
on the host plane. Times are in seconds.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# a TPU op event is named by its whole HLO instruction:
# "%name = f32[104,2,6,128]{...} custom-call(...)"
_HLO = re.compile(r"^%?([^\s=]+)(?: = \(?\w+\[([\d,]*)\])?")


def op_name(event_name: str):
    """(short op name, output shape) of a device op event."""
    m = _HLO.match(event_name)
    if not m:
        return event_name, ()
    shape = tuple(int(x) for x in m.group(2).split(",") if x) \
        if m.group(2) is not None else ()
    return m.group(1), shape


def _merge(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce_events(device: Dict[str, List[tuple]], host: List[tuple],
                  t0: float, t1: float) -> dict:
    """``device``: per device, events ``(line, name, start, end)``;
    ``host``: ``(name, start, end)`` spans; ``[t0, t1]`` the traced
    stretch. Returns busy seconds (mean over devices), seconds per op
    (with each op's output shape) and per program, and the ten longest
    idle gaps with the host span open in each."""
    ops: Dict[str, float] = {}
    shapes: Dict[str, tuple] = {}
    modules: Dict[str, float] = {}
    busy_total = 0.0
    gaps: List[Tuple[float, str]] = []
    spans = sorted(host, key=lambda s: s[1])
    for dev, evs in device.items():
        iv = []
        for line, name, a, b in evs:
            if line == "XLA Modules":
                modules[name] = modules.get(name, 0.0) + (b - a)
                continue
            short, shape = op_name(name)
            ops[short] = ops.get(short, 0.0) + (b - a)
            shapes[short] = shape
            iv.append((max(a, t0), min(b, t1)))
        merged = _merge([x for x in iv if x[1] > x[0]])
        busy_total += sum(b - a for a, b in merged)
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                what = [s[0] for s in spans if s[1] <= mid <= s[2]]
                gaps.append((b - a, what[-1] if what else "no host span"))
    n = max(len(device), 1)
    gaps.sort(reverse=True)
    return {"busy_s": busy_total / n, "window_s": t1 - t0, "ops": ops,
            "shapes": shapes, "modules": modules, "devices": n,
            "idle_gaps": [[w, s] for s, w in gaps[:10]]}


def read(profile_dir: str) -> dict:
    """Reduce the one trace written under ``profile_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(f"{profile_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {profile_dir}, "
                           f"found {len(paths)}")
    pd = ProfileData.from_file(paths[0])
    device: Dict[str, List[tuple]] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name not in ("XLA Ops", "XLA Modules"):
                    continue
                for ev in line.events:
                    a = ev.start_ns * 1e-9
                    evs.append((line.name, ev.name, a,
                                a + ev.duration_ns * 1e-9))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        a = ev.start_ns * 1e-9
                        host.append((ev.name, a, a + ev.duration_ns * 1e-9))
    ticks = [s for s in host if s[0] == "bench.tick"]
    if ticks:
        # the traced stretch: from the first traced tick to the last
        t0, t1 = min(s[1] for s in ticks), max(s[2] for s in ticks)
    else:
        allt = [t for evs in device.values() for e in evs for t in e[2:]]
        t0, t1 = (min(allt), max(allt)) if allt else (0.0, 0.0)
    out = reduce_events(device, host, t0, t1)
    out["host_spans"] = len(host)
    out["traced_ticks"] = len(ticks)
    return out
