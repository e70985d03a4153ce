"""One run of one cell: set-up, the measured window, the readings, and
the comparison with the plain reference that decides ``correct``.

Everything that belongs to a configuration, a traffic mix or a metric
is found by name under ``bench/``: ``configs/<config>.json``,
``traffic/<traffic>.json``, ``metrics/<metric>.py`` (or the part of the
metric's name before its first dot), and ``limits/<cell>.json``.

The served path is the program's: ``ServingFrontend`` over
``PagedScheduler`` (chunked prefill into 64-token pages, the Pallas
paged kernels, the fused sampler and the pooled KAPPA controller),
driven from one asyncio loop that also runs the clients. The harness
only wraps calls into it to record host spans and counters.
"""
from __future__ import annotations

import asyncio
import gc
import importlib.util
import json
import sys
import tempfile
import time
from contextlib import nullcontext
from pathlib import Path
from typing import Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402

from bench import model, reference, trace as trace_lib  # noqa: E402
from bench import traffic as traffic_lib, weights  # noqa: E402
from bench.peaks import peaks  # noqa: E402
from repro.serving import strategies  # noqa: E402

# JAX reports each program it traces
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"


class NoAccelerator(RuntimeError):
    """The run needs chips that JAX does not have."""


def log(run, what: str) -> None:
    """A progress line on standard error, seconds since process start."""
    print(f"bench: {time.perf_counter() - run.t_start:8.2f}s {what}",
          file=sys.stderr, flush=True)


# ------------------------------------------------------------- discovery

def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


class Bench:
    """``BENCHMARK.json`` and the files it names, under ``root``."""

    def __init__(self, root: Path = ROOT):
        self.root = Path(root)
        self.dir = self.root / "bench"
        self.spec = load_json(self.root / "BENCHMARK.json")

    def cell(self, name: str) -> dict:
        for c in self.spec["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def config(self, name: str) -> dict:
        for c in self.spec["configs"]:
            if c["name"] == name:
                mc = load_json(self.root / c["file"])
                mc.setdefault("name", name)
                mc.setdefault("source", c["source"])
                return mc
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        return load_json(self.dir / "traffic" / f"{name}.json")

    def limits(self, cell: str) -> dict:
        return load_json(self.dir / "limits" / f"{cell}.json")

    def metrics(self, cell: str, kind: str) -> List[dict]:
        """The cell's ``end_to_end`` or ``per_layer`` metrics: those that
        list it, or list no cells (per-layer ones then go wherever the
        end-to-end metric they move is reported)."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if kind == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in names
                                 else [])]

    def reader(self, metric: str):
        """The module that computes ``metric``: ``metrics/<name>.py``,
        else ``metrics/<name before the first dot>.py``."""
        for stem in (metric, metric.split(".")[0]):
            path = self.dir / "metrics" / f"{stem}.py"
            if path.exists():
                spec = importlib.util.spec_from_file_location(
                    f"bench_metric_{stem.replace('-', '_')}", path)
                mod = importlib.util.module_from_spec(spec)
                spec.loader.exec_module(mod)
                return mod
        raise KeyError(f"no reader for metric {metric!r} under "
                       f"{self.dir / 'metrics'}")


# --------------------------------------------------------- device checks

def device_info(chips: int, require_tpu: bool = True) -> dict:
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoAccelerator(f"JAX's first device is {devs[0].platform!r} "
                            f"({devs[0].device_kind}), not a TPU")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX has "
                            f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak() -> int:
    s = jax.devices()[0].memory_stats() or {}
    return int(s.get("peak_bytes_in_use", 0))


class CompileCounter:
    """Programs traced while ``window`` is set: each is compiled, or
    fetched from the persistent cache, inside the window."""

    def __init__(self):
        self.window = False
        self.traced = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_) -> None:
        if self.window and event == _TRACE_EVENT:
            self.traced += 1


# ----------------------------------------------------------- the record

class Recorder:
    """Host spans around each scheduler tick and the counters read at
    its end, plus what each tick fed the model: the decode rows'
    positions and each prefill chunk's (start, length)."""

    def __init__(self, sched):
        self.sched = sched
        self.ticks: List[dict] = []
        self.cur: Optional[dict] = None
        self.trace_from: Optional[float] = None   # host time to start
        self.trace_until: Optional[float] = None
        self.trace_dir: Optional[str] = None
        self.tracing = False
        self.traced = False
        orig_tick, orig_decode = sched.tick, sched._decode_tick
        orig_chunk = sched._chunk_args

        def tick():
            now = time.perf_counter()
            if (self.trace_from is not None and not self.traced
                    and not self.tracing and now >= self.trace_from):
                jax.profiler.start_trace(self.trace_dir)
                self.tracing = True
            rec = {"t0": time.perf_counter(), "pos": None, "chunks": [],
                   "queue": len(sched.queue), "traced": self.tracing}
            self.cur = rec
            span = jax.profiler.TraceAnnotation("bench.tick") \
                if self.tracing else nullcontext()
            with span:
                orig_tick()
            rec["t1"] = time.perf_counter()
            rec["host_s"] = sched.tick_time["host"]
            rec["preemptions"] = sched.counters["preemptions"]
            rec["page_ticks"] = sched._page_ticks
            self.cur = None
            self.ticks.append(rec)
            if self.tracing and rec["t1"] >= self.trace_until:
                jax.profiler.stop_trace()
                self.tracing, self.traced = False, True

        def decode_tick():
            occ = [s for _, slots in sched.active.values() for s in slots]
            if self.cur is not None:
                self.cur["pos"] = sched.row_pos[occ].copy()
            return orig_decode()

        def chunk_args(pf, c):
            if self.cur is not None:
                self.cur["chunks"].append((int(pf.filled), int(c)))
            return orig_chunk(pf, c)

        sched.tick = tick
        sched._decode_tick = decode_tick
        sched._chunk_args = chunk_args

    def stop_tracing(self):
        if self.tracing:
            jax.profiler.stop_trace()
            self.tracing, self.traced = False, True


# ------------------------------------------------------------ the drive

class Client:
    """What one stream's client saw: per rid, its token events
    ``(emitted_at, received_at, 1)`` and its terminal status."""

    def __init__(self):
        self.events: Dict[int, list] = {}
        self.status: Dict[int, str] = {}
        self.tokens: Dict[int, list] = {}


async def _consume(fe, rid: int, cl: Client) -> str:
    evs = cl.events.setdefault(rid, [])
    toks = cl.tokens.setdefault(rid, [])
    async for ev in fe.events(rid):
        now = time.perf_counter()
        if ev.kind == "token":
            evs.append((ev.t, now, 1))
            toks.append(ev.token)
        else:
            cl.status[rid] = ev.status
            return ev.status
    return cl.status.get(rid, "")


async def _wait(fe, cond, poll: float = 0.0):
    """Yield to the tick loop until ``cond()``; a failed tick (which
    stops the loop) is raised here instead of waited on forever."""
    while not cond():
        fe._check_failed()
        await asyncio.sleep(poll)


class Run:
    """State of one run: what the readers of the metrics get."""


def _submit(fe, req, key, meta: dict) -> int:
    rid = fe.submit_nowait(req.prompt, key, max_new=req.max_new,
                           method=req.method)
    meta[rid] = req
    return rid


async def _serve(run: Run) -> None:
    sched, fe, tr, cl = run.sched, run.fe, run.traffic, run.client
    fe.start_async()
    keybase = jax.random.fold_in(weights.seed_key(run.seed), 7)
    counter = iter(range(1 << 30))

    def key():
        return jax.random.fold_in(keybase, next(counter))

    consumers = {}

    def start(req, tag):
        rid = _submit(fe, req, key(), run.requests)
        run.tags[rid] = tag
        consumers[rid] = asyncio.ensure_future(_consume(fe, rid, cl))
        return rid

    def settled(rids):
        # admitted and past prefill (or already ended)
        queued = {i.rid for i in sched.queue}
        return not any(r in sched.prefilling or r in queued for r in rids)

    vocab = run.mc["vocab_size"]
    method = tr["method"]
    fan_out = strategies.make_strategy(method).rows(run.kcfg)
    for n in range(1, fan_out + 1):
        jax.block_until_ready(jax.random.split(key(), n))
    # 1. the population in flight at window open, group by group: a
    # group is submitted once its fan-out fits the free rows, so that
    # its chunks are fused in lockstep
    pre = tr["preroll"]
    sched.prefill_chunk, sched.prefill_budget = pre["prefill_chunk"], None
    groups = traffic_lib.preroll(tr, run.seed, vocab,
                                 max_seq=run.serving["max_seq"])
    pre_rids = []
    for i, g in enumerate(groups):
        await _wait(fe, lambda: len(sched.free) >= fan_out * len(g))
        rids = [start(r, "preroll") for r in g]
        pre_rids += rids
        await _wait(fe, lambda: settled(rids))
        log(run, f"pre-roll group {i + 1}/{len(groups)} of {len(g)} x "
                 f"{len(g[0].prompt)} tokens admitted, {sched.ticks} ticks")
    # 2. until every request of it has decided (delivered its first
    # committed token) or ended
    await _wait(fe, lambda: all(cl.events.get(r) or r in cl.status
                                for r in pre_rids))
    log(run, f"pre-roll decided, {sched.ticks} ticks, "
             f"{sum(r not in cl.status for r in pre_rids)} in flight, "
             f"{len(sched.free)} rows free")
    # 3. every prompt length the mix sends, one chunk each, admitted one
    # a tick beside running decode rows, as in the window
    sched.prefill_chunk = run.serving["prefill_chunk"]
    sched.prefill_budget = 1
    wrids = [start(traffic_lib.Request(
        np.random.default_rng([run.seed, 91, n]).integers(
            0, vocab, n, dtype=np.int32), tr["warmup"]["new_tokens"],
        method), "warmup") for n in traffic_lib.prompt_lengths(tr)]
    await _wait(fe, lambda: all(r in cl.status for r in wrids))
    log(run, f"prompt lengths warmed, {sched.ticks} ticks")

    # 4. the window
    arr = tr["arrival"]
    reqs = iter(traffic_lib.requests(tr, run.seed, vocab,
                                     tr["requests"]))
    run.setup_s = time.perf_counter() - run.t_start
    run.compiles.window = True
    log(run, "window open")
    ticks0 = sched.ticks
    w0 = time.perf_counter()
    run.w0, run.w1 = w0, w0 + run.seconds
    if run.trace:
        run.rec.trace_from = w0 + 0.25 * run.seconds
        run.rec.trace_until = run.rec.trace_from + min(
            tr.get("trace_seconds", 3.0), 0.5 * run.seconds)
    stop = {"flag": False}
    if arr["mode"] == "closed":
        n_clients = int(round(arr["clients_per_row"] * run.serving["rows"]))

        async def client(first: Optional[int]):
            if first is not None:
                await consumers[first]
            while not stop["flag"]:
                rid = start(next(reqs), "window")
                run.due[rid] = time.perf_counter()
                await consumers[rid]

        live = [r for r in pre_rids if r not in cl.status]
        tasks = [asyncio.ensure_future(client(r)) for r in live]
        tasks += [asyncio.ensure_future(client(None))
                  for _ in range(max(0, n_clients - len(live)))]
        await asyncio.sleep(max(0.0, run.w1 - time.perf_counter()))
        stop["flag"] = True
    else:
        due = traffic_lib.arrivals(tr, run.seed, run.seconds)
        for t in due:
            await asyncio.sleep(max(0.0, w0 + t - time.perf_counter()))
            now = time.perf_counter()
            rid = start(next(reqs), "window")
            run.due[rid] = w0 + t
            run.late.append(now - (w0 + t))
        await asyncio.sleep(max(0.0, run.w1 - time.perf_counter()))
        # requests due in the window are waited for until their first
        # token, a minute past the close at most
        limit = run.w1 + arr.get("drain_s", 60.0)
        await _wait(fe, lambda: all(cl.events.get(r) or r in cl.status
                                for r in run.due)
                    or time.perf_counter() > limit, 0.001)
    run.t_close = time.perf_counter()
    run.compiles.window = False
    log(run, f"window closed, {sched.ticks - ticks0} ticks in it")
    run.rec.stop_tracing()
    run.memory_peak = memory_peak()
    for rid in list(run.requests):
        if rid not in cl.status:
            fe.cancel(rid)
    if arr["mode"] == "closed":
        await asyncio.gather(*tasks)
    await fe.aclose()
    await asyncio.gather(*consumers.values())


# ----------------------------------------------------------- correctness

def _sample(run: Run, rng) -> list:
    """The served requests the reference checks: the one with the most
    tokens delivered and others drawn from the seed, within the token
    budget."""
    chk = run.traffic["check"]
    budget = chk["max_tokens"]
    cands = [r for r in run.requests if run.tags[r] != "warmup"
             and run.client.tokens.get(r)]
    if not cands:
        return []
    longest = max(cands, key=lambda r: len(run.client.tokens[r]))
    rest = [r for r in cands if r != longest]
    picked = []
    for r in [longest] + list(rng.permutation(rest)):
        n = len(run.requests[r].prompt) + len(run.client.tokens[r])
        if len(picked) >= chk["requests"] or n > budget:
            continue
        picked.append(int(r))
        budget -= n
    return picked


def check(run: Run, limits: dict, control: bool = False) -> dict:
    """Run the reference over the sampled requests' prompts and served
    tokens and compare. Returns {name: {"value", "limit", "tokens"}}
    for each number that has a limit. With ``control`` also the
    control's reading of each (``"control"``): the reference one
    precision lower (int8) put in the program's place, drawing its own
    token at each served position by the mix's sampling rule, its picks
    read under the float32 reference."""
    rng = np.random.default_rng([run.seed, 5])
    rids = _sample(run, rng)
    seqs, starts = [], []
    for r in rids:
        q = run.requests[r]
        seqs.append(np.concatenate([q.prompt,
                                    np.asarray(run.client.tokens[r],
                                               np.int32)]))
        starts.append(len(q.prompt))
    k = run.kcfg.top_k
    read = None
    if control and seqs:
        rule = (run.kcfg.temperature, k, run.kcfg.top_p)
        low = reference.forward(run.seed, run.mc, seqs, k=k, quant=True,
                                sample=rule)
        read = [r["sampled"] for r in low]
    ref = reference.forward(run.seed, run.mc, seqs, k=k, read=read) \
        if seqs else []
    prog, n = reference.gaps(ref, starts)
    ctrl = reference.gaps(ref, starts, field="read")[0] if control else {}
    run.readings = {"program": prog, "control": ctrl, "tokens": n,
                    "requests": len(rids)}
    out = {}
    for name, limit in limits.items():
        out[name] = {"value": prog[name], "limit": limit, "tokens": n}
        if control:
            out[name]["control"] = ctrl[name]
    return out


# -------------------------------------------------------------- the run

def run_cell(cell_name: str, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, require_tpu: bool = True,
             control: bool = False,
             t_start: Optional[float] = None) -> Run:
    """One run; returns its ``Run`` with the result line in
    ``.result``. ``control``: read the control at the checked positions
    as well, and let its readings decide ``correct`` (the control has to
    come out as not correct; ``bench/control.py``). ``t_start``: when
    the process started, for ``setup_s``."""
    t_start = time.perf_counter() if t_start is None else t_start
    bench = Bench(root)
    cell = bench.cell(cell_name)
    device = device_info(cell["chips"], require_tpu)
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serving.frontend import ServingFrontend
    if require_tpu:
        enable_compile_cache()
        # every program goes to the persistent cache, however fast it
        # compiled, so that a second run compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    run = Run()
    run.t_start, run.seed, run.seconds, run.trace = t_start, seed, seconds, \
        trace
    run.device = device
    run.mc = bench.config(cell["config"])
    run.traffic = bench.traffic(cell["traffic"])
    run.serving = run.mc["serving"]
    run.limits = bench.limits(cell_name)
    run.peaks = peaks(device["kind"]) if require_tpu else None
    run.compiles = CompileCounter()
    run.client, run.requests, run.tags = Client(), {}, {}
    run.due, run.late = {}, []
    cfg = model.model_config(run.mc)
    run.kcfg = model.kappa_config(run.traffic)
    params = model.make_params(cfg, run.mc, seed)
    log(run, "weights made")
    sched = model.make_scheduler(params, cfg, run.mc, run.kcfg, run.serving)
    run.sched, run.fe = sched, ServingFrontend(sched)
    run.rec = Recorder(sched)
    tmp = tempfile.TemporaryDirectory() if trace else None
    run.rec.trace_dir = tmp.name if tmp else None
    try:
        asyncio.run(_serve(run))
        run.num_pages = sched.num_pages
        run.trace_summary = trace_lib.read(run.rec.trace_dir) \
            if trace and run.rec.traced else None
    finally:
        if tmp is not None:
            tmp.cleanup()
    # the program's state goes before the reference runs
    run.sched = run.fe = sched = params = None
    run.rec.sched = None
    gc.collect()
    log(run, "program state freed; reference")
    run.checks = check(run, run.limits, control)
    log(run, "reference done")
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in bench.metrics(cell_name, kind):
        v = bench.reader(m["name"]).read(run, m)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    window = [q for r, q in run.requests.items()
              if run.tags[r] == "window" and r in run.due
              and run.due[r] < run.w1]
    poisson = run.traffic["arrival"]["mode"] == "poisson"
    failed = sum(1 for r in run.due if run.due[r] < run.w1
                 and (run.client.status.get(r) not in ("OK", "CANCELLED")
                      or (poisson and not run.client.events.get(r))))
    read = "control" if control else "value"
    correct = all(c["tokens"] > 0 and c[read] <= c["limit"]
                  for c in run.checks.values()) and failed == 0
    dev = dict(device, memory_peak_bytes=run.memory_peak)
    result = {"correct": bool(correct), "attempted": len(window),
              "failed": int(failed), "metrics": metrics, "device": dev}
    if trace and run.trace_summary:
        ts = run.trace_summary
        dev.update(busy_s=ts["busy_s"], window_s=ts["window_s"])
        top = sorted(ts["ops"].items(), key=lambda kv: -kv[1])[:10]
        label = lambda k: f"{k} {list(ts['shapes'][k])}" \
            if ts["shapes"].get(k) else k
        result["breakdown"] = {"device_ops": [[label(k), v] for k, v in top],
                               "idle_gaps": ts["idle_gaps"]}
    result["checks"] = {k: {"value": v[read], "limit": v["limit"]}
                        for k, v in run.checks.items()}
    run.result = result
    return run
