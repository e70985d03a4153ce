"""Serving launcher: run Greedy / BoN / ST-BoN / KAPPA over synthetic
task prompts with a trained (or fresh) model and print the paper's
metric columns.

  PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
      --method kappa --n 5 --problems 20 [--ckpt ckpt.msgpack]

``--scheduler`` serves the same prompts through the continuous-batching
paged pool (repro.serving.scheduler.PagedScheduler) instead of one at a
time, and adds throughput columns (requests/s, tokens/s, row and page
utilization). ``--page-size``, ``--num-pages``, ``--prefill-chunk``,
``--prefix-cache`` and ``--kv-dtype`` shape that pool.

``--frontend`` drives the same pool through the async streaming
front-end (repro.serving.frontend) instead of batch ``run()``: every
request is submitted and consumed as a concurrent event stream;
``--stream`` additionally asserts each stream's tokens reassemble the
terminal result exactly (the §9 equivalence contract, end to end).

By default the model is the config's 2-layer float32 toy size
(``ModelConfig.reduced``), which the CPU tests and CI serve.
``--full-width`` serves the config as published instead: every layer,
its own widths and dtype, random weights from a seed unless ``--ckpt``
is given. That size is for an accelerator:

  python -m repro.launch.serve --arch deepseek-r1-distill-qwen-1.5b \
      --full-width --method kappa --n 5 --problems 4 --max-new 128 \
      --prefill-chunk 8 --frontend --stream
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.configs.base import KappaConfig
from repro.data import tasks
from repro.data import tokenizer as tok
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.models.frontends import stub_frontend
from repro.serving import engine
from repro.serving import faults as faults_lib
from repro.serving import strategies
from repro.serving.frontend import ServingFrontend
from repro.serving.scheduler import PagedScheduler
from repro.training import checkpoint

METHODS = {
    "greedy": engine.generate_greedy,
    "bon": engine.generate_bon,
    "stbon": engine.generate_stbon,
    "kappa": engine.generate_kappa,
}


def _strategy_factory(method: str, kcfg: KappaConfig):
    if method == "stbon":
        # ST-BoN's fixed buffer window scales with the gating horizon so
        # truncation happens well before EOS at toy sequence lengths
        return lambda: strategies.STBoNStrategy(
            buffer_window=max(2, kcfg.horizon))
    return lambda: strategies.make_strategy(method)


def _serve_frontend(sched, test, *, deadline_s, stream: bool):
    """Drive every prompt through the async streaming front-end
    concurrently; returns results in submission order. With ``stream``,
    asserts each stream's token events reassemble the terminal result
    exactly (committed-prefix + terminal-flush contract)."""

    async def go():
        t0 = sched.clock()
        async with ServingFrontend(sched) as fe:

            async def one(i, prob):
                toks, res = [], None
                async for ev in fe.submit_stream(
                        np.array(prob.prompt), jax.random.PRNGKey(i),
                        deadline_s=deadline_s):
                    if ev.kind == "token":
                        toks.append(ev.token)
                    else:
                        res = ev.result
                if stream and toks != res.tokens:
                    raise AssertionError(
                        f"rid stream diverged from result ({res.status})")
                return res

            gens = await asyncio.gather(
                *[one(i, p) for i, p in enumerate(test)])
        # no batch run() ran, so stamp elapsed for throughput() ourselves
        sched.elapsed = sched.clock() - t0
        return gens

    return asyncio.run(go())


def serve_eval(arch: str, method: str, *, n: int = 5, problems: int = 20,
               ckpt: str | None = None, d_model: int = 256,
               num_layers: int = 2, seed: int = 999, max_new: int = 48,
               kcfg_kw: dict | None = None, dataset_kw: dict | None = None,
               params=None, cfg=None, verbose: bool = True,
               scheduler: bool = False, sched_rows: int | None = None,
               page_size: int = 64,
               num_pages: int | None = None,
               prefill_chunk: int | None = None,
               prefix_cache: bool = False,
               inject_faults: str | None = None,
               max_queue: int | None = None,
               deadline_s: float | None = None,
               frontend_serve: bool = False,
               stream: bool = False,
               kv_dtype: str = "model",
               full_width: bool = False) -> dict:
    """Serve ``problems`` synthetic prompts with ``method`` and return the
    metric columns. ``full_width`` serves the config as published (all
    layers, its widths and dtype); otherwise it is cut to the toy size
    given by ``num_layers`` and ``d_model``. The toy tokenizer's ids all
    fall inside any config's vocabulary."""
    if cfg is None:
        cfg = get_config(arch)
        if not full_width:
            cfg = cfg.reduced(num_layers=num_layers, d_model=d_model,
                              vocab_size=tok.VOCAB_SIZE)
    if kv_dtype != "model":
        # quantized KV pages: the paged pool stores int8 values + fp32
        # per-token-head scales and halves HBM per page vs bf16/fp32
        cfg = dataclasses.replace(cfg, kv_cache_dtype=kv_dtype)
    if params is None:
        params = init_params(jax.random.PRNGKey(0), cfg)
        if ckpt:
            params = checkpoint.restore(ckpt, params)

    kw = dict(num_branches=n, max_new_tokens=max_new, max_cutoff=6,
              horizon=8, window=8, mom_buckets=4)
    kw.update(kcfg_kw or {})
    kcfg = KappaConfig(**kw)
    # the streaming front-end implies the scheduler path
    scheduler = scheduler or frontend_serve
    dkw = dict(min_steps=2, max_steps=5, num_ops=2, max_operand=10)
    dkw.update(dataset_kw or {})
    test = tasks.make_dataset(seed, problems, **dkw)

    fe = stub_frontend(jax.random.PRNGKey(1), cfg, 1)
    factory = _strategy_factory(method, kcfg)
    t0 = time.time()
    if scheduler:
        n_prefix = engine._n_prefix(cfg)
        max_seq = max(len(p.prompt) for p in test) + max_new + n_prefix
        fan_out = factory().rows(kcfg)
        plan = (faults_lib.parse_fault_spec(inject_faults)
                if inject_faults else None)
        sched = PagedScheduler(
            params, cfg, kcfg, rows=sched_rows or 2 * fan_out,
            max_seq=max_seq, page_size=page_size, num_pages=num_pages,
            method=method, eos_id=tok.EOS, bos_id=tok.BOS, frontend=fe,
            strategy_factory=factory, prefill_chunk=prefill_chunk,
            prefix_cache=prefix_cache, faults=plan, max_queue=max_queue)
        if frontend_serve:
            gens = _serve_frontend(sched, test, deadline_s=deadline_s,
                                   stream=stream)
        else:
            rids = [sched.submit(np.array(prob.prompt),
                                 jax.random.PRNGKey(i),
                                 deadline_s=deadline_s)
                    for i, prob in enumerate(test)]
            res = sched.run()
            gens = [res[rid] for rid in rids]
    else:
        gens = []
        for i, prob in enumerate(test):
            strategy = factory()
            gens.append(engine._decode_loop(
                params, cfg, kcfg, np.array(prob.prompt),
                jax.random.PRNGKey(i), strategy, eos_id=tok.EOS,
                bos_id=tok.BOS, frontend=fe))

    acc = lt = ct = 0
    fbt = 0.0
    peak = 0
    for prob, r in zip(test, gens):
        acc += tasks.check_answer(r.tokens, prob)
        lt += r.logical_tokens
        ct += r.compute_tokens
        fbt += len(r.tokens)
        peak = max(peak, r.peak_cache_bytes)
    out = {
        "arch": arch, "method": method, "n": n,
        "model": {"layers": cfg.num_layers, "d_model": cfg.d_model,
                  "heads": cfg.num_heads, "kv_heads": cfg.num_kv_heads,
                  "head_dim": cfg.resolved_head_dim, "d_ff": cfg.d_ff,
                  "vocab": cfg.vocab_size, "dtype": cfg.dtype,
                  "kv_cache_dtype": cfg.kv_cache_dtype},
        "generated_tokens": lt,
        "accuracy": acc / len(test),
        "final_branch_tokens": fbt / len(test),
        "total_tokens": lt / len(test),
        "compute_tokens": ct / len(test),
        "peak_memory_mb": peak / 1e6,
        "time_s": time.time() - t0,
    }
    if scheduler:
        tp = sched.throughput()
        out.update({
            "tokens_per_s": tp["tokens_per_s"],
            "requests_per_s": tp["requests_per_s"],
            "row_utilization": tp["row_utilization"],
            "ticks": tp["ticks"],
            "status_counts": tp["status_counts"],
            "retries": tp["retries"],
            "failures": tp["failures"],
            "timeouts": tp["timeouts"],
            "shed": tp["shed"],
            "cancelled": tp["cancelled"],
            "faults_injected": tp["faults_injected"],
        })
        out["ttft_p99_s"] = tp["ttft_p99_s"]
        out["itl_p99_s"] = tp["itl_p99_s"]
        # goodput: OK tokens per wall second — comparable between the
        # batch run() path and the streaming front-end path
        ok_tokens = sum(r.logical_tokens for r in gens if r.status == "OK")
        out["goodput_tokens_per_s"] = ok_tokens / max(tp["time_s"], 1e-9)
        out["page_utilization"] = tp["page_utilization"]
        out["page_peak"] = tp["page_peak"]
        out["preemptions"] = tp["preemptions"]
        if prefix_cache:
            out["prefix_hit_rate"] = tp["prefix_hit_rate"]
            out["prefix_tokens_saved"] = tp["prefix_tokens_saved"]
            out["prefix_evictions"] = tp["prefix_evictions"]
            out["prefix_pinned_pages"] = tp["prefix_pinned_pages"]
    if verbose:
        line = (f"{arch} {method:7s} N={n:3d} acc={out['accuracy']:.3f} "
                f"total_toks={out['total_tokens']:8.1f} "
                f"peak={out['peak_memory_mb']:8.3f}MB t={out['time_s']:.1f}s")
        if scheduler:
            mode = "frontend" if frontend_serve else "sched"
            line += (f" | {mode}: {out['tokens_per_s']:.1f} tok/s "
                     f"goodput={out['goodput_tokens_per_s']:.1f} tok/s "
                     f"{out['requests_per_s']:.2f} req/s "
                     f"util={out['row_utilization']:.2f}")
        if scheduler and prefix_cache:
            line += (f" | prefix: hit={out['prefix_hit_rate']:.2f} "
                     f"saved={out['prefix_tokens_saved']} "
                     f"evict={out['prefix_evictions']} "
                     f"pinned={out['prefix_pinned_pages']}")
        print(line)
        if scheduler:
            # per-terminal-status summary — every submission lands in
            # exactly one of these buckets (DESIGN.md §8)
            sc = out["status_counts"]
            print("  status: "
                  + " ".join(f"{k}={sc.get(k, 0)}" for k in
                             ("OK", "CANCELLED", "TIMEOUT", "FAILED",
                              "SHED"))
                  + f" | retries={out['retries']} "
                    f"faults_injected={out['faults_injected']}")
    if scheduler and inject_faults:
        # chaos-smoke contract (CI): faults actually fired, the run
        # survived, and nothing leaked — pages all free, no pins left
        assert out["faults_injected"] > 0, \
            "fault plan injected nothing — raise its probabilities"
        assert out["retries"] > 0, "no fault-triggered retries recorded"
        if sched.pcache is not None:
            sched.pcache.drop()
        assert sched.alloc.free_count == sched.num_pages, \
            f"leaked pages: {sched.num_pages - sched.alloc.free_count}"
        assert int(sched.alloc.pinned.sum()) == 0, "leaked pins"
        if verbose:
            print("  chaos smoke: zero leaked pages/pins, "
                  f"{out['retries']} retries survived")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--method", default="kappa", choices=sorted(METHODS))
    ap.add_argument("--n", type=int, default=5)
    ap.add_argument("--problems", type=int, default=20)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--max-new", type=int, default=48)
    ap.add_argument("--scheduler", action="store_true",
                    help="serve through the continuous-batching paged "
                         "KV pool")
    ap.add_argument("--rows", type=int, default=None,
                    help="pool rows for --scheduler (default 2x fan-out)")
    ap.add_argument("--page-size", type=int, default=64,
                    help="token slots per KV page (scheduler paths only)")
    ap.add_argument("--num-pages", type=int, default=None,
                    help="allocatable KV pages (scheduler paths only; "
                         "default: no page pressure, "
                         "rows*max_seq/page_size)")
    ap.add_argument("--kv-dtype", default="model",
                    choices=("model", "int8"),
                    help="KV cache dtype: 'model' keeps the model dtype; "
                         "'int8' quantizes KV pages (per-token-head fp32 "
                         "scales, in-kernel dequant) for ~2x pages per "
                         "HBM byte")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="enable the cross-request radix prefix cache "
                         "(scheduler paths with --prefill-chunk only): "
                         "admissions alias previously "
                         "published prompt/winner pages and skip their "
                         "prefill")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="chunked-prefill size: admissions advance this "
                         "many prompt tokens per tick interleaved with "
                         "decode instead of one blocking whole-prompt "
                         "prefill (scheduler paths only)")
    ap.add_argument("--inject-faults", default=None, metavar="SPEC",
                    help="seeded fault injection for chaos smoke runs, "
                         "e.g. 'seed:7' or 'seed:7,step:0.1,alloc:0.2' "
                         "(scheduler paths only); asserts zero leaked "
                         "pages/pins and nonzero retries on completion")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="bounded admission queue: submissions beyond "
                         "this depth are shed with a SHED result")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="per-request wall-clock deadline; expired "
                         "requests truncate to a TIMEOUT result")
    ap.add_argument("--frontend", action="store_true",
                    help="drive the pool through the async streaming "
                         "front-end (concurrent per-request event "
                         "streams) instead of batch run(); implies "
                         "--scheduler")
    ap.add_argument("--stream", action="store_true",
                    help="with --frontend: assert every stream's token "
                         "events reassemble its terminal result exactly")
    ap.add_argument("--full-width", action="store_true",
                    help="serve the config as published (all layers, its "
                         "widths and dtype) instead of the 2-layer float32 "
                         "toy size; random weights unless --ckpt")
    args = ap.parse_args(argv)
    enable_compile_cache()
    serve_eval(args.arch, args.method, n=args.n, problems=args.problems,
               ckpt=args.ckpt, max_new=args.max_new,
               scheduler=args.scheduler, sched_rows=args.rows,
               page_size=args.page_size,
               num_pages=args.num_pages, prefill_chunk=args.prefill_chunk,
               prefix_cache=args.prefix_cache,
               inject_faults=args.inject_faults, max_queue=args.max_queue,
               deadline_s=args.deadline_s,
               frontend_serve=args.frontend or args.stream,
               stream=args.stream, kv_dtype=args.kv_dtype,
               full_width=args.full_width)


if __name__ == "__main__":
    main()
