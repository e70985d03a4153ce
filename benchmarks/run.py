# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness — one module per paper table/figure:

  kappa_table       — Appendix A: acc/tokens/memory, all methods × N
  memory_ratio      — Fig. 2: peak-memory reduction KAPPA vs BoN
  token_ratio       — Fig. 3: token reduction KAPPA vs BoN
  schedule_ablation — §4.2: linear vs cosine vs step pruning
  weight_ablation   — §4.1: (w_KL, w_C, w_H) mixes
  kernel_bench      — fused-score traffic arithmetic

Usage: PYTHONPATH=src python -m benchmarks.run [table ...]
Env:   BENCH_FULL=1 for paper-scale N∈{5,10,20} + longer training.

Besides the ``name,us_per_call,derived`` CSV on stdout, every table
writes ``BENCH_<name>.json`` ({name, rows, wall_s, config}) to the
working directory so the perf trajectory is machine-trackable across
PRs (see common.write_bench_json).
"""
from __future__ import annotations

import sys
import time

from benchmarks import (
    common,
    horizon_ablation,
    kappa_table,
    kernel_bench,
    memory_ratio,
    schedule_ablation,
    token_ratio,
    weight_ablation,
)
from repro.launch.compile_cache import enable_compile_cache

TABLES = {
    "kappa_table": kappa_table,
    "memory_ratio": memory_ratio,
    "token_ratio": token_ratio,
    "schedule_ablation": schedule_ablation,
    "weight_ablation": weight_ablation,
    "horizon_ablation": horizon_ablation,
    "kernel_bench": kernel_bench,
}


def main() -> None:
    enable_compile_cache()
    names = sys.argv[1:] or list(TABLES)
    needs_model = any(n != "kernel_bench" for n in names)
    cfg = params = None
    if needs_model:
        t0 = time.time()
        cfg, params = common.bench_model()
        print(f"# bench model ready ({time.time()-t0:.0f}s, "
              f"steps={common.STEPS}, problems={common.PROBLEMS}, "
              f"N={common.NS})", file=sys.stderr)
    print("name,us_per_call,derived")
    for name in names:
        mod = TABLES[name]
        t0 = time.time()
        rows = mod.run(cfg, params)
        for line in mod.emit_csv(rows):
            print(line)
        wall = time.time() - t0
        path = common.write_bench_json(name, rows, wall)
        print(f"# {name} done in {wall:.0f}s -> {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
