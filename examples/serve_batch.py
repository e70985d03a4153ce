"""End-to-end serving driver: train a small model once, then serve a
batch of reasoning requests under all four decoding strategies and print
the paper's comparison table (accuracy / tokens / peak memory), then the
same pool behind the async streaming front-end (DESIGN.md §9).

  PYTHONPATH=src python examples/serve_batch.py [--steps 1200] [--problems 30]
"""
import argparse

from repro.launch.serve import serve_eval
from repro.launch.train import train_loop

ap = argparse.ArgumentParser()
ap.add_argument("--steps", type=int, default=1200)
ap.add_argument("--problems", type=int, default=30)
ap.add_argument("--arch", default="deepseek-r1-distill-qwen-1.5b")
args = ap.parse_args()

cfg, params = train_loop(args.arch, steps=args.steps, batch=64, d_model=256)

print(f"\n{'method':8s} {'N':>3s} {'acc':>6s} {'final_toks':>10s} "
      f"{'total_toks':>10s} {'peak_MB':>8s}")
rows = []
for method in ["greedy", "bon", "stbon", "kappa"]:
    for n in ([5, 10] if method != "greedy" else [1]):
        r = serve_eval(args.arch, method, n=n, problems=args.problems,
                       params=params, cfg=cfg, verbose=False)
        rows.append(r)
        print(f"{method:8s} {n:3d} {r['accuracy']:6.3f} "
              f"{r['final_branch_tokens']:10.1f} {r['total_tokens']:10.1f} "
              f"{r['peak_memory_mb']:8.3f}")

bon10 = next(r for r in rows if r["method"] == "bon" and r["n"] == 10)
kap10 = next(r for r in rows if r["method"] == "kappa" and r["n"] == 10)
print(f"\nKAPPA vs BoN (N=10): token reduction "
      f"{1 - kap10['total_tokens']/bon10['total_tokens']:.1%}, "
      f"memory reduction {1 - kap10['peak_memory_mb']/bon10['peak_memory_mb']:.1%}, "
      f"accuracy delta {kap10['accuracy'] - bon10['accuracy']:+.3f}")

# the same prompts through the continuous-batching paged pool: identical
# outputs (same per-request keys), but pruned rows are backfilled with
# queued prefills instead of idling, KV reservations are per-request
# pages, and pruning frees pages instantly
pg5 = serve_eval(args.arch, "kappa", n=5, problems=args.problems,
                 params=params, cfg=cfg, verbose=False, scheduler=True,
                 page_size=16, sched_rows=20)
print(f"paged pool        (N=5, rows=20): {pg5['tokens_per_s']:.1f} tok/s, "
      f"{pg5['requests_per_s']:.2f} req/s, "
      f"page utilization {pg5['page_utilization']:.2f} "
      f"(wall {pg5['time_s']:.1f}s)")

# the same paged pool behind the async streaming front-end: every
# request is an AsyncIterator of token events, tokens arrive as the
# scheduler commits them, and the reassembled streams are asserted
# token-for-token equal to the terminal results
fe5 = serve_eval(args.arch, "kappa", n=5, problems=args.problems,
                 params=params, cfg=cfg, verbose=False, scheduler=True,
                 page_size=16, sched_rows=20,
                 frontend_serve=True, stream=True)
print(f"streaming frontend(N=5, rows=20): {fe5['tokens_per_s']:.1f} tok/s, "
      f"{fe5['requests_per_s']:.2f} req/s (wall {fe5['time_s']:.1f}s)")

# per-terminal-status summary with goodput (OK tokens per wall second —
# the number the SLO-adaptive admission controller protects): with no
# faults, deadlines, or queue bound every request should land in OK
for name, r in [("paged", pg5), ("frontend", fe5)]:
    sc = r["status_counts"]
    print(f"{name:10s} statuses: "
          + " ".join(f"{k}={sc.get(k, 0)}"
                     for k in ("OK", "CANCELLED", "TIMEOUT", "FAILED",
                               "SHED"))
          + f" (retries={r['retries']}, "
          + f"goodput={r['goodput_tokens_per_s']:.1f} tok/s)")
