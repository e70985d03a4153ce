"""Chip smoke test: the served path on one TPU, at the published widths of
deepseek-r1-distill-qwen-1.5b (28 layers, d_model 1536, 12 query heads
over 2 KV heads, d_ff 8960, vocabulary 151,936, bf16).

  python chip_smoke.py

Everything runs in this one process, which starts no other. Weights,
pages and prompts come from seeds; nothing is read from disk or the
network. The phases run in order, and any failure exits non-zero
without printing the result line:

1. device: JAX's first device must be a TPU. There is no CPU path.
2. kernel: the paged decode and chunk-prefill Pallas kernels at the
   model's widths (64-token pages, bf16 and int8 pages) against the jnp
   reference of ``repro/kernels/decode_attn/ref.py``, on seeded pages and
   block tables, within ``KERNEL_TOL``.
3. serve: KAPPA (N=5) over 4 prompts x 128 new tokens through
   ``ServingFrontend`` over ``PagedScheduler`` with chunked prefill, via
   ``repro.launch.serve.serve_eval(..., full_width=True)``: bf16 pages,
   the same again with everything compiled, then int8 pages. Every
   request must end OK, every stream must equal its result, and the
   paged attention must have traced the Pallas kernel, never the gather
   oracle, in both decode and prefill.

The seconds printed are smoke timings of one run, not metrics. The last
line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.kernels.decode_attn.ops import (paged_decode_attn,  # noqa: E402
                                           paged_prefill_attn)
from repro.kernels.decode_attn.ref import (  # noqa: E402
    paged_decode_attn_ref, paged_prefill_attn_ref)
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.serve import serve_eval  # noqa: E402
from repro.models import attention, init_params  # noqa: E402

ARCH = "deepseek-r1-distill-qwen-1.5b"
PUBLISHED = {"layers": 28, "d_model": 1536, "heads": 12, "kv_heads": 2,
             "head_dim": 128, "d_ff": 8960, "vocab": 151_936,
             "dtype": "bfloat16"}
SEED = 0
PAGE_SIZE = 64
PREFILL_CHUNK = 8       # 15-token prompts: chunks of 8 then 7 tokens
N_BRANCHES = 5
PROBLEMS = 4
MAX_NEW = 128
# max |kernel - reference| over outputs of unit scale: both read the same
# bf16 (or int8 + fp32 scale) pages; the reference runs in fp32 at
# "highest" matmul precision, the kernel on the MXU
KERNEL_TOL = 3e-2

# JAX reports tracing, lowering and backend compilation as events
_COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                   "/jax/core/compile/jaxpr_to_mlir_module_duration",
                   "/jax/core/compile/backend_compile_duration")
_compile = {"seconds": 0.0, "programs": 0}


def _on_event(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _compile["seconds"] += duration
    if event == _COMPILE_EVENTS[-1]:
        _compile["programs"] += 1


def _scrambled_tables(rng, B, MP, P, last_pos):
    """Each row owns the pages up to ``last_pos``, drawn without
    replacement; the rest of its table aliases the trash page P-1."""
    bt = np.full((B, MP), P - 1, np.int32)
    for b in range(B):
        owned = int(last_pos[b]) // PAGE_SIZE + 1
        bt[b, :owned] = rng.choice(P - 1, size=owned, replace=False)
    return jnp.asarray(bt)


def _pages(key, shape, kv_dtype):
    """Seeded head-major pages (P, KV, ps, hd); int8 pages come with
    their per-(page, head, slot) scales (P, KV, 1, ps)."""
    x = jax.random.normal(key, shape, jnp.float32)
    if kv_dtype == "bfloat16":
        return x.astype(jnp.bfloat16), None
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1), 1e-8) / 127.0
    q = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
    return q, s[:, :, None, :]


def check_kernels(cfg) -> float:
    """Phase 2: kernel against reference at the model's widths."""
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, MP, P = 10, 8, 96
    rng = np.random.RandomState(SEED)
    worst = 0.0
    for kv_dtype in ("bfloat16", "int8"):
        ks = jax.random.split(jax.random.PRNGKey(SEED), 4)
        kp, k_s = _pages(ks[0], (P, KV, PAGE_SIZE, hd), kv_dtype)
        vp, v_s = _pages(ks[1], (P, KV, PAGE_SIZE, hd), kv_dtype)
        pos = rng.randint(PREFILL_CHUNK, MP * PAGE_SIZE, size=B)
        bt = _scrambled_tables(rng, B, MP, P, pos)
        qd = jax.random.normal(ks[2], (B, H, hd)).astype(jnp.bfloat16)
        qp = jax.random.normal(ks[3], (B, PREFILL_CHUNK, H, hd)
                               ).astype(jnp.bfloat16)
        pos0 = pos - PREFILL_CHUNK + 1
        sc = dict(k_scales=k_s, v_scales=v_s)
        cases = {
            "decode": (paged_decode_attn, paged_decode_attn_ref,
                       (qd, kp, vp, bt, jnp.asarray(pos, jnp.int32))),
            "prefill": (paged_prefill_attn, paged_prefill_attn_ref,
                        (qp, kp, vp, bt, jnp.asarray(pos0, jnp.int32))),
        }
        for name, (kernel, ref, args) in cases.items():
            out = np.asarray(kernel(*args, **sc))
            with jax.default_matmul_precision("highest"):
                want = np.asarray(ref(*args, **sc))
            err = float(np.max(np.abs(out - want)))
            ok = np.all(np.isfinite(out)) and err <= KERNEL_TOL
            print(f"kernel {name:7s} {kv_dtype:8s} pages: max |kernel - ref| "
                  f"= {err:.3e} (tolerance {KERNEL_TOL:g})", flush=True)
            if not ok:
                raise AssertionError(f"paged {name} kernel on {kv_dtype} "
                                     f"pages is off the reference: {err}")
            worst = max(worst, err)
    return worst


def serve(params, kv_dtype: str, label: str, *, traced: bool) -> dict:
    """Phase 3: one full-width KAPPA serve through the streaming
    front-end; ``kv_dtype`` "model" keeps bf16 pages, "int8" quantizes
    them. ``traced``: this run compiles its programs, so the paged
    backend counters must show the kernel in decode and prefill."""
    attention.reset_paged_backend_counts()
    c0 = dict(_compile)
    t0 = time.perf_counter()
    out = serve_eval(ARCH, "kappa", n=N_BRANCHES, problems=PROBLEMS,
                     max_new=MAX_NEW, params=params, full_width=True,
                     seed=SEED, paged=True, page_size=PAGE_SIZE,
                     prefill_chunk=PREFILL_CHUNK, frontend_serve=True,
                     stream=True, kv_dtype=kv_dtype, verbose=False,
                     dataset_kw=dict(min_steps=5, max_steps=5))
    wall = time.perf_counter() - t0
    compile_s = _compile["seconds"] - c0["seconds"]
    programs = _compile["programs"] - c0["programs"]
    counts = attention.paged_backend_counts()
    want_model = dict(PUBLISHED, kv_cache_dtype=kv_dtype)
    if out["model"] != want_model:
        raise AssertionError(f"served {out['model']}, not {want_model}")
    status = out["status_counts"]
    if status.get("OK", 0) != PROBLEMS or sum(status.values()) != PROBLEMS:
        raise AssertionError(f"not every request ended OK: {status}")
    if out["generated_tokens"] <= 0:
        raise AssertionError("no tokens generated")
    if traced and not (counts["decode_kernel"] > 0
                       and counts["prefill_kernel"] > 0
                       and counts["decode_oracle"] == 0
                       and counts["prefill_oracle"] == 0):
        raise AssertionError(f"paged attention left the kernel: {counts}")
    peak = jax.devices()[0].memory_stats()["peak_bytes_in_use"]
    print(f"serve {label}: requests OK {status['OK']}/{PROBLEMS}, "
          f"streams == results, generated_tokens {out['generated_tokens']}, "
          f"paged backend counts {counts}", flush=True)
    print(f"serve {label}: smoke timings (not metrics): compile_s "
          f"{compile_s:.1f} over {programs} programs, serve_s "
          f"{wall - compile_s:.1f}, wall_s {wall:.1f}; "
          f"peak_bytes_in_use {peak}", flush=True)
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform "
              f"{dev.platform!r} ({dev.device_kind}); there is no CPU path",
              file=sys.stderr)
        return 1
    cache_dir = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_event)
    cfg = get_config(ARCH)
    print(f"device: {dev.device_kind} x{len(jax.devices())} "
          f"({dev.platform}); compile cache {cache_dir}", flush=True)
    print(f"model: {ARCH} layers {cfg.num_layers} d_model {cfg.d_model} "
          f"heads {cfg.num_heads}/{cfg.num_kv_heads} head_dim "
          f"{cfg.resolved_head_dim} d_ff {cfg.d_ff} vocab {cfg.vocab_size} "
          f"dtype {cfg.dtype}; random weights, seed {SEED}", flush=True)

    worst = check_kernels(cfg)
    print(f"kernel: worst max |kernel - ref| {worst:.3e} <= {KERNEL_TOL:g}",
          flush=True)

    t0 = time.perf_counter()
    params = jax.jit(init_params, static_argnums=1)(
        jax.random.PRNGKey(SEED), cfg)
    jax.block_until_ready(params)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"weights: {n_params} parameters, "
          f"{sum(x.nbytes for x in jax.tree.leaves(params))} bytes, "
          f"init {time.perf_counter() - t0:.1f}s", flush=True)

    serve(params, "model", "bf16 pages", traced=True)
    serve(params, "model", "bf16 pages, compiled", traced=False)
    serve(params, "int8", "int8 pages", traced=True)

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
