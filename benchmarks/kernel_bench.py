"""Kernel microbenchmarks: wall-time of the jnp oracle path on CPU (the
Pallas kernels themselves run in interpret mode here — TPU wall-time is
the dry-run/roofline's job) + derived per-call traffic, proving the
fusion arithmetic: fused_score reads the logits row once vs 4×.

Also *executes* every Pallas kernel wrapper end to end (fused_score,
decode_attn contiguous + paged, rwkv6_scan) at small shapes — the CI
smoke step runs this module so a broken pallas_call surfaces on push,
not only in the unit-test sweeps.

On a TPU it times the paged decode kernel alone at the decode call of
the benchmark cell ``r1d-1.5b.kappa-batch`` in place of the wrapper
smoke, whose contiguous decode_attn call does not lower there."""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.signals import compute_signals, log_softmax, reference_log_q
from repro.kernels.decode_attn.ops import (decode_attn, paged_decode_attn,
                                           paged_prefill_attn)
from repro.kernels import on_tpu
from repro.kernels.fused_score.ops import fused_score
from repro.kernels.rwkv6_scan.ops import rwkv6_scan


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.tree.leaves(out)[0].block_until_ready()
    return (time.perf_counter() - t0) / iters * 1e6  # us


def run(cfg=None, params=None):
    rows = []
    for B, V in [(5, 50_000), (20, 150_000)]:
        k1, k2 = jax.random.split(jax.random.PRNGKey(B))
        logits = jax.random.normal(k1, (B, V))
        log_q = reference_log_q(jax.random.normal(k2, (V,)))

        fused = jax.jit(lambda l, q: compute_signals(l, q))
        us_fused = _time(fused, logits, log_q)

        def separate(l, q):
            lp = log_softmax(l)
            p = jnp.exp(lp)
            kl = jnp.sum(p * (lp - q), -1)
            conf = jnp.max(p, -1)
            ent = -jnp.sum(p * jnp.log(p + 1e-9), -1)
            return kl, conf, ent

        us_sep = _time(jax.jit(separate), logits, log_q)
        bytes_once = B * V * 4
        rows.append({"name": f"signals_B{B}_V{V}", "us_fused": us_fused,
                     "us_separate": us_sep, "row_bytes": bytes_once})
    if on_tpu():
        # the TPU lowering refuses the smoke's contiguous decode_attn
        # blocks, (1, S-tile, 1, hd) of a 2-KV-head cache
        rows.append(_paged_decode_cell())
    else:
        rows.extend(_wrapper_smoke())
    return rows


def _cell_positions(rng, rows):
    """Row positions as the closed-loop KAPPA batch cell holds them:
    prompts lognormal (median 160, sigma 0.6) in 48-512, answers
    lognormal (median 1,200, sigma 0.7) in 256-4,096; a row is busy in
    proportion to its answer's length and sits at a uniform point of
    it."""
    prompt = np.clip(rng.lognormal(np.log(160), 0.6, 4096), 48, 512)
    answer = np.clip(rng.lognormal(np.log(1200), 0.7, 4096), 256, 4096)
    pick = rng.choice(4096, size=rows, p=answer / answer.sum())
    return (prompt[pick] + rng.uniform(size=rows) * answer[pick]).astype(
        np.int32)


def _paged_decode_cell(iters=50):
    """The paged decode kernel alone at ``r1d-1.5b.kappa-batch``'s decode
    call: 104 rows, 12 query heads over 2 KV heads, hd 128, bf16 64-token
    pages, 80-page block tables (max_seq 5,120), rows at the cell's
    lengths, their pages drawn from a 2,304-page pool (rows may share
    pages, as a fan-out shares its prompt's)."""
    B, H, KV, hd, ps, MP, P = 104, 12, 2, 128, 64, 80, 2305
    rng = np.random.RandomState(0)
    pos = _cell_positions(rng, B)
    bt = np.full((B, MP), P - 1, np.int32)         # tails: the trash page
    for b in range(B):
        bt[b, :pos[b] // ps + 1] = rng.choice(P - 1, pos[b] // ps + 1,
                                              replace=False)
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.bfloat16)
    kp = jax.random.normal(ks[1], (P, KV, ps, hd), jnp.bfloat16)
    vp = jax.random.normal(ks[2], (P, KV, ps, hd), jnp.bfloat16)
    step = jax.jit(paged_decode_attn)
    us = _time(lambda *a: (step(*a),), q, kp, vp, jnp.asarray(bt),
               jnp.asarray(pos), iters=iters)
    # the K and V bytes the rows need: positions 0..pos of each KV head
    need = int((pos.astype(np.int64) + 1).sum()) * KV * hd * 2 * 2
    return {"name": "paged_decode_r1d_cell", "us_fused": us,
            "kv_bytes": need, "live_pages": int((pos // ps + 1).sum()),
            "table_pages": B * MP}


def _wrapper_smoke():
    """Execute each Pallas kernel wrapper once and record its wall time
    (interpret mode off-TPU, so this is a does-it-run check, not a perf
    number — contiguous vs paged decode ride through the same shapes)."""
    out = []
    ks = jax.random.split(jax.random.PRNGKey(0), 8)

    logits = jax.random.normal(ks[0], (4, 4096))
    log_q = reference_log_q(jax.random.normal(ks[1], (4096,)))
    out.append({"name": "wrapper_fused_score",
                "us_fused": _time(lambda l, q: fused_score(l, q),
                                  logits, log_q, iters=3)})

    B, H, KV, hd, S = 2, 4, 2, 64, 128
    q = jax.random.normal(ks[2], (B, H, hd))
    k = jax.random.normal(ks[3], (B, S, KV, hd))
    v = jax.random.normal(ks[4], (B, S, KV, hd))
    out.append({"name": "wrapper_decode_attn",
                "us_fused": _time(
                    lambda *a: (decode_attn(*a),), q, k, v, 100, iters=3)})

    ps, MP, P = 32, 4, 9          # same 128 logical slots, paged
    kp = k.reshape(B * 2, ps * 2, KV, hd)[:, :ps]
    kp = jnp.concatenate([kp, jnp.zeros((P - B * 2, ps, KV, hd))], 0)
    vp = jnp.concatenate([v.reshape(B * 2, ps * 2, KV, hd)[:, :ps],
                          jnp.zeros((P - B * 2, ps, KV, hd))], 0)
    kp, vp = kp.transpose(0, 2, 1, 3), vp.transpose(0, 2, 1, 3)  # head-major
    bt = jnp.array([[0, 1, 8, 8], [2, 3, 8, 8]], jnp.int32)
    pos = jnp.array([50, 60], jnp.int32)
    out.append({"name": "wrapper_paged_decode_attn",
                "us_fused": _time(
                    lambda *a: (paged_decode_attn(*a),), q, kp, vp, bt, pos,
                    iters=3)})

    # int8 pages: per-(page, head, slot) absmax scales, dequant in-kernel
    def q8(x):
        s = jnp.maximum(jnp.max(jnp.abs(x), -1), 1e-8) / 127.0
        qv = jnp.clip(jnp.round(x / s[..., None]), -127, 127).astype(jnp.int8)
        return qv, s[:, :, None, :]

    kq, ksc = q8(kp)
    vq, vsc = q8(vp)
    out.append({"name": "wrapper_paged_decode_attn_int8",
                "us_fused": _time(
                    lambda *a: (paged_decode_attn(
                        *a, k_scales=ksc, v_scales=vsc),),
                    q, kq, vq, bt, pos, iters=3)})

    # paged chunk prefill: C tokens attend causally through the table
    C = 8
    qc = jax.random.normal(ks[7], (B, C, H, hd))
    pos0 = jnp.array([40, 56], jnp.int32)
    out.append({"name": "wrapper_paged_prefill_attn",
                "us_fused": _time(
                    lambda *a: (paged_prefill_attn(*a),), qc, kp, vp, bt,
                    pos0, iters=3)})
    out.append({"name": "wrapper_paged_prefill_attn_int8",
                "us_fused": _time(
                    lambda *a: (paged_prefill_attn(
                        *a, k_scales=ksc, v_scales=vsc),),
                    qc, kq, vq, bt, pos0, iters=3)})

    # the serving-layer wiring: attn_decode_paged with the paged kernel
    # forced on (the path TPU decode takes), K/V write included
    from repro.models import attention as attn_mod
    d = H * hd
    ap = attn_mod.init_attn(ks[5], d, H, KV, hd, False, jnp.float32)
    cache = attn_mod.init_paged_kv(P, ps, KV, hd, jnp.float32)
    x = jax.random.normal(ks[6], (B, 1, d))
    attn_mod.set_paged_kernel(True)
    try:
        out.append({"name": "wrapper_attn_decode_paged_wired",
                    "us_fused": _time(
                        lambda *a: attn_mod.attn_decode_paged(
                            *a, num_heads=H, num_kv_heads=KV, head_dim=hd,
                            rope_theta=1e4, use_rope=True),
                        ap, x, pos, cache, bt, iters=3)})
        # quantized edition of the same wiring — the regression smoke
        # for the silent int8 fallback (attention must still trace the
        # Pallas kernel when the pool carries scale leaves)
        cache8 = attn_mod.init_paged_kv(P, ps, KV, hd, jnp.float32,
                                        quantized=True)
        attn_mod.reset_paged_backend_counts()
        out.append({"name": "wrapper_attn_decode_paged_wired_int8",
                    "us_fused": _time(
                        lambda *a: attn_mod.attn_decode_paged(
                            *a, num_heads=H, num_kv_heads=KV, head_dim=hd,
                            rope_theta=1e4, use_rope=True),
                        ap, x, pos, cache8, bt, iters=3)})
        counts = attn_mod.paged_backend_counts()
        assert counts["decode_oracle"] == 0, \
            f"int8 paged decode fell back to the gather oracle: {counts}"
    finally:
        attn_mod.set_paged_kernel(None)

    T, Hh, hd2 = 32, 2, 32
    r = jax.random.normal(ks[5], (1, T, Hh, hd2))
    kk = jax.random.normal(ks[6], (1, T, Hh, hd2))
    vv = jax.random.normal(ks[7], (1, T, Hh, hd2))
    w = jax.nn.sigmoid(kk) * 0.9 + 0.05
    u = jnp.zeros((Hh, hd2))
    out.append({"name": "wrapper_rwkv6_scan",
                "us_fused": _time(
                    lambda *a: rwkv6_scan(*a, chunk=16), r, kk, vv, w, u,
                    iters=3)})
    return out


def emit_csv(rows):
    out = []
    for r in rows:
        if "us_separate" in r:
            out.append(f"kernel_bench/{r['name']},{r['us_fused']:.1f},"
                       f"separate_us={r['us_separate']:.1f};"
                       f"row_bytes={r['row_bytes']}")
        elif "kv_bytes" in r:
            out.append(f"kernel_bench/{r['name']},{r['us_fused']:.1f},"
                       f"kv_bytes={r['kv_bytes']};"
                       f"live_pages={r['live_pages']};"
                       f"table_pages={r['table_pages']}")
        else:
            out.append(f"kernel_bench/{r['name']},{r['us_fused']:.1f},"
                       f"wrapper_smoke=1")
    return out
