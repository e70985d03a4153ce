"""GQA attention: full-causal / sliding-window, prefill + decode paths.

Layouts
-------
hidden     x : (B, S, d)
query      q : (B, S, H, hd)
key/value    : (B, S, KV, hd)
full cache   : (B, S_max, KV, hd), written at absolute position
ring cache   : (B, W, KV, hd), slot = pos % W  (sliding-window layers)

All softmax math is fp32; inputs/outputs stay in the model dtype.
The decode path has a pure-jnp implementation here; the Pallas
flash-decode kernel (kernels/decode_attn) is an optional drop-in used
when ``repro.kernels.use_pallas()`` is true.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init
from repro.models.rope import apply_rope

NEG_INF = -2.0 ** 30  # large-but-finite; avoids NaN from inf-inf in padding rows

# query-chunk size for the memory-bounded prefill/train path
Q_CHUNK = 1024


def init_attn(rng, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, qkv_bias: bool, dtype):
    ks = jax.random.split(rng, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, num_heads * head_dim), dtype=dtype),
        "wk": dense_init(ks[1], (d_model, num_kv_heads * head_dim), dtype=dtype),
        "wv": dense_init(ks[2], (d_model, num_kv_heads * head_dim), dtype=dtype),
        "wo": dense_init(ks[3], (num_heads * head_dim, d_model), dtype=dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["bk"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["bv"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    return p


def _project_qkv(p, x, num_heads, num_kv_heads, head_dim):
    B, S, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, S, num_heads, head_dim)
    k = k.reshape(B, S, num_kv_heads, head_dim)
    v = v.reshape(B, S, num_kv_heads, head_dim)
    return q, k, v


def _gqa_scores(q, k):
    """q: (B,Sq,KV,G,hd)  k: (B,Sk,KV,hd) -> (B,KV,G,Sq,Sk) fp32."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q, k,
                      preferred_element_type=jnp.float32)


def _attend(q, k, v, mask):
    """Masked softmax attention. q:(B,Sq,KV,G,hd) k,v:(B,Sk,KV,hd)
    mask broadcastable to (B,KV,G,Sq,Sk). Returns (B,Sq,KV,G,hd)."""
    hd = q.shape[-1]
    scores = _gqa_scores(q, k) * (hd ** -0.5)
    scores = jnp.where(mask, scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", probs.astype(v.dtype), v)
    return out


def attn_forward(p, x, positions, *, num_heads: int, num_kv_heads: int,
                 head_dim: int, window: int, rope_theta: float,
                 use_rope: bool) -> jnp.ndarray:
    """Full-sequence causal attention (train / prefill). window<=0 → global.

    Scans over query chunks so live score memory is O(Q_CHUNK · S), not
    O(S²) — required for the 32k prefill shape to fit HBM.
    """
    B, S, d = x.shape
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)
    q = q.reshape(B, S, num_kv_heads, G, head_dim)

    kv_pos = positions  # (B, S) or (S,)
    if kv_pos.ndim == 1:
        kv_pos = jnp.broadcast_to(kv_pos, (B, S))

    def block(q_blk, qpos_blk):
        # q_blk: (B, C, KV, G, hd); qpos_blk: (B, C)
        m = qpos_blk[:, None, None, :, None] >= kv_pos[:, None, None, None, :]
        if window > 0:
            m &= (qpos_blk[:, None, None, :, None] - kv_pos[:, None, None, None, :]) < window
        return _attend(q_blk, k, v, m)

    out = _chunked_q(block, q, kv_pos, B, S, num_kv_heads, G, head_dim)
    out = out.reshape(B, S, num_heads * head_dim)
    return out @ p["wo"]


def _chunked_q(block, q, kv_pos, B, S, num_kv_heads, G, head_dim):
    """Scan ``block`` over query chunks (pads S up to a Q_CHUNK multiple;
    padded queries get position −1 → fully masked → sliced away)."""
    if S <= Q_CHUNK:
        return block(q, kv_pos)
    nc = -(-S // Q_CHUNK)
    Sp = nc * Q_CHUNK
    if Sp != S:
        q = jnp.pad(q, ((0, 0), (0, Sp - S)) + ((0, 0),) * (q.ndim - 2))
        kv_pos_q = jnp.pad(kv_pos, ((0, 0), (0, Sp - S)), constant_values=-1)
    else:
        kv_pos_q = kv_pos
    qc = q.reshape(B, nc, Q_CHUNK, num_kv_heads, G, head_dim)
    pc = kv_pos_q.reshape(B, nc, Q_CHUNK)
    out = jax.lax.scan(
        lambda _, xs: (None, block(xs[0], xs[1])),
        None, (jnp.moveaxis(qc, 1, 0), jnp.moveaxis(pc, 1, 0)))[1]
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sp, num_kv_heads, G, head_dim)
    return out[:, :S]


# ---------------------------------------------------------------- caches
#
# KV caches come in two flavours:
#   bf16/f32:  {"k": (B,S,KV,hd), "v": ...} in the model dtype
#   int8:      {"k","v": int8, "k_s","v_s": (B,S,KV) f32 per-token-head
#               absmax scales} — halves decode HBM traffic (§Perf B)

def init_full_cache(batch: int, max_seq: int, num_kv_heads: int,
                    head_dim: int, dtype, quantized: bool = False):
    shp = (batch, max_seq, num_kv_heads, head_dim)
    if quantized:
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_s": jnp.zeros(shp[:3], jnp.float32),
                "v_s": jnp.zeros(shp[:3], jnp.float32)}
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def init_ring_cache(batch: int, window: int, num_kv_heads: int,
                    head_dim: int, dtype, quantized: bool = False):
    return init_full_cache(batch, window, num_kv_heads, head_dim, dtype,
                           quantized)


def init_paged_kv(num_pages: int, page_size: int, num_kv_heads: int,
                  head_dim: int, dtype, quantized: bool = False):
    """Paged pool for a global-attention layer: physical page p holds
    ``page_size`` contiguous token slots of whichever row owns it
    (DESIGN.md §5). Pages are head-major: values (P, KV, ps, hd), int8
    scales (P, KV, 1, ps). The paged kernel reads one (ps, hd) tile per
    (page, kv-head); the TPU lowering needs a block's last two dims to be
    whole array dims or (8, 128)-aligned, which a token-major
    (P, ps, KV, hd) page read per head cannot be. The caller reserves one
    extra *trash* page (by convention the last physical index) that
    unowned block-table entries alias — writes to it are garbage, reads
    from it are always masked."""
    shp = (num_pages, num_kv_heads, page_size, head_dim)
    if quantized:
        sshp = (num_pages, num_kv_heads, 1, page_size)
        return {"k": jnp.zeros(shp, jnp.int8), "v": jnp.zeros(shp, jnp.int8),
                "k_s": jnp.zeros(sshp, jnp.float32),
                "v_s": jnp.zeros(sshp, jnp.float32)}
    return {"k": jnp.zeros(shp, dtype), "v": jnp.zeros(shp, dtype)}


def pages_from_seq(x, page_size: int, *, scale: bool = False):
    """Token-major (..., S, KV, hd) values, or (..., S, KV) int8 scales,
    cut into head-major pages (..., S/ps, KV, ps, hd) or
    (..., S/ps, KV, 1, ps). S must be a page multiple."""
    if scale:
        *lead, S, KV = x.shape
        x = x.reshape(*lead, S // page_size, page_size, KV)
        return jnp.swapaxes(x, -1, -2)[..., None, :]
    *lead, S, KV, hd = x.shape
    x = x.reshape(*lead, S // page_size, page_size, KV, hd)
    return jnp.swapaxes(x, -3, -2)


def seq_from_pages(pages, *, scale: bool = False):
    """Inverse of :func:`pages_from_seq`: (..., n, KV, ps, hd) pages →
    (..., n*ps, KV, hd); (..., n, KV, 1, ps) scales → (..., n*ps, KV)."""
    if scale:
        x = jnp.swapaxes(pages[..., 0, :], -1, -2)        # (..., n, ps, KV)
        *lead, n, ps, KV = x.shape
        return x.reshape(*lead, n * ps, KV)
    x = jnp.swapaxes(pages, -3, -2)                        # (..., n, ps, KV, hd)
    *lead, n, ps, KV, hd = x.shape
    return x.reshape(*lead, n * ps, KV, hd)


def _is_quantized(cache) -> bool:
    return cache["k"].dtype == jnp.int8


def _quantize_kv(x):
    """x: (..., hd) → (int8 values, (...,) f32 scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.round(x.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def _dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)).astype(dtype)


def write_ring_from_kv(cache, k, v, positions):
    """Fill a ring (or short full) cache from already-computed K/V
    (used by the halo-attention prefill path). k, v: (B, S, KV, hd)."""
    S = k.shape[1]
    W = cache["k"].shape[1]
    quant = _is_quantized(cache)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
    else:
        kq, vq, ks, vs = k, v, None, None
    if W < S:
        slots = jnp.mod(positions[-W:], W)
        new = {
            "k": jnp.zeros_like(cache["k"]).at[:, slots].set(
                kq[:, -W:].astype(cache["k"].dtype)),
            "v": jnp.zeros_like(cache["v"]).at[:, slots].set(
                vq[:, -W:].astype(cache["v"].dtype)),
        }
        if quant:
            new["k_s"] = jnp.zeros_like(cache["k_s"]).at[:, slots].set(ks[:, -W:])
            new["v_s"] = jnp.zeros_like(cache["v_s"]).at[:, slots].set(vs[:, -W:])
    else:
        new = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], kq.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vq.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
        if quant:
            new["k_s"] = jax.lax.dynamic_update_slice(cache["k_s"], ks, (0, 0, 0))
            new["v_s"] = jax.lax.dynamic_update_slice(cache["v_s"], vs, (0, 0, 0))
    return new


def ring_slot_positions(pos, window: int):
    """Absolute position held by each ring slot when the newest write is at
    ``pos``: slot s holds the largest p <= pos with p ≡ s (mod W)."""
    s = jnp.arange(window)
    return pos - jnp.mod(pos - s, window)


# ---------------------------------------------------------------- prefill

def attn_prefill(p, x, positions, cache, *, num_heads: int, num_kv_heads: int,
                 head_dim: int, window: int, rope_theta: float,
                 use_rope: bool):
    """Run full attention over the prompt AND populate the cache.

    positions: (S,) absolute, shared across batch (lockstep engine).
    Returns (y, new_cache)."""
    B, S, _ = x.shape
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    if use_rope:
        q = apply_rope(q, positions, rope_theta)
        k = apply_rope(k, positions, rope_theta)

    # Quantize FIRST and attend over what the cache will hold: under int8
    # every attention path (one-shot, chunked, paged, decode) sees the
    # same dequantized values, so serving mode never perturbs logits.
    quant = _is_quantized(cache)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        ka = _dequantize_kv(kq, ks, x.dtype)
        va = _dequantize_kv(vq, vs, x.dtype)
    else:
        kq, vq, ks, vs = k, v, None, None
        ka, va = k, v

    qr = q.reshape(B, S, num_kv_heads, G, head_dim)
    kv_pos = jnp.broadcast_to(positions, (B, S))

    def block(q_blk, qpos_blk):
        m = qpos_blk[:, None, None, :, None] >= kv_pos[:, None, None, None, :]
        if window > 0:
            m &= (qpos_blk[:, None, None, :, None] - kv_pos[:, None, None, None, :]) < window
        return _attend(q_blk, ka, va, m)

    out = _chunked_q(block, qr, kv_pos, B, S, num_kv_heads, G, head_dim)
    y = out.reshape(B, S, num_heads * head_dim) @ p["wo"]

    W = cache["k"].shape[1]
    if window > 0 and W < S:
        # ring cache: keep the last W tokens, rotated so slot = pos % W
        slots = jnp.mod(positions[-W:], W)
        new = {
            "k": jnp.zeros_like(cache["k"]).at[:, slots].set(
                kq[:, -W:].astype(cache["k"].dtype)),
            "v": jnp.zeros_like(cache["v"]).at[:, slots].set(
                vq[:, -W:].astype(cache["v"].dtype)),
        }
        if quant:
            new["k_s"] = jnp.zeros_like(cache["k_s"]).at[:, slots].set(ks[:, -W:])
            new["v_s"] = jnp.zeros_like(cache["v_s"]).at[:, slots].set(vs[:, -W:])
    else:
        new = {
            "k": jax.lax.dynamic_update_slice(
                cache["k"], kq.astype(cache["k"].dtype), (0, 0, 0, 0)),
            "v": jax.lax.dynamic_update_slice(
                cache["v"], vq.astype(cache["v"].dtype), (0, 0, 0, 0)),
        }
        if quant:
            new["k_s"] = jax.lax.dynamic_update_slice(cache["k_s"], ks, (0, 0, 0))
            new["v_s"] = jax.lax.dynamic_update_slice(cache["v_s"], vs, (0, 0, 0))
    return y, new


# ---------------------------------------------------------------- decode

def attn_decode(p, x, pos, cache, *, num_heads: int, num_kv_heads: int,
                head_dim: int, window: int, rope_theta: float,
                use_rope: bool):
    """One-token decode. x: (B, 1, d); pos: scalar absolute position, or
    (B,) int32 per-row positions (continuous batching: pool rows belong
    to different requests and advance independently).
    Returns (y (B,1,d), new_cache)."""
    B = x.shape[0]
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos = jnp.asarray(pos)
    per_row = pos.ndim == 1
    posa = pos[:, None] if per_row else jnp.full((1,), pos)
    if use_rope:
        q = apply_rope(q, posa, rope_theta)
        k = apply_rope(k, posa, rope_theta)

    W = cache["k"].shape[1]
    is_ring = window > 0 and W <= window
    slot = jnp.mod(pos, W) if is_ring else pos
    quant = _is_quantized(cache)
    new_cache = dict(cache)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
    else:
        kq, vq = k, v
    if per_row:
        rows = jnp.arange(B)
        if quant:
            new_cache["k_s"] = cache["k_s"].at[rows, slot].set(ks[:, 0])
            new_cache["v_s"] = cache["v_s"].at[rows, slot].set(vs[:, 0])
        new_cache["k"] = cache["k"].at[rows, slot].set(
            kq[:, 0].astype(cache["k"].dtype))
        new_cache["v"] = cache["v"].at[rows, slot].set(
            vq[:, 0].astype(cache["v"].dtype))
    else:
        if quant:
            new_cache["k_s"] = jax.lax.dynamic_update_slice(cache["k_s"], ks, (0, slot, 0))
            new_cache["v_s"] = jax.lax.dynamic_update_slice(cache["v_s"], vs, (0, slot, 0))
        new_cache["k"] = jax.lax.dynamic_update_slice(
            cache["k"], kq.astype(cache["k"].dtype), (0, slot, 0, 0))
        new_cache["v"] = jax.lax.dynamic_update_slice(
            cache["v"], vq.astype(cache["v"].dtype), (0, slot, 0, 0))
    new_k = new_cache["k"] if not quant else _dequantize_kv(
        new_cache["k"], new_cache["k_s"], x.dtype)
    new_v = new_cache["v"] if not quant else _dequantize_kv(
        new_cache["v"], new_cache["v_s"], x.dtype)

    posq = pos[:, None] if per_row else pos  # (B,1) or scalar
    if is_ring:
        kv_positions = ring_slot_positions(posq, W)         # (W,) or (B,W)
        valid = (kv_positions >= 0) & (kv_positions <= posq)
        if window > 0:
            valid &= (posq - kv_positions) < window
    else:
        kv_positions = jnp.arange(W)
        valid = kv_positions <= posq
        if window > 0:
            valid &= (posq - kv_positions) > -1
            valid &= (posq - kv_positions) < window

    qr = q.reshape(B, 1, num_kv_heads, G, head_dim)
    mask = valid[:, None, None, None, :] if valid.ndim == 2 \
        else valid[None, None, None, None, :]
    out = _attend(qr, new_k, new_v, mask)
    y = out.reshape(B, 1, num_heads * head_dim) @ p["wo"]
    return y, new_cache


# ------------------------------------------------------------ chunk prefill
#
# Chunked prefill (DESIGN.md §6): a (B, C) slice of the prompt is run
# against a cache that already holds each row's first pos0 tokens, so a
# long admission advances one bounded chunk per scheduler tick instead
# of stalling every decode row for the whole prompt. Keys are always
# ordered by absolute position (history first, then the chunk) — ring
# layers included, whose slot-ordered window is re-gathered ascending —
# so the causal mask only ever *trails*: masked slots contribute
# exact-0.0 terms outside the real keys, which is what keeps the final
# chunk's logits bitwise equal to the one-shot prefill on the same
# positions for every layer kind.


def _write_chunk_kv(cache, kq, vq, ks, vs, rows, slots, quant):
    """Scatter a chunk's (B, C) K/V (and int8 scales) into per-row cache
    slots. ``rows``: (B, 1); ``slots``: (B, C)."""
    new = dict(cache)
    new["k"] = cache["k"].at[rows, slots].set(kq.astype(cache["k"].dtype))
    new["v"] = cache["v"].at[rows, slots].set(vq.astype(cache["v"].dtype))
    if quant:
        new["k_s"] = cache["k_s"].at[rows, slots].set(ks)
        new["v_s"] = cache["v_s"].at[rows, slots].set(vs)
    return new


def attn_prefill_chunk(p, x, pos0, cache, *, hist_len: int, num_heads: int,
                       num_kv_heads: int, head_dim: int, window: int,
                       rope_theta: float, use_rope: bool):
    """Chunk prefill against a contiguous (full or ring) cache.

    x: (B, C, d); pos0: (B,) absolute position of each row's first chunk
    token; the cache already holds positions < pos0. ``hist_len`` is the
    static history slice bound for full caches (callers pass the exact
    filled length, so no masked slot sits between real keys); ring
    caches ignore it (their whole window is the history). Returns
    (y (B, C, d), new_cache)."""
    B, C, _ = x.shape
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos0 = jnp.asarray(pos0)
    qpos = pos0[:, None] + jnp.arange(C)                       # (B, C)
    if use_rope:
        q = apply_rope(q, qpos, rope_theta)
        k = apply_rope(k, qpos, rope_theta)

    W = cache["k"].shape[1]
    is_ring = window > 0 and W <= window
    quant = _is_quantized(cache)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        # attend-from-cache: the chunk's own keys go through the same
        # quantize→dequantize round trip the history already took, so
        # chunked int8 prefill stays bitwise equal to one-shot (which
        # rounds identically) and to the paged path (which re-reads the
        # chunk from its pages)
        kc = _dequantize_kv(kq, ks, x.dtype)
        vc = _dequantize_kv(vq, vs, x.dtype)
    else:
        kq, vq, ks, vs = k, v, None, None
        kc, vc = k, v
    rows = jnp.arange(B)[:, None]

    if is_ring:
        # history = the whole ring as it stands before this chunk,
        # gathered in ascending absolute-position order: position
        # pos0 - W + i lives at slot (pos0 + i) mod W. Slot order (a
        # rotation) holds the same keys but permutes the nonzero softmax
        # terms, which perturbs the fp summation order — ascending order
        # is what makes chunked ring prefill bitwise-equal to the
        # one-shot path across chunk arrangements (DESIGN.md §6).
        slots_asc = jnp.mod(pos0[:, None] + jnp.arange(W), W)  # (B, W)
        hist_pos = pos0[:, None] - W + jnp.arange(W)           # (B, W)
        hk = cache["k"][rows, slots_asc]
        hv = cache["v"][rows, slots_asc]
        if quant:
            hk = _dequantize_kv(hk, cache["k_s"][rows, slots_asc], x.dtype)
            hv = _dequantize_kv(hv, cache["v_s"][rows, slots_asc], x.dtype)
        kv_pos = jnp.concatenate([hist_pos, qpos], axis=1)     # (B, W + C)
        ka = jnp.concatenate([hk, kc], axis=1)
        va = jnp.concatenate([hv, vc], axis=1)
        valid = kv_pos >= 0
        # write the chunk's last min(C, W) tokens (their slots are
        # distinct mod W; older chunk tokens would be overwritten anyway)
        if C > W:
            wslots = jnp.mod(qpos[:, -W:], W)
            kw, vw = kq[:, -W:], vq[:, -W:]
            ksw = ks[:, -W:] if quant else None
            vsw = vs[:, -W:] if quant else None
        else:
            wslots, kw, vw, ksw, vsw = jnp.mod(qpos, W), kq, vq, ks, vs
        new_cache = _write_chunk_kv(cache, kw, vw, ksw, vsw, rows, wslots,
                                    quant)
    else:
        hk, hv = cache["k"][:, :hist_len], cache["v"][:, :hist_len]
        if quant:
            hk = _dequantize_kv(hk, cache["k_s"][:, :hist_len], x.dtype)
            hv = _dequantize_kv(hv, cache["v_s"][:, :hist_len], x.dtype)
        hist_pos = jnp.broadcast_to(jnp.arange(hist_len), (B, hist_len))
        kv_pos = jnp.concatenate([hist_pos, qpos], axis=1)     # (B, H + C)
        ka = jnp.concatenate([hk, kc], axis=1)
        va = jnp.concatenate([hv, vc], axis=1)
        # history slots at/after pos0 hold garbage (or other rows' data)
        valid = kv_pos < pos0[:, None]
        valid = valid.at[:, hist_len:].set(True)
        new_cache = _write_chunk_kv(cache, kq, vq, ks, vs, rows, qpos, quant)

    mask = valid[:, None, :] & (kv_pos[:, None, :] <= qpos[:, :, None])
    if window > 0:
        mask &= (qpos[:, :, None] - kv_pos[:, None, :]) < window
    mask = mask[:, None, None]                                 # (B,1,1,C,S)

    qr = q.reshape(B, C, num_kv_heads, G, head_dim)
    out = _attend(qr, ka, va, mask)
    y = out.reshape(B, C, num_heads * head_dim) @ p["wo"]
    return y, new_cache


def _gather_pages(cache, block_tables, dtype):
    """Gather-oracle view of each row's pages: (B, MP*ps, KV, hd) K and V
    in logical token order, int8 pages dequantized to ``dtype``."""
    ka = seq_from_pages(cache["k"][block_tables])
    va = seq_from_pages(cache["v"][block_tables])
    if _is_quantized(cache):
        ka = _dequantize_kv(
            ka, seq_from_pages(cache["k_s"][block_tables], scale=True), dtype)
        va = _dequantize_kv(
            va, seq_from_pages(cache["v_s"][block_tables], scale=True), dtype)
    return ka, va


def attn_prefill_chunk_paged(p, x, pos0, cache, block_tables, chunk_pages, *,
                             num_heads: int, num_kv_heads: int,
                             head_dim: int, rope_theta: float,
                             use_rope: bool):
    """Chunk prefill writing straight into allocator-owned pages — no
    batch-1 side cache for the global layers (DESIGN.md §6).

    x: (B, C, d); pos0: (B,); cache: page pool from :func:`init_paged_kv`;
    block_tables: (B, MP) the rows' tables (prompt pages so far, trash
    elsewhere); chunk_pages: (B, C) physical page of each chunk token
    (all refcount-1 during prefill — the allocator hands them out before
    the chunk runs). Attention gathers the row's pages exactly like the
    decode oracle; validity is purely positional. Returns
    (y (B, C, d), new_cache)."""
    B, C, _ = x.shape
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos0 = jnp.asarray(pos0)
    qpos = pos0[:, None] + jnp.arange(C)                       # (B, C)
    if use_rope:
        q = apply_rope(q, qpos, rope_theta)
        k = apply_rope(k, qpos, rope_theta)

    ps = cache["k"].shape[2]
    off = jnp.mod(qpos, ps)
    quant = _is_quantized(cache)
    new_cache = dict(cache)
    # head-major pages: (page, :, slot) picks a token's (KV, hd) values
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache["k_s"] = cache["k_s"].at[chunk_pages, :, 0, off].set(ks)
        new_cache["v_s"] = cache["v_s"].at[chunk_pages, :, 0, off].set(vs)
    else:
        kq, vq = k, v
    new_cache["k"] = cache["k"].at[chunk_pages, :, off].set(
        kq.astype(cache["k"].dtype))
    new_cache["v"] = cache["v"].at[chunk_pages, :, off].set(
        vq.astype(cache["v"].dtype))

    if use_paged_kernel():
        # paged chunk-prefill kernel: C chunk tokens attend causally over
        # the row's pages, streamed through the block table — the same
        # no-HBM-gather property as the decode kernel, int8 included
        from repro.kernels.decode_attn.ops import paged_prefill_attn
        _count_paged_backend("prefill_kernel")
        out = paged_prefill_attn(
            q, new_cache["k"], new_cache["v"], block_tables, pos0,
            k_scales=new_cache["k_s"] if quant else None,
            v_scales=new_cache["v_s"] if quant else None)
        y = (out.astype(x.dtype).reshape(B, C, num_heads * head_dim)
             @ p["wo"])
        return y, new_cache

    _count_paged_backend("prefill_oracle")
    ka, va = _gather_pages(new_cache, block_tables, x.dtype)
    kv_pos = jnp.arange(ka.shape[1])
    mask = kv_pos[None, None, :] <= qpos[:, :, None]           # (B, C, S)
    mask = mask[:, None, None]

    qr = q.reshape(B, C, num_kv_heads, G, head_dim)
    out = _attend(qr, ka, va, mask)
    y = out.reshape(B, C, num_heads * head_dim) @ p["wo"]
    return y, new_cache


_PAGED_KERNEL: Optional[bool] = None

# Trace-time record of which backend the paged attention paths actually
# dispatched — the kernel/oracle choice is a *Python* branch, invisible in
# jaxprs and silent at runtime. Every trace of a paged attention function
# bumps exactly one key, so a test (or an operator reading server stats)
# can assert the Pallas kernel really traced instead of silently falling
# back to the jnp gather oracle (the int8 bypass bug this guards against).
_PAGED_BACKEND_COUNTS = {"decode_kernel": 0, "decode_oracle": 0,
                         "prefill_kernel": 0, "prefill_oracle": 0}


def paged_backend_counts() -> dict:
    """Snapshot of trace-time paged-attention backend choices."""
    return dict(_PAGED_BACKEND_COUNTS)


def reset_paged_backend_counts() -> None:
    for key in _PAGED_BACKEND_COUNTS:
        _PAGED_BACKEND_COUNTS[key] = 0


def _count_paged_backend(which: str) -> None:
    _PAGED_BACKEND_COUNTS[which] += 1


def set_paged_kernel(flag: Optional[bool]) -> None:
    """Force the paged flash-decode kernel on/off (None = auto: kernel on
    TPU, jnp gather oracle under the Pallas interpreter / CPU). Tests set
    True to run the wired kernel path through the interpreter.

    The choice is captured at jit TRACE time: already-compiled callers
    (e.g. the scheduler's cached decode step) keep whichever path they
    were traced with — toggle before the first paged decode, or call
    ``attn_decode_paged`` eagerly as the wiring test does. The auto
    resolution is backend-based and stable for a process lifetime, so
    this only matters for explicit mid-process toggles."""
    global _PAGED_KERNEL
    _PAGED_KERNEL = flag


def use_paged_kernel() -> bool:
    if _PAGED_KERNEL is not None:
        return _PAGED_KERNEL
    from repro.kernels import interpret_mode
    return not interpret_mode()


def attn_decode_paged(p, x, pos, cache, block_tables, write_pages=None, *,
                      num_heads: int, num_kv_heads: int, head_dim: int,
                      rope_theta: float, use_rope: bool):
    """One-token decode against a paged KV pool (global layers only).

    x: (B, 1, d); pos: (B,) int32 per-row positions; cache: page pool from
    :func:`init_paged_kv` with leaves (P, KV, ps, hd); block_tables:
    (B, MP) int32 mapping row-logical pages to physical pages (unowned
    entries alias the trash page — validity is purely ``kv_pos <= pos``).

    The current token's K/V is written into ``write_pages`` ((B,) int32)
    when given — the scheduler computes it from allocator truth via
    ``PageAllocator.write_page``, which asserts each write page is
    refcount-1, so with prefix sharing a decode write is provably
    confined to unshared pages — else into the page the block table
    names at ``pos`` (standalone callers own every page privately).
    Attention then runs over the row's own pages: the gather below is
    the pure-jnp CPU oracle; when :func:`use_paged_kernel` is true the
    paged flash-decode kernel (kernels/decode_attn) streams the pages
    directly through the block table instead.
    Returns (y (B,1,d), new_cache)."""
    B = x.shape[0]
    G = num_heads // num_kv_heads
    q, k, v = _project_qkv(p, x, num_heads, num_kv_heads, head_dim)
    pos = jnp.asarray(pos)
    if use_rope:
        q = apply_rope(q, pos[:, None], rope_theta)
        k = apply_rope(k, pos[:, None], rope_theta)

    ps = cache["k"].shape[2]
    lpage = pos // ps
    off = pos % ps
    if write_pages is None:
        phys = jnp.take_along_axis(block_tables, lpage[:, None], axis=1)[:, 0]
    else:
        phys = jnp.asarray(write_pages)

    quant = _is_quantized(cache)
    new_cache = dict(cache)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache["k_s"] = cache["k_s"].at[phys, :, 0, off].set(ks[:, 0])
        new_cache["v_s"] = cache["v_s"].at[phys, :, 0, off].set(vs[:, 0])
    else:
        kq, vq = k, v
    new_cache["k"] = cache["k"].at[phys, :, off].set(
        kq[:, 0].astype(cache["k"].dtype))
    new_cache["v"] = cache["v"].at[phys, :, off].set(
        vq[:, 0].astype(cache["v"].dtype))

    if use_paged_kernel():
        # paged flash-decode kernel: the S-tile index map dereferences the
        # block table, so only owned (and trash-aliased) pages stream
        # through VMEM — no (B, MP*ps, ...) gather materialized in HBM.
        # Int8 pools pass their scale pages for in-kernel dequant (the
        # quantized case used to silently drop to the oracle below).
        from repro.kernels.decode_attn.ops import paged_decode_attn
        _count_paged_backend("decode_kernel")
        out = paged_decode_attn(
            q[:, 0], new_cache["k"], new_cache["v"], block_tables, pos,
            k_scales=new_cache["k_s"] if quant else None,
            v_scales=new_cache["v_s"] if quant else None)
        y = out.astype(x.dtype).reshape(B, 1, num_heads * head_dim) @ p["wo"]
        return y, new_cache

    _count_paged_backend("decode_oracle")
    ka, va = _gather_pages(new_cache, block_tables, x.dtype)
    kv_positions = jnp.arange(ka.shape[1])
    valid = kv_positions[None, :] <= pos[:, None]               # (B, S)

    qr = q.reshape(B, 1, num_kv_heads, G, head_dim)
    out = _attend(qr, ka, va, valid[:, None, None, None, :])
    y = out.reshape(B, 1, num_heads * head_dim) @ p["wo"]
    return y, new_cache


# ------------------------------------------------- halo attention (SP)
#
# §Perf hillclimb C iteration 2: with sequence parallelism the residual
# stream is seq-sharded on "model". A sliding-window layer does NOT need
# the full sequence gathered — each shard attends to its own tokens plus
# a window-sized halo from its left neighbour (one collective-permute of
# W tokens instead of an all-gather of S). Requires W ≤ S/shards.

_HALO_MESH = None


def set_halo_mesh(mesh) -> None:
    global _HALO_MESH
    _HALO_MESH = mesh


def halo_attn_available(seq_len: int, window: int, model_size: int) -> bool:
    return (_HALO_MESH is not None and seq_len % model_size == 0
            and window <= seq_len // model_size)


def attn_forward_halo(p, x, *, num_heads: int, num_kv_heads: int,
                      head_dim: int, window: int, rope_theta: float,
                      use_rope: bool, dp_axes=("pod", "data"),
                      model_axis: str = "model", return_kv: bool = False):
    """Sliding-window attention over a seq-sharded residual stream.

    x: (B, S, d) logically; sharded (dp, model, None). Returns y with the
    same sharding (and optionally the full-precision k, v for cache fill).
    """
    from jax.sharding import PartitionSpec as P
    mesh = _HALO_MESH
    dp = tuple(a for a in dp_axes if a in mesh.axis_names)
    M = mesh.shape[model_axis]

    def inner(wq, wk, wv, wo, bq, bk, bv, xl):
        B, S_loc, d = xl.shape
        idx = jax.lax.axis_index(model_axis)
        base = idx * S_loc
        q = xl @ wq
        k = xl @ wk
        v = xl @ wv
        if bq is not None:
            q, k, v = q + bq, k + bk, v + bv
        q = q.reshape(B, S_loc, num_heads, head_dim)
        k = k.reshape(B, S_loc, num_kv_heads, head_dim)
        v = v.reshape(B, S_loc, num_kv_heads, head_dim)
        positions = base + jnp.arange(S_loc)
        if use_rope:
            q = apply_rope(q, positions, rope_theta)
            k = apply_rope(k, positions, rope_theta)

        W = min(window, S_loc)
        perm = [(j, j + 1) for j in range(M - 1)]  # shard j → j+1
        k_halo = jax.lax.ppermute(k[:, -W:], model_axis, perm)
        v_halo = jax.lax.ppermute(v[:, -W:], model_axis, perm)
        k_full = jnp.concatenate([k_halo, k], axis=1)   # (B, W+S_loc, KV, hd)
        v_full = jnp.concatenate([v_halo, v], axis=1)
        kv_pos = base - W + jnp.arange(W + S_loc)       # halo positions < base

        G = num_heads // num_kv_heads
        qr = q.reshape(B, S_loc, num_kv_heads, G, head_dim)
        qp = positions[None, :]
        kp = kv_pos[None, :]
        mask = (qp[:, None, None, :, None] >= kp[:, None, None, None, :]) \
            & ((qp[:, None, None, :, None] - kp[:, None, None, None, :]) < window) \
            & (kp[:, None, None, None, :] >= 0)
        out = _attend(qr, k_full, v_full, mask)
        y = out.reshape(B, S_loc, num_heads * head_dim) @ wo
        return y, k, v

    xspec = P(dp if dp else None, model_axis, None)
    f = jax.shard_map(inner, mesh=mesh, check_vma=False,
                      in_specs=(P(), P(), P(), P(), P(), P(), P(), xspec),
                      out_specs=(xspec, xspec, xspec))
    bq, bk, bv = p.get("bq"), p.get("bk"), p.get("bv")
    y, k, v = f(p["wq"], p["wk"], p["wv"], p["wo"], bq, bk, bv, x)
    if return_kv:
        return y, k, v
    return y


# ---------------------------------------------------------------- cross

def init_cross_attn(rng, d_model: int, num_heads: int, num_kv_heads: int,
                    head_dim: int, dtype):
    return init_attn(rng, d_model, num_heads, num_kv_heads, head_dim, False, dtype)


def cross_attn_kv(p, enc_out, num_kv_heads: int, head_dim: int):
    """Precompute K,V from encoder output: (B, S_enc, KV, hd) each."""
    B, S, _ = enc_out.shape
    k = (enc_out @ p["wk"]).reshape(B, S, num_kv_heads, head_dim)
    v = (enc_out @ p["wv"]).reshape(B, S, num_kv_heads, head_dim)
    return k, v


def cross_attn(p, x, enc_k, enc_v, *, num_heads: int, num_kv_heads: int,
               head_dim: int):
    """Decoder→encoder cross attention (no causal mask, no rope)."""
    B, S, _ = x.shape
    G = num_heads // num_kv_heads
    q = (x @ p["wq"]).reshape(B, S, num_kv_heads, G, head_dim)
    mask = jnp.ones((1, 1, 1, 1, enc_k.shape[1]), bool)
    out = _attend(q, enc_k, enc_v, mask)
    return out.reshape(B, S, num_heads * head_dim) @ p["wo"]
