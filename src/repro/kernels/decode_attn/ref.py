"""Pure-jnp oracle for GQA flash-decode with full / ring KV caches."""
from __future__ import annotations

import jax
import jax.numpy as jnp

NEG_INF = -2.0 ** 30


def decode_attn_ref(q, k, v, pos, *, window: int = 0, ring: bool = False):
    """One-token GQA decode attention.

    q: (B, H, hd) — query for the current token (already rope'd)
    k, v: (B, S, KV, hd) — cache contents (slot s semantics below)
    pos: scalar int — absolute position of the current token (its K/V is
         already written into the cache)
    window: sliding window size (0 = global)
    ring: if True the cache is a ring buffer (slot s holds the largest
          p ≤ pos with p ≡ s mod S), else slot s holds position s.

    Returns (B, H, hd) fp32.
    """
    B, S, KV, hd = k.shape
    H = q.shape[1]
    G = H // KV

    slots = jnp.arange(S)
    if ring:
        kv_pos = pos - jnp.mod(pos - slots, S)
    else:
        kv_pos = slots
    valid = (kv_pos >= 0) & (kv_pos <= pos)
    if window > 0:
        valid &= (pos - kv_pos) < window

    qr = q.reshape(B, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bskh->bkgs", qr, k.astype(jnp.float32))
    scores = scores * (hd ** -0.5)
    scores = jnp.where(valid[None, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v.astype(jnp.float32))
    return out.reshape(B, H, hd)


def _gather_paged_kv(k_pages, v_pages, block_tables, k_scales, v_scales):
    """Gather each row's head-major pages into a contiguous logical fp32
    view (B, MP*ps, KV, hd), dequantizing int8 pages when scales are
    given."""
    B, MP = block_tables.shape
    P, KV, ps, hd = k_pages.shape

    def logical(pages):                   # (B, MP, KV, ps, hd) -> seq view
        return (pages.transpose(0, 1, 3, 2, 4)
                .reshape(B, MP * ps, KV, hd).astype(jnp.float32))

    def logical_scales(s):                # (B, MP, KV, 1, ps) -> (B, S, KV, 1)
        return s[:, :, :, 0].transpose(0, 1, 3, 2).reshape(B, MP * ps, KV, 1)

    k = logical(k_pages[block_tables])
    v = logical(v_pages[block_tables])
    if k_scales is not None:
        k = k * logical_scales(k_scales[block_tables])
        v = v * logical_scales(v_scales[block_tables])
    return k, v


def paged_decode_attn_ref(q, k_pages, v_pages, block_tables, pos, *,
                          k_scales=None, v_scales=None):
    """One-token GQA decode attention over a paged KV pool.

    q: (B, H, hd) — query for the current token (already rope'd)
    k_pages, v_pages: (P, KV, ps, hd) — global head-major page pool;
        physical page p holds ps contiguous token slots of whichever row
        owns it
    block_tables: (B, MP) int32 — row b's logical page l lives at physical
        page block_tables[b, l]. Entries for pages past a row's current
        position may point anywhere (a shared trash page): validity is
        purely positional, ``kv_pos <= pos[b]``, because the allocator
        only hands out pages covering positions the row will write.
    pos: (B,) int32 — per-row absolute position of the current token
        (its K/V already written into the owning page)
    k_scales, v_scales: optional (P, KV, 1, ps) fp32 per-token-head
        scales for int8 page pools (dequantized before attention).

    Returns (B, H, hd) fp32.
    """
    B, H, hd = q.shape
    P, KV, ps, _ = k_pages.shape
    MP = block_tables.shape[1]
    G = H // KV

    k, v = _gather_paged_kv(k_pages, v_pages, block_tables,
                            k_scales, v_scales)

    kv_pos = jnp.arange(MP * ps)
    valid = kv_pos[None, :] <= jnp.asarray(pos)[:, None]        # (B, S)

    qr = q.reshape(B, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bkgh,bskh->bkgs", qr, k)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", probs, v)
    return out.reshape(B, H, hd)


def paged_prefill_attn_ref(q, k_pages, v_pages, block_tables, pos0, *,
                           k_scales=None, v_scales=None):
    """Chunk-prefill GQA attention over a paged KV pool.

    q: (B, C, H, hd) — C chunk tokens per row (already rope'd); their K/V
        is already written into the owning pages.
    pos0: (B,) int32 — absolute position of each row's first chunk token;
        chunk token c sits at pos0 + c and attends causally over
        ``kv_pos <= pos0 + c``.
    k_scales, v_scales: optional (P, KV, 1, ps) fp32 per-token-head scales.

    Returns (B, C, H, hd) fp32.
    """
    B, C, H, hd = q.shape
    P, KV, ps, _ = k_pages.shape
    MP = block_tables.shape[1]
    G = H // KV

    k, v = _gather_paged_kv(k_pages, v_pages, block_tables,
                            k_scales, v_scales)

    kv_pos = jnp.arange(MP * ps)
    qpos = jnp.asarray(pos0)[:, None] + jnp.arange(C)[None, :]  # (B, C)
    valid = kv_pos[None, None, :] <= qpos[:, :, None]           # (B, C, S)

    qr = q.reshape(B, C, KV, G, hd).astype(jnp.float32)
    scores = jnp.einsum("bckgh,bskh->bckgs", qr, k)
    scores = scores * (hd ** -0.5)
    scores = jnp.where(valid[:, :, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bckgs,bskh->bckgh", probs, v)
    return out.reshape(B, C, H, hd)
