"""Flash-decode GQA attention kernel (one new token vs a long KV cache).

Why a kernel: decode attention is the memory-bound inner loop of the
serving engine — every step streams the whole KV cache once. Flash-decode
tiles the cache sequence into VMEM blocks and keeps online-softmax
statistics per (batch, kv-head), so HBM traffic is exactly one read of
K and V, no (S,) score materialization in HBM, and the G=H/KV query rows
of a GQA group ride along in registers/VMEM (sublane dim) for free.

Grid: (B, KV, S/TS) — S innermost (sequential). Scratch per (b, kv):
  m (G,1), l (G,1), acc (G, hd). Output written on the last S tile.

Masking (causal / sliding-window / ring-buffer slot semantics) is
computed from the absolute position scalar, prefetched via
PrefetchScalarGridSpec so block index maps could depend on it if needed.

TS defaults to 512: K tile + V tile = 2·512·hd·2B ≈ 256 KiB (hd=128
bf16) — comfortably inside VMEM with double buffering.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG = -2.0 ** 30


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, m_s, l_s, acc_s,
            *, n_s_tiles: int, tile_s: int, window: int, ring: bool,
            seq: int, scale: float):
    si = pl.program_id(2)
    pos = pos_ref[0]

    @pl.when(si == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)           # (G, hd)
    k = k_ref[0, :, 0].astype(jnp.float32)        # (TS, hd)
    v = v_ref[0, :, 0].astype(jnp.float32)        # (TS, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (G, TS)

    slots = si * tile_s + jax.lax.broadcasted_iota(jnp.int32, (1, tile_s), 1)
    if ring:
        kv_pos = pos - jnp.mod(pos - slots, seq)
    else:
        kv_pos = slots
    valid = (kv_pos >= 0) & (kv_pos <= pos) & (slots < seq)  # last: seq padding
    if window > 0:
        valid &= (pos - kv_pos) < window
    s = jnp.where(valid, s, NEG)

    m_prev = m_s[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    r = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                        # (G, TS)
    l_s[:] = l_s[:] * r + jnp.sum(p, axis=-1, keepdims=True)
    acc_s[:] = acc_s[:] * r + jnp.dot(p, v, preferred_element_type=jnp.float32)
    m_s[:] = m_new

    @pl.when(si == n_s_tiles - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def decode_attn_pallas(q, k, v, pos, *, window: int = 0, ring: bool = False,
                       tile_s: int = 512, interpret: Optional[bool] = None):
    """q: (B, H, hd); k, v: (B, S, KV, hd); pos: scalar int32.
    Returns (B, H, hd) fp32. See ref.py for slot semantics.

    ``interpret=None`` resolves via :func:`repro.kernels.interpret_mode`
    (compiled on TPU, interpreter elsewhere) — callers bypassing ops.py no
    longer silently run the Pallas interpreter on real hardware."""
    if interpret is None:
        interpret = interpret_mode()
    return _decode_attn_jit(q, k, v, pos, window=window, ring=ring,
                            tile_s=tile_s, interpret=interpret)


@functools.partial(jax.jit,
                   static_argnames=("window", "ring", "tile_s", "interpret"))
def _decode_attn_jit(q, k, v, pos, *, window: int, ring: bool,
                     tile_s: int, interpret: bool):
    B, S, KV, hd = k.shape
    H = q.shape[1]
    G = H // KV
    ts = min(tile_s, S)
    Sp = -(-S // ts) * ts
    if Sp != S:
        k = jnp.pad(k, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, Sp - S), (0, 0), (0, 0)))
        # padded slots: ring positions computed mod original seq — mask
        # them via kv_pos > pos (slots >= S get kv_pos = slot > pos in
        # non-ring; in ring mode pad is masked below via seq=S semantics)
    n_s = Sp // ts
    qr = q.reshape(B, KV, G, hd)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KV, n_s),
        in_specs=[
            pl.BlockSpec((1, 1, G, hd), lambda b, kv, s, pos_ref: (b, kv, 0, 0)),
            pl.BlockSpec((1, ts, 1, hd), lambda b, kv, s, pos_ref: (b, s, kv, 0)),
            pl.BlockSpec((1, ts, 1, hd), lambda b, kv, s, pos_ref: (b, s, kv, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, hd), lambda b, kv, s, pos_ref: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_kernel, n_s_tiles=n_s, tile_s=ts, window=window,
                             ring=ring, seq=S, scale=hd ** -0.5)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, G, hd), jnp.float32),
        interpret=interpret,
    )(jnp.asarray(pos, jnp.int32).reshape(1), qr, k, v)
    return out.reshape(B, H, hd)


# ----------------------------------------------------------------- paged

def _paged_kernel(bt_ref, pos_ref, q_ref, k_ref, v_ref, *rest,
                  n_pages: int, page_size: int, group: int, scale: float,
                  quantized: bool):
    """Paged flash attention body, shared by decode and chunk prefill:
    one grid step streams one owned page against R query rows.

    The S-tile index map dereferences the block table (scalar-prefetched),
    so the kernel's K/V DMAs touch only physical pages a row's table names
    — pruned/unallocated capacity is never streamed. Validity is purely
    positional; table entries past a row's position may alias a shared
    trash page and are masked here.

    Query-row positions: row r belongs to chunk token r // group at
    absolute position pos_ref[b] + r // group. Decode is the R == group
    case (every row is the same single token at pos). Causality between
    chunk tokens falls out of the same mask: a chunk token never sees a
    younger sibling's freshly written slot.

    ``quantized`` prepends per-token-head fp32 scale refs (same block-table
    indexed layout as K/V) to ``rest``; dequant happens in VMEM, so int8
    KV never materializes as fp32 in HBM."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_s, l_s, acc_s = rest
    else:
        o_ref, m_s, l_s, acc_s = rest
    b = pl.program_id(0)
    li = pl.program_id(2)                         # logical page index
    pos = pos_ref[b]
    R = q_ref.shape[2]

    @pl.when(li == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    q = q_ref[0, 0].astype(jnp.float32)           # (R, hd)
    k = k_ref[0, 0].astype(jnp.float32)           # (ps, hd)
    v = v_ref[0, 0].astype(jnp.float32)           # (ps, hd)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale  # (R, ps)
    if quantized:
        # per-token scales arrive lane-major (1, ps): dequantizing the
        # scores and probabilities is exact algebra for (k * s_k) and
        # (v * s_v), and needs no sublane<->lane transpose of the scales
        s = s * ks_ref[0, 0]

    qpos = pos + jax.lax.broadcasted_iota(
        jnp.int32, (R, page_size), 0) // group
    kv_pos = li * page_size + jax.lax.broadcasted_iota(
        jnp.int32, (R, page_size), 1)
    valid = kv_pos <= qpos
    s = jnp.where(valid, s, NEG)

    m_prev = m_s[:]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    r = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # guard all-masked tiles
    l_s[:] = l_s[:] * r + jnp.sum(p, axis=-1, keepdims=True)
    pv = p * vs_ref[0, 0] if quantized else p
    acc_s[:] = acc_s[:] * r + jnp.dot(pv, v, preferred_element_type=jnp.float32)
    m_s[:] = m_new

    @pl.when(li == n_pages - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def paged_decode_attn_pallas(q, k_pages, v_pages, block_tables, pos, *,
                             k_scales=None, v_scales=None,
                             interpret: Optional[bool] = None):
    """Paged GQA flash-decode. q: (B, H, hd); k_pages, v_pages:
    (P, KV, ps, hd) head-major page pools; block_tables: (B, MP) int32
    physical page per logical page; pos: (B,) int32 per-row positions.

    ``k_scales``/``v_scales`` (P, KV, 1, ps) fp32 activate the int8 path:
    pages are dequantized in VMEM (scale pages ride the same block-table
    scalar prefetch), never as fp32 in HBM.

    Returns (B, H, hd) fp32. See ref.paged_decode_attn_ref for the page
    semantics (entries past pos may alias a trash page — masked)."""
    if interpret is None:
        interpret = interpret_mode()
    out = _paged_attn_jit(q[:, None], k_pages, v_pages, block_tables, pos,
                          k_scales, v_scales, interpret=interpret)
    return out[:, 0]


def paged_prefill_attn_pallas(q, k_pages, v_pages, block_tables, pos0, *,
                              k_scales=None, v_scales=None,
                              interpret: Optional[bool] = None):
    """Paged GQA chunk-prefill attention: C chunk tokens per row attend
    causally over the row's pages (history + the chunk's own freshly
    written slots). q: (B, C, H, hd); pos0: (B,) int32 absolute position
    of each row's first chunk token. The block table prefix is expected
    bucketed by the caller (scheduler `_chunk_args` style) so jit keys
    stay stable across chunk counts.

    ``k_scales``/``v_scales`` (P, KV, 1, ps) fp32 activate the int8 path.
    Returns (B, C, H, hd) fp32."""
    if interpret is None:
        interpret = interpret_mode()
    return _paged_attn_jit(q, k_pages, v_pages, block_tables, pos0,
                           k_scales, v_scales, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _paged_attn_jit(q, k_pages, v_pages, block_tables, pos,
                    k_scales, v_scales, *, interpret: bool):
    B, C, H, hd = q.shape
    P, KV, ps, _ = k_pages.shape
    MP = block_tables.shape[1]
    G = H // KV
    R = C * G
    quant = k_scales is not None
    # group query rows by kv-head: row r = chunk token r // G, head r % G
    qr = (q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, KV, R, hd))
    bt_flat = jnp.asarray(block_tables, jnp.int32).reshape(B * MP)

    def page_map(b, kv, l, bt_ref, pos_ref):
        # dereference the block table: stream only the row's own pages.
        # Head-major pages make each block's last two dims whole array
        # dims — (ps, hd) values, (1, ps) scales — as the TPU tiling needs
        return (bt_ref[b * MP + l], kv, 0, 0)

    in_specs = [
        pl.BlockSpec((1, 1, R, hd),
                     lambda b, kv, l, bt_ref, pos_ref: (b, kv, 0, 0)),
        pl.BlockSpec((1, 1, ps, hd), page_map),
        pl.BlockSpec((1, 1, ps, hd), page_map),
    ]
    operands = [bt_flat, jnp.asarray(pos, jnp.int32), qr, k_pages, v_pages]
    if quant:
        in_specs += [pl.BlockSpec((1, 1, 1, ps), page_map),
                     pl.BlockSpec((1, 1, 1, ps), page_map)]
        operands += [k_scales, v_scales]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, MP),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, R, hd),
                               lambda b, kv, l, bt_ref, pos_ref: (b, kv, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, hd), jnp.float32),
        ],
    )
    kern = functools.partial(_paged_kernel, n_pages=MP, page_size=ps,
                             group=G, scale=hd ** -0.5, quantized=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
        interpret=interpret,
    )(*operands)
    return (out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, hd))
