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

# VMEM for one grid step's page tile: the f32 scores of every query row
# against the tile, plus the double-buffered K and V pages (and int8
# scale pages). 1 MiB keeps a prefill chunk's step at one page: a
# 3,584-row chunk's query, output, accumulator and softmax-statistics
# blocks already take about 12 MiB of the 16 MiB a v5e kernel gets by
# default, and one more page of its scores is 0.9 MiB. A decode step
# (one token, G query rows) fits 15 pages of 64 tokens at hd 128 bf16.
_TILE_VMEM_BYTES = 1 << 20


def _pages_per_tile(rows: int, page_size: int, head_dim: int,
                    itemsize: int, quantized: bool, max_pages: int) -> int:
    """Pages each grid step walks, from the call's shapes: as many as fit
    ``_TILE_VMEM_BYTES``, at least one, at most the block table."""
    rows_pad = -(-rows // 8) * 8                  # f32 sublanes
    lanes = -(-head_dim // 128) * 128             # a page's rows in VMEM
    page = page_size * lanes * itemsize
    if quantized:                                 # a (1, ps) f32 row,
        page += 8 * (-(-page_size // 128) * 128) * 4  # padded to (8, 128)
    per_page = rows_pad * page_size * 4 + 2 * 2 * page
    return max(1, min(max_pages, _TILE_VMEM_BYTES // per_page))


def _paged_kernel(bt_ref, pos_ref, q_ref, *refs,
                  max_pages: int, tile_pages: int, n_tiles: int,
                  page_size: int, chunk: int, group: int, scale: float,
                  quantized: bool, manual: bool):
    """Paged flash attention body, shared by decode and chunk prefill:
    one grid step walks a tile of ``tile_pages`` of a row's block table
    against R query rows.

    Only a row's live pages are read: those up to its last position,
    ``pos + chunk - 1``. A tile wholly past the last live page reads no
    page and does no arithmetic; the online-softmax state carries
    through it. Within the last live tile, a slot past the last live
    page may hold another page's or stale VMEM, so its values are masked
    as well as its probabilities (0 * NaN is NaN).

    The pages reach VMEM one of two ways, by the head dim:

    - ``manual`` (a head dim of whole 128-lane tiles): the pools stay in
      HBM and the kernel copies each live page by hand, its address read
      from the scalar-prefetched block table, into two VMEM buffers:
      while one tile is weighed, the next live tile (of this row and
      head, else the first tile of the next (row, head) in grid order) is
      in flight. Within the last live tile, only live pages are copied.
    - else (Mosaic slices an HBM page only in whole 128-lane tiles): each
      page slot of the tile is a block of its own, whose index map reads
      the block table. Past the last live page the map repeats the last
      live tile's pages, so the pipeline copies nothing new there.

    Query-row positions: row r belongs to chunk token r // group at
    absolute position pos_ref[b] + r // group. Decode is the R == group
    case (every row is the same single token at pos). Causality between
    chunk tokens falls out of the same mask: a chunk token never sees a
    younger sibling's freshly written slot.

    ``quantized`` adds per-token-head fp32 scale pages (1, ps) beside the
    K/V pages; dequant happens in VMEM, so int8 KV never materializes as
    fp32 in HBM."""
    n_pools = 4 if quantized else 2
    n_in = n_pools * (1 if manual else tile_pages)
    ins, (o_ref, m_s, l_s, acc_s, *bufs) = refs[:n_in], refs[n_in:]
    b, kv, t = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    R = q_ref.shape[2]

    def last_page(row):
        return jnp.minimum((pos_ref[row] + chunk - 1) // page_size,
                           max_pages - 1)

    if manual:
        # (HBM pool, VMEM buffer, semaphore): K's copies on one semaphore
        # of a buffer slot, V's on the other
        slot_ref, sems = bufs[-2:]
        pools = tuple(zip(ins, bufs[:n_pools], (0, 1, 0, 1)))
        n_rows, n_heads = pl.num_programs(0), pl.num_programs(1)

        def tile_copies(row, head, tile, slot):
            """(live, copies) for each page slot of one tile."""
            row_last = last_page(row)
            out = []
            for j in range(tile_pages):
                lp = tile * tile_pages + j
                page = bt_ref[row * max_pages + jnp.minimum(lp, max_pages - 1)]
                copies = [pltpu.make_async_copy(src.at[page, head],
                                                buf.at[slot, j],
                                                sems.at[slot, i])
                          for src, buf, i in pools]
                out.append((lp <= row_last, copies))
            return out

        def start(row, head, tile, slot):
            for live, copies in tile_copies(row, head, tile, slot):
                @pl.when(live)
                def _():
                    for c in copies:
                        c.start()

        @pl.when((b == 0) & (kv == 0) & (t == 0))
        def _first():                             # nothing prefetched it
            slot_ref[0] = 0
            start(b, kv, t, 0)

    @pl.when(t == 0)
    def _init():
        m_s[:] = jnp.full_like(m_s, NEG)
        l_s[:] = jnp.zeros_like(l_s)
        acc_s[:] = jnp.zeros_like(acc_s)

    last = last_page(b)

    @pl.when(t * tile_pages <= last)
    def _tile():
        if manual:
            slot = slot_ref[0]
            more = (t + 1) * tile_pages <= last
            head_next = kv + 1 < n_heads
            nxt_b = jnp.where(more | head_next, b, b + 1)
            nxt_kv = jnp.where(more, kv, jnp.where(head_next, kv + 1, 0))
            nxt_t = jnp.where(more, t + 1, 0)

            @pl.when(nxt_b < n_rows)
            def _prefetch():
                start(nxt_b, nxt_kv, nxt_t, 1 - slot)

            page_live = []
            for live, copies in tile_copies(b, kv, t, slot):
                @pl.when(live)
                def _():
                    for c in copies:
                        c.wait()
                page_live.append(live)

            def page(j, i):                       # K, V, K scale, V scale
                buf = bufs[i]
                return buf[slot, j] if i < 2 else buf[slot, j, :, :page_size]
        else:
            page_live = [t * tile_pages + j <= last
                         for j in range(tile_pages)]

            def page(j, i):
                return ins[i * tile_pages + j][0, 0]

        q = q_ref[0, 0].astype(jnp.float32)       # (R, hd)
        qpos = pos_ref[b] + jax.lax.broadcasted_iota(
            jnp.int32, (R, page_size), 0) // group
        col = jax.lax.broadcasted_iota(jnp.int32, (R, page_size), 1)
        scores, valid = [], []
        for j in range(tile_pages):
            k = page(j, 0).astype(jnp.float32)   # (ps, hd)
            s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale
            if quantized:
                # per-token scales arrive lane-major (1, ps): scaling the
                # scores and probabilities is exact algebra for (k * s_k)
                # and (v * s_v), with no sublane<->lane transpose
                s = s * page(j, 2)
            ok = (t * tile_pages + j) * page_size + col <= qpos
            scores.append(jnp.where(ok, s, NEG))
            valid.append(ok)

        m_prev = m_s[:]
        m_new = m_prev
        for s in scores:
            m_new = jnp.maximum(m_new, jnp.max(s, axis=-1, keepdims=True))
        r = jnp.exp(m_prev - m_new)
        l_new = l_s[:] * r
        acc = acc_s[:] * r
        for j, (s, ok) in enumerate(zip(scores, valid)):
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_new += jnp.sum(p, axis=-1, keepdims=True)
            if quantized:
                p = jnp.where(ok, p * page(j, 3), 0.0)
            v = jnp.where(page_live[j], page(j, 1).astype(jnp.float32),
                          0.0)
            acc += jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_s[:] = m_new
        l_s[:] = l_new
        acc_s[:] = acc
        if manual:
            slot_ref[0] = 1 - slot

    @pl.when(t == n_tiles - 1)
    def _finalize():
        o_ref[0, 0] = (acc_s[:] / jnp.maximum(l_s[:], 1e-30)).astype(o_ref.dtype)


def paged_decode_attn_pallas(q, k_pages, v_pages, block_tables, pos, *,
                             k_scales=None, v_scales=None,
                             interpret: Optional[bool] = None):
    """Paged GQA flash-decode. q: (B, H, hd); k_pages, v_pages:
    (P, KV, ps, hd) head-major page pools; block_tables: (B, MP) int32
    physical page per logical page; pos: (B,) int32 per-row positions.

    ``k_scales``/``v_scales`` (P, KV, 1, ps) fp32 activate the int8 path:
    pages are dequantized in VMEM (scale pages are copied beside their
    K/V pages through the same block table), never as fp32 in HBM.

    Returns (B, H, hd) fp32. See ref.paged_decode_attn_ref for the page
    semantics (entries past pos may alias a trash page — never read)."""
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
    tp = _pages_per_tile(R, ps, hd, k_pages.dtype.itemsize, quant, MP)
    n_tiles = -(-MP // tp)
    # Mosaic slices an HBM page by hand only in whole 128-lane tiles
    manual = hd % 128 == 0
    # group query rows by kv-head: row r = chunk token r // G, head r % G
    qr = (q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4)
          .reshape(B, KV, R, hd))
    bt_flat = jnp.asarray(block_tables, jnp.int32).reshape(B * MP)
    pools = [k_pages, v_pages] + ([k_scales, v_scales] if quant else [])

    def row_head(b, kv, t, bt_ref, pos_ref):
        return (b, kv, 0, 0)

    in_specs = [pl.BlockSpec((1, 1, R, hd), row_head)]
    operands = [bt_flat, jnp.asarray(pos, jnp.int32), qr]
    scratch = [pltpu.VMEM((R, 1), jnp.float32),
               pltpu.VMEM((R, 1), jnp.float32),
               pltpu.VMEM((R, hd), jnp.float32)]
    if manual:
        # the pools stay in HBM: the kernel copies each live page itself
        in_specs += [pl.BlockSpec(memory_space=pltpu.HBM)] * len(pools)
        scratch += [pltpu.VMEM((2, tp, ps, hd), k_pages.dtype),
                    pltpu.VMEM((2, tp, ps, hd), v_pages.dtype)]
        if quant:
            # a copy out of an f32 HBM array spans whole 128-lane tiles:
            # pad the (1, ps) scale rows to them
            lanes = -(-ps // 128) * 128
            pools[2:] = [jnp.pad(x, ((0, 0),) * 3 + ((0, lanes - ps),))
                         for x in pools[2:]]
            scratch += [pltpu.VMEM((2, tp, 1, lanes), jnp.float32)] * 2
        operands += pools
        # the buffer slot in use, and a DMA semaphore per (slot, K or V)
        scratch += [pltpu.SMEM((1,), jnp.int32),
                    pltpu.SemaphoreType.DMA((2, 2))]
    else:
        def page_map(j):
            # page slot j of tile t; past the last live page, the last
            # live tile's pages again, so the pipeline copies nothing
            def index(b, kv, t, bt_ref, pos_ref):
                last = jnp.minimum((pos_ref[b] + C - 1) // ps, MP - 1)
                lp = jnp.minimum(jnp.minimum(t, last // tp) * tp + j, last)
                return (bt_ref[b * MP + lp], kv, 0, 0)
            return index

        # head-major pages make each block's last two dims whole array
        # dims, (ps, hd) values and (1, ps) scales, as the TPU tiling needs
        for x in pools:
            in_specs += [pl.BlockSpec((1, 1) + x.shape[2:], page_map(j))
                         for j in range(tp)]
            operands += [x] * tp

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, KV, n_tiles),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, R, hd), row_head),
        scratch_shapes=scratch,
    )
    kern = functools.partial(_paged_kernel, max_pages=MP, tile_pages=tp,
                             n_tiles=n_tiles, page_size=ps, chunk=C,
                             group=G, scale=hd ** -0.5, quantized=quant,
                             manual=manual)
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, R, hd), jnp.float32),
        # sequential grid: a step may prefetch the next step's pages
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=interpret,
    )(*operands)
    return (out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4)
            .reshape(B, C, H, hd))
