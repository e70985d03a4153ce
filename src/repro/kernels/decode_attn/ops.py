"""Public wrappers for the flash-decode attention kernels (contiguous
and paged). Both entry points resolve interpret mode themselves, so the
explicit pass-through here is belt-and-braces for readability."""
from __future__ import annotations

from repro.kernels import interpret_mode
from repro.kernels.decode_attn.kernel import (
    decode_attn_pallas,
    paged_decode_attn_pallas,
    paged_prefill_attn_pallas,
)


def decode_attn(q, k, v, pos, *, window: int = 0, ring: bool = False,
                tile_s: int = 512):
    """Flash GQA decode: q (B,H,hd) vs cache (B,S,KV,hd). See kernel.py."""
    return decode_attn_pallas(q, k, v, pos, window=window, ring=ring,
                              tile_s=tile_s, interpret=interpret_mode())


def paged_decode_attn(q, k_pages, v_pages, block_tables, pos, *,
                      k_scales=None, v_scales=None):
    """Paged flash GQA decode: q (B,H,hd) vs head-major page pool
    (P,KV,ps,hd) addressed through (B,MP) block tables at per-row
    positions (B,). Optional (P,KV,1,ps) fp32 scales switch the pool to
    int8 with in-kernel dequant. See kernel.py / ref.py for the page
    semantics."""
    return paged_decode_attn_pallas(q, k_pages, v_pages, block_tables, pos,
                                    k_scales=k_scales, v_scales=v_scales,
                                    interpret=interpret_mode())


def paged_prefill_attn(q, k_pages, v_pages, block_tables, pos0, *,
                       k_scales=None, v_scales=None):
    """Paged chunk-prefill GQA attention: q (B,C,H,hd) chunk tokens
    attend causally vs the head-major page pool (P,KV,ps,hd) through
    (B,MP) block tables starting at per-row positions pos0 (B,). Optional
    (P,KV,1,ps) fp32 scales switch the pool to int8 with in-kernel
    dequant."""
    return paged_prefill_attn_pallas(q, k_pages, v_pages, block_tables, pos0,
                                     k_scales=k_scales, v_scales=v_scales,
                                     interpret=interpret_mode())
