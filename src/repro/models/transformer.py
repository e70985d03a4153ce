"""Model assembly: heterogeneous layer stacks via cycle-scan.

The layer pattern (e.g. gemma3's 5×local+1×global, recurrentgemma's
rec/rec/local) repeats K = L // len(pattern) times with R = L % len(pattern)
remainder layers. Parameters and caches are **stacked over the K cycles**
(one stacked pytree per pattern position) and the stack is applied with a
single ``lax.scan`` — compile time and HLO size stay flat in depth
(80-layer internvl2 lowers as fast as 12-layer whisper), which also keeps
the roofline HLO readable.

Public API (cfg is static / hashable):
    init_params(rng, cfg)                         -> params pytree
    train_logits(params, cfg, tokens, frontend)   -> (logits, aux_loss)
    init_cache(cfg, batch, max_seq)               -> cache pytree
    prefill(params, cfg, tokens, cache, frontend) -> (last_logits, cache)
    decode_step(params, cfg, token, pos, cache)   -> (logits, cache)
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as attn
from repro.models import mlp as mlp_lib
from repro.models import moe as moe_lib
from repro.models import rglru as rglru_lib
from repro.models import rwkv6 as rwkv6_lib
from repro.models.layers import dense_init, embed, rms_norm, sinusoidal_positions, unembed


# ------------------------------------------------------------------ init

def _init_block(rng, cfg: ModelConfig, block_type: str, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 4)
    p = {"ln1": jnp.zeros((d,), jnp.float32), "ln2": jnp.zeros((d,), jnp.float32)}
    if block_type in ("global", "local"):
        p["attn"] = attn.init_attn(ks[0], d, cfg.num_heads, cfg.num_kv_heads,
                                   hd, cfg.qkv_bias, dtype)
    elif block_type == "recurrent":
        p["rec"] = rglru_lib.init_rglru(ks[0], d, dtype)
    elif block_type == "rwkv6":
        p["mix"] = rwkv6_lib.init_rwkv6(ks[0], d, cfg.d_ff, cfg.num_heads, hd, dtype)
        return p  # rwkv6 block carries its own channel-mix FFN
    else:
        raise ValueError(block_type)
    if cfg.is_moe:
        p["ffn"] = moe_lib.init_moe(ks[1], d, cfg.d_ff, cfg.num_experts, dtype)
    else:
        p["ffn"] = mlp_lib.init_swiglu(ks[1], d, cfg.d_ff, dtype)
    if cfg.is_encoder_decoder:
        p["lnx"] = jnp.zeros((d,), jnp.float32)
        p["xattn"] = attn.init_cross_attn(ks[2], d, cfg.num_heads,
                                          cfg.num_kv_heads, hd, dtype)
    return p


def _init_encoder_block(rng, cfg: ModelConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    ks = jax.random.split(rng, 2)
    return {
        "ln1": jnp.zeros((d,), jnp.float32),
        "ln2": jnp.zeros((d,), jnp.float32),
        "attn": attn.init_attn(ks[0], d, cfg.num_heads, cfg.num_heads, hd, False, dtype),
        "ffn": mlp_lib.init_gelu_mlp(ks[1], d, cfg.d_ff, dtype),
    }


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def init_params(rng, cfg: ModelConfig):
    dtype = jnp.dtype(cfg.dtype)
    pattern = cfg.layer_pattern
    P = len(pattern)
    K, R = cfg.num_layers // P, cfg.num_layers % P
    keys = jax.random.split(rng, cfg.num_layers + cfg.encoder_layers + 3)

    params = {
        "embed": dense_init(keys[0], (cfg.vocab_size, cfg.d_model),
                            scale=0.02, dtype=dtype),
        "final_norm": jnp.zeros((cfg.d_model,), jnp.float32),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = dense_init(keys[1], (cfg.vocab_size, cfg.d_model),
                                       scale=0.02, dtype=dtype)

    blocks = [_init_block(keys[2 + i], cfg, pattern[i % P], dtype)
              for i in range(cfg.num_layers)]
    if K > 0:
        params["stack"] = tuple(_stack(blocks[j::P][:K]) for j in range(P))
    else:
        params["stack"] = ()
    params["rem"] = tuple(blocks[K * P:])

    if cfg.is_encoder_decoder:
        ekeys = keys[2 + cfg.num_layers:]
        enc_blocks = [_init_encoder_block(ekeys[i], cfg, dtype)
                      for i in range(cfg.encoder_layers)]
        params["encoder"] = _stack(enc_blocks)
        params["enc_norm"] = jnp.zeros((cfg.d_model,), jnp.float32)
    return params


# ------------------------------------------------------------------ blocks

# Megatron-style sequence parallelism (§Perf C): the launcher installs the
# data-parallel axis names; blocks then constrain the residual stream to
# (batch=dp, seq="model") so GSPMD lowers the TP partial-sums as
# reduce-scatter + all-gather instead of full all-reduces.
_SP_DP_AXES = None


def set_sequence_parallel_axes(dp_axes) -> None:
    global _SP_DP_AXES
    _SP_DP_AXES = tuple(dp_axes) if dp_axes else None


def _sp_constrain(x, cfg: ModelConfig):
    if not cfg.seq_parallel or _SP_DP_AXES is None or x.ndim != 3:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(
            x, P(_SP_DP_AXES, "model", None))
    except Exception:
        return x


def _sp_gather(x, cfg: ModelConfig):
    """§Perf C it.3 — REFUTED, kept for the record: forcing the classic
    Megatron AG(x)→matmul→RS dataflow regressed collectives 0.43s→1.24s on
    gemma3 prefill. With few batch rows per chip (2×32k×2560 ≈ 335 MB vs
    3 FFN weight shards ≈ 157 MB/layer), GSPMD's weight-gather choice is
    the cheaper side of the trade — the textbook SP dataflow assumes
    activations ≪ weights, which long-context prefill inverts."""
    if not cfg.seq_parallel or _SP_DP_AXES is None or x.ndim != 3:
        return x
    from jax.sharding import PartitionSpec as P
    try:
        return jax.lax.with_sharding_constraint(x, P(_SP_DP_AXES, None, None))
    except Exception:
        return x


def _use_halo(cfg: ModelConfig, seq_len: int) -> bool:
    """Halo-exchange local attention (§Perf C it.2): seq-sharded sliding
    window with a neighbour halo instead of a full-sequence all-gather."""
    if not cfg.seq_parallel or attn._HALO_MESH is None:
        return False
    m = attn._HALO_MESH.shape.get("model", 1)
    return attn.halo_attn_available(seq_len, cfg.window_size, m)


def _window_of(cfg: ModelConfig, bt: str) -> int:
    return cfg.window_size if bt == "local" else 0


def _ffn_apply(p, cfg: ModelConfig, x):
    if cfg.is_moe:
        if cfg.moe_impl == "expert_parallel":
            seq_ok = (cfg.seq_parallel and attn._HALO_MESH is not None
                      and x.shape[1] % attn._HALO_MESH.shape.get("model", 1) == 0)
            return moe_lib.moe_ffn_expert_parallel(
                p["ffn"], x, num_experts=cfg.num_experts,
                experts_per_tok=cfg.experts_per_tok,
                capacity_factor=max(cfg.moe_capacity_factor, 1.25),
                seq_sharded=seq_ok)
        return moe_lib.moe_ffn(p["ffn"], x, num_experts=cfg.num_experts,
                               experts_per_tok=cfg.experts_per_tok,
                               capacity_factor=cfg.moe_capacity_factor)
    return mlp_lib.swiglu(p["ffn"], x), jnp.float32(0.0)


def _block_forward(p, cfg: ModelConfig, bt: str, x, positions, enc_kv=None):
    """Full-sequence (train) block, no cache. Returns (x, aux)."""
    aux = jnp.float32(0.0)
    x = _sp_constrain(x, cfg)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if bt in ("global", "local"):
        if bt == "local" and _use_halo(cfg, x.shape[1]):
            y = attn.attn_forward_halo(
                p["attn"], h, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
                window=cfg.window_size, rope_theta=cfg.rope_theta,
                use_rope=cfg.use_rope)
        else:
            y = attn.attn_forward(p["attn"], h, positions,
                                  num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                                  head_dim=cfg.resolved_head_dim,
                                  window=_window_of(cfg, bt),
                                  rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
        x = x + y
    elif bt == "recurrent":
        y, _ = rglru_lib.rglru_forward(p["rec"], h)
        x = x + y
    elif bt == "rwkv6":
        st = rwkv6_lib.init_rwkv6_state(x.shape[0], cfg.d_model, cfg.num_heads,
                                        cfg.resolved_head_dim, x.dtype)
        y, _ = rwkv6_lib.time_mix(p["mix"], h, st, num_heads=cfg.num_heads,
                                  head_dim=cfg.resolved_head_dim)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, _ = rwkv6_lib.channel_mix(p["mix"], h2, st)
        return x + y2, aux
    if cfg.is_encoder_decoder and enc_kv is not None:
        hx = rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn.cross_attn(p["xattn"], hx, enc_kv[0], enc_kv[1],
                                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                                head_dim=cfg.resolved_head_dim)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    y2, aux = _ffn_apply(p, cfg, h2)
    return x + y2, aux


def _block_prefill(p, cfg: ModelConfig, bt: str, x, positions, cache, enc_kv=None):
    aux = jnp.float32(0.0)
    x = _sp_constrain(x, cfg)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if bt in ("global", "local"):
        if bt == "local" and _use_halo(cfg, x.shape[1]):
            y, k, v = attn.attn_forward_halo(
                p["attn"], h, num_heads=cfg.num_heads,
                num_kv_heads=cfg.num_kv_heads, head_dim=cfg.resolved_head_dim,
                window=cfg.window_size, rope_theta=cfg.rope_theta,
                use_rope=cfg.use_rope, return_kv=True)
            new_cache = attn.write_ring_from_kv(cache, k, v, positions)
        else:
            y, new_cache = attn.attn_prefill(
                p["attn"], h, positions, cache,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, window=_window_of(cfg, bt),
                rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
        x = x + y
    elif bt == "recurrent":
        y, new_cache = rglru_lib.rglru_forward(p["rec"], h, cache)
        x = x + y
    elif bt == "rwkv6":
        y, tm = rwkv6_lib.time_mix(p["mix"], h, cache, num_heads=cfg.num_heads,
                                   head_dim=cfg.resolved_head_dim)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, cm = rwkv6_lib.channel_mix(p["mix"], h2, cache)
        new_cache = {**tm, **cm}
        return x + y2, new_cache, aux
    if cfg.is_encoder_decoder and enc_kv is not None:
        hx = rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn.cross_attn(p["xattn"], hx, enc_kv[0], enc_kv[1],
                                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                                head_dim=cfg.resolved_head_dim)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    y2, aux = _ffn_apply(p, cfg, h2)
    return x + y2, new_cache, aux


def _block_decode(p, cfg: ModelConfig, bt: str, x, pos, cache, enc_kv=None,
                  block_tables=None, write_pages=None):
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if bt == "global" and block_tables is not None:
        y, new_cache = attn.attn_decode_paged(
            p["attn"], h, pos, cache, block_tables, write_pages,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim,
            rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
        x = x + y
    elif bt in ("global", "local"):
        y, new_cache = attn.attn_decode(
            p["attn"], h, pos, cache,
            num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
            head_dim=cfg.resolved_head_dim, window=_window_of(cfg, bt),
            rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
        x = x + y
    elif bt == "recurrent":
        y, new_cache = rglru_lib.rglru_step(p["rec"], h, cache)
        x = x + y
    elif bt == "rwkv6":
        y, tm = rwkv6_lib.time_mix_step(p["mix"], h, cache, num_heads=cfg.num_heads,
                                        head_dim=cfg.resolved_head_dim)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, cm = rwkv6_lib.channel_mix_step(p["mix"], h2, cache)
        return x + y2, {**tm, **cm}
    if cfg.is_encoder_decoder and enc_kv is not None:
        hx = rms_norm(x, p["lnx"], cfg.norm_eps)
        x = x + attn.cross_attn(p["xattn"], hx, enc_kv[0], enc_kv[1],
                                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                                head_dim=cfg.resolved_head_dim)
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    y2, _ = _ffn_apply(p, cfg, h2)
    return x + y2, new_cache



def _scan_maybe(fn, carry, xs, unroll: bool):
    """lax.scan, or an unrolled Python loop when cfg.unroll is set (the
    dry-run uses unrolled stacks so cost_analysis sees every layer)."""
    if not unroll:
        return jax.lax.scan(fn, carry, xs)
    K = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(K):
        sl = jax.tree.map(lambda a: a[i], xs)
        carry, y = fn(carry, sl)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


# ------------------------------------------------------------------ encoder

def run_encoder(params, cfg: ModelConfig, frames):
    """Whisper-style bidirectional encoder over stub frame embeddings."""
    S = frames.shape[1]
    x = frames + sinusoidal_positions(S, cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(S)

    def body(x, blk):
        h = rms_norm(x, blk["ln1"], cfg.norm_eps)
        # bidirectional: reuse attn_forward with an all-true mask via window=0
        # and positions trick — simplest is direct call with no causal mask:
        y = _encoder_attn(blk["attn"], h, cfg)
        x = x + y
        h2 = rms_norm(x, blk["ln2"], cfg.norm_eps)
        x = x + mlp_lib.gelu_mlp(blk["ffn"], h2)
        return x, None

    x, _ = _scan_maybe(body, x, params["encoder"], cfg.unroll)
    return rms_norm(x, params["enc_norm"], cfg.norm_eps)


def _encoder_attn(p, x, cfg: ModelConfig):
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    H = cfg.num_heads
    q = (x @ p["wq"]).reshape(B, S, H, 1, hd)
    k = (x @ p["wk"]).reshape(B, S, H, hd)
    v = (x @ p["wv"]).reshape(B, S, H, hd)
    mask = jnp.ones((1, 1, 1, 1, S), bool)
    out = attn._attend(q.reshape(B, S, H, 1, hd), k, v, mask)
    return out.reshape(B, S, H * hd) @ p["wo"]


# ------------------------------------------------------------------ public

def _apply_stack(params, cfg: ModelConfig, x, fn_cycle, fn_rem):
    """Run the cycle-scan + remainder. fn_cycle(x, stacked_slices)->(x, ys),
    fn_rem(x, rem_params, idx)->x."""
    pattern = cfg.layer_pattern
    P = len(pattern)
    K = cfg.num_layers // P
    ys = None
    if K > 0:
        x, ys = _scan_maybe(fn_cycle, x, params["stack"], cfg.unroll)
    for j, bp in enumerate(params["rem"]):
        x = fn_rem(x, bp, j)
    return x, ys


def _logits(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = params["embed"] if cfg.tie_embeddings else params["unembed"]
    return unembed(x, table)


def _embed_in(params, cfg: ModelConfig, tokens, positions=None):
    x = embed(tokens, params["embed"])
    if not cfg.use_rope and not cfg.is_encoder_decoder:
        pass  # rwkv6: no positional signal needed
    if cfg.is_encoder_decoder:
        S = tokens.shape[1]
        start = 0 if positions is None else positions
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
    return x


def train_logits(params, cfg: ModelConfig, tokens, frontend=None):
    """Teacher-forced full-sequence logits. tokens: (B, S) int32.
    frontend: stub embeddings (B, F, d) for vlm/audio archs.
    Returns (logits (B, S_text, V), aux_loss)."""
    B, S = tokens.shape
    pattern = cfg.layer_pattern
    enc_kv = None
    x = embed(tokens, params["embed"])
    n_prefix = 0

    if cfg.is_encoder_decoder:
        assert frontend is not None, "enc-dec arch needs frontend frames"
        enc_out = run_encoder(params, cfg, frontend)
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
    elif cfg.frontend is not None and frontend is not None:
        # VLM: prepend patch embeddings to the token stream
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        n_prefix = frontend.shape[1]

    Sx = x.shape[1]
    positions = jnp.arange(Sx)
    aux_total = jnp.float32(0.0)

    if cfg.is_encoder_decoder:
        # precompute per-layer cross K/V lazily inside each block instead:
        # simplest faithful version recomputes K,V from enc_out per layer.
        def fn_cycle(x, slices):
            aux_c = jnp.float32(0.0)
            for j, bt in enumerate(pattern):
                ekv = attn.cross_attn_kv(slices[j]["xattn"], enc_out,
                                         cfg.num_kv_heads, cfg.resolved_head_dim)
                x, aux = _block_forward(slices[j], cfg, bt, x, positions, ekv)
                aux_c += aux
            return x, aux_c

        def fn_rem(x, bp, j):
            nonlocal aux_total
            bt = pattern[(cfg.num_layers // len(pattern)) * len(pattern) + j] \
                if False else pattern[j % len(pattern)]
            ekv = attn.cross_attn_kv(bp["xattn"], enc_out,
                                     cfg.num_kv_heads, cfg.resolved_head_dim)
            x, aux = _block_forward(bp, cfg, bt, x, positions, ekv)
            aux_total += aux
            return x
    else:
        def fn_cycle(x, slices):
            aux_c = jnp.float32(0.0)
            for j, bt in enumerate(pattern):
                x, aux = _block_forward(slices[j], cfg, bt, x, positions)
                aux_c += aux
            return x, aux_c

        def fn_rem(x, bp, j):
            nonlocal aux_total
            x, aux = _block_forward(bp, cfg, pattern[j % len(pattern)], x, positions)
            aux_total += aux
            return x

    x, ys = _apply_stack(params, cfg, x, fn_cycle, fn_rem)
    if ys is not None:
        aux_total = aux_total + jnp.sum(ys)
    if n_prefix:
        x = x[:, n_prefix:]
    return _logits(params, cfg, x), aux_total


def init_cache(cfg: ModelConfig, batch: int, max_seq: int):
    """Cache pytree matching the stacked-params layout."""
    dtype = jnp.dtype(cfg.dtype)
    pattern = cfg.layer_pattern
    P = len(pattern)
    K, R = cfg.num_layers // P, cfg.num_layers % P
    hd = cfg.resolved_head_dim

    quant = cfg.kv_cache_dtype == "int8"

    def one(bt):
        if bt == "global":
            return attn.init_full_cache(batch, max_seq, cfg.num_kv_heads, hd,
                                        dtype, quantized=quant)
        if bt == "local":
            W = min(cfg.window_size, max_seq)
            return attn.init_ring_cache(batch, W, cfg.num_kv_heads, hd,
                                        dtype, quantized=quant)
        if bt == "recurrent":
            return rglru_lib.init_rglru_state(batch, cfg.d_model, dtype)
        if bt == "rwkv6":
            return rwkv6_lib.init_rwkv6_state(batch, cfg.d_model, cfg.num_heads, hd, dtype)
        raise ValueError(bt)

    def stacked(bt):
        c = one(bt)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (K,) + a.shape).copy(), c) \
            if K > 0 else c

    cache = {
        "stack": tuple(stacked(pattern[j]) for j in range(P)) if K > 0 else (),
        "rem": tuple(one(pattern[j % P]) for j in range(R)),
    }
    if cfg.is_encoder_decoder:
        # cross-attn K/V per decoder layer, filled at prefill
        xshape = (batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd)
        cache["xkv_stack"] = tuple(
            {"k": jnp.zeros((K,) + xshape, dtype), "v": jnp.zeros((K,) + xshape, dtype)}
            for _ in range(P)) if K > 0 else ()
        cache["xkv_rem"] = tuple({"k": jnp.zeros(xshape, dtype),
                                  "v": jnp.zeros(xshape, dtype)} for _ in range(R))
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, max_seq: int):
    """Paged variant of :func:`init_cache` (DESIGN.md §5).

    Global-attention layers share a page pool — their leaves get the
    head-major shape (num_pages + 1, KV, page_size, hd), where physical
    page ``num_pages`` is the shared *trash* page that unowned block-table
    entries alias. With ``cfg.kv_cache_dtype == "int8"`` the K/V leaves
    are int8 and per-token-head fp32 scale leaves ``k_s``/``v_s`` of shape
    (num_pages + 1, KV, 1, page_size) ride alongside — page-granular, so
    they follow the same block table through COW copies, prefix sharing,
    and the Pallas kernels' scalar-prefetched index maps (DESIGN.md
    §13). Every other leaf family (sliding-window ring caches,
    recurrent / RWKV-6 state, cross-attention K/V) keeps its per-row
    layout: those states are O(window) or O(1) in sequence, so paging
    them would buy nothing. One block table therefore addresses every
    global layer — a logical page maps to the same physical index in
    each layer's pool."""
    dtype = jnp.dtype(cfg.dtype)
    pattern = cfg.layer_pattern
    P = len(pattern)
    K, R = cfg.num_layers // P, cfg.num_layers % P
    hd = cfg.resolved_head_dim
    quant = cfg.kv_cache_dtype == "int8"

    def one(bt):
        if bt == "global":
            return attn.init_paged_kv(num_pages + 1, page_size,
                                      cfg.num_kv_heads, hd, dtype,
                                      quantized=quant)
        if bt == "local":
            W = min(cfg.window_size, max_seq)
            return attn.init_ring_cache(batch, W, cfg.num_kv_heads, hd,
                                        dtype, quantized=quant)
        if bt == "recurrent":
            return rglru_lib.init_rglru_state(batch, cfg.d_model, dtype)
        if bt == "rwkv6":
            return rwkv6_lib.init_rwkv6_state(batch, cfg.d_model,
                                              cfg.num_heads, hd, dtype)
        raise ValueError(bt)

    def stacked(bt):
        c = one(bt)
        return jax.tree.map(lambda a: jnp.broadcast_to(a, (K,) + a.shape).copy(), c) \
            if K > 0 else c

    cache = {
        "stack": tuple(stacked(pattern[j]) for j in range(P)) if K > 0 else (),
        "rem": tuple(one(pattern[j % P]) for j in range(R)),
    }
    if cfg.is_encoder_decoder:
        xshape = (batch, cfg.encoder_seq_len, cfg.num_kv_heads, hd)
        cache["xkv_stack"] = tuple(
            {"k": jnp.zeros((K,) + xshape, dtype), "v": jnp.zeros((K,) + xshape, dtype)}
            for _ in range(P)) if K > 0 else ()
        cache["xkv_rem"] = tuple({"k": jnp.zeros(xshape, dtype),
                                  "v": jnp.zeros(xshape, dtype)} for _ in range(R))
    return cache


def prefill(params, cfg: ModelConfig, tokens, cache, frontend=None):
    """Process the prompt, fill the cache. tokens: (B, S_prompt).
    Returns (logits at last position (B, V), cache)."""
    B, S = tokens.shape
    pattern = cfg.layer_pattern
    P = len(pattern)
    x = embed(tokens, params["embed"])
    n_prefix = 0

    enc_out = None
    if cfg.is_encoder_decoder:
        assert frontend is not None
        enc_out = run_encoder(params, cfg, frontend)
        x = x + sinusoidal_positions(S, cfg.d_model).astype(x.dtype)
    elif cfg.frontend is not None and frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
        n_prefix = frontend.shape[1]

    positions = jnp.arange(x.shape[1])

    def fn_cycle(x, slices):
        pslices, cslices = slices
        newc = []
        xkv = []
        for j, bt in enumerate(pattern):
            ekv = None
            if cfg.is_encoder_decoder:
                ekv = attn.cross_attn_kv(pslices[j]["xattn"], enc_out,
                                         cfg.num_kv_heads, cfg.resolved_head_dim)
                xkv.append({"k": ekv[0], "v": ekv[1]})
            x, c, _ = _block_prefill(pslices[j], cfg, bt, x, positions, cslices[j], ekv)
            newc.append(c)
        return x, (tuple(newc), tuple(xkv))

    new_rem = []
    new_xkv_rem = []

    def fn_rem(x, bp_c, j):
        bp, c = bp_c
        bt = pattern[j % P]
        ekv = None
        if cfg.is_encoder_decoder:
            ekv = attn.cross_attn_kv(bp["xattn"], enc_out,
                                     cfg.num_kv_heads, cfg.resolved_head_dim)
            new_xkv_rem.append({"k": ekv[0], "v": ekv[1]})
        x, c2, _ = _block_prefill(bp, cfg, bt, x, positions, c, ekv)
        new_rem.append(c2)
        return x

    K = cfg.num_layers // P
    ys = None
    if K > 0:
        x, ys = _scan_maybe(fn_cycle, x, (params["stack"], cache["stack"]), cfg.unroll)
    for j, bp in enumerate(params["rem"]):
        x = fn_rem(x, (bp, cache["rem"][j]), j)

    new_cache = {
        "stack": ys[0] if ys is not None else (),
        "rem": tuple(new_rem),
    }
    if cfg.is_encoder_decoder:
        new_cache["xkv_stack"] = ys[1] if ys is not None else ()
        new_cache["xkv_rem"] = tuple(new_xkv_rem)
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, new_cache


def _block_prefill_chunk(p, cfg: ModelConfig, bt: str, x, pos0, centry,
                         aentry, *, hist_len: int, block_tables,
                         chunk_pages):
    """One block over a (B, C) prompt chunk. ``centry`` is the block's
    main-cache entry (contiguous cache, or the paged pool); ``aentry``
    is the batch-1 aux entry holding the per-row families' state in
    paged mode (None in contiguous mode, where ``centry`` holds it).
    Returns (x, new_centry, new_aentry)."""
    paged = block_tables is not None
    own = aentry if (paged and bt != "global") else centry
    new_c, new_a = centry, aentry
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if bt in ("global", "local"):
        if paged and bt == "global":
            y, new_c = attn.attn_prefill_chunk_paged(
                p["attn"], h, pos0, centry, block_tables, chunk_pages,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, rope_theta=cfg.rope_theta,
                use_rope=cfg.use_rope)
        else:
            y, s_new = attn.attn_prefill_chunk(
                p["attn"], h, pos0, own, hist_len=hist_len,
                num_heads=cfg.num_heads, num_kv_heads=cfg.num_kv_heads,
                head_dim=cfg.resolved_head_dim, window=_window_of(cfg, bt),
                rope_theta=cfg.rope_theta, use_rope=cfg.use_rope)
            if paged:
                new_a = s_new
            else:
                new_c = s_new
        x = x + y
    elif bt == "recurrent":
        y, s_new = rglru_lib.rglru_forward(p["rec"], h, own)
        x = x + y
        if paged:
            new_a = s_new
        else:
            new_c = s_new
    elif bt == "rwkv6":
        y, tm = rwkv6_lib.time_mix(p["mix"], h, own, num_heads=cfg.num_heads,
                                   head_dim=cfg.resolved_head_dim)
        x = x + y
        h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
        y2, cm = rwkv6_lib.channel_mix(p["mix"], h2, own)
        s_new = {**tm, **cm}
        if paged:
            new_a = s_new
        else:
            new_c = s_new
        return x + y2, new_c, new_a
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    y2, _ = _ffn_apply(p, cfg, h2)
    return x + y2, new_c, new_a


def prefill_chunk(params, cfg: ModelConfig, tokens, pos0, hist_len: int,
                  cache, block_tables=None, chunk_pages=None, aux=None):
    """Advance the caches over one (B, C) prompt chunk whose first token
    sits at per-row absolute position ``pos0`` (DESIGN.md §6).

    Contiguous mode (``block_tables`` None): ``cache`` is a batch-B
    cache already holding each row's first ``pos0`` tokens; ``hist_len``
    is the static history slice length for full-attention layers
    (callers pass the exact filled length so the key sequence stays
    zero-gap — the bitwise-equality precondition).

    Paged mode: ``cache`` is the paged pool; global layers write the
    chunk's K/V straight into allocator-owned pages (``chunk_pages``,
    (B, C)) and attend through ``block_tables`` (B, MP); the per-row
    families (ring / recurrent / rwkv6) thread their state through the
    batch-1 ``aux`` cache, installed into the row slots when prefill
    completes. Because attention validity is purely positional
    (kv_pos <= q_pos through the block table), the FIRST chunk may start
    at a nonzero ``pos0``: the radix prefix cache (DESIGN.md §7) aliases
    already-written pages into the block table and resumes prefill at
    the cached extent — the earlier pages are attended, never recomputed.
    For all-'global' patterns this is bitwise-equal to prefilling from
    token 0; per-row aux families cannot be resumed this way, which is
    why the prefix cache requires an all-global pattern. Encoder-decoder
    and frontend-prefixed models are not supported (callers fall back to
    one-shot prefill).

    Returns (last-position logits (B, V), new_cache, new_aux)."""
    if cfg.is_encoder_decoder:
        raise ValueError("chunked prefill does not support encoder-decoder "
                         "models (use one-shot prefill)")
    paged = block_tables is not None
    pattern = cfg.layer_pattern
    P = len(pattern)
    x = embed(tokens, params["embed"])
    pos0 = jnp.asarray(pos0)

    def fn_cycle(x, slices):
        if paged:
            pslices, cslices, aslices = slices
        else:
            pslices, cslices = slices
            aslices = (None,) * P
        newc, newa = [], []
        for j, bt in enumerate(pattern):
            x, c, a = _block_prefill_chunk(
                pslices[j], cfg, bt, x, pos0, cslices[j], aslices[j],
                hist_len=hist_len, block_tables=block_tables,
                chunk_pages=chunk_pages)
            newc.append(c)
            newa.append(a)
        return x, (tuple(newc), tuple(newa)) if paged else tuple(newc)

    K = cfg.num_layers // P
    ys = None
    if K > 0:
        xs = (params["stack"], cache["stack"], aux["stack"]) if paged \
            else (params["stack"], cache["stack"])
        x, ys = _scan_maybe(fn_cycle, x, xs, cfg.unroll)

    new_rem, new_arem = [], []
    for j, bp in enumerate(params["rem"]):
        bt = pattern[j % P]
        aentry = aux["rem"][j] if paged else None
        x, c, a = _block_prefill_chunk(
            bp, cfg, bt, x, pos0, cache["rem"][j], aentry,
            hist_len=hist_len, block_tables=block_tables,
            chunk_pages=chunk_pages)
        new_rem.append(c)
        new_arem.append(a)

    if paged:
        new_cache = {"stack": ys[0] if ys is not None else (),
                     "rem": tuple(new_rem)}
        new_aux = {"stack": ys[1] if ys is not None else (),
                   "rem": tuple(new_arem)}
    else:
        new_cache = {"stack": ys if ys is not None else (),
                     "rem": tuple(new_rem)}
        new_aux = None
    logits = _logits(params, cfg, x[:, -1:])[:, 0]
    return logits, new_cache, new_aux


def decode_step(params, cfg: ModelConfig, token, pos, cache, block_tables=None,
                write_pages=None):
    """One decode step. token: (B,) int32; pos: scalar int32 (absolute
    position of this token) or (B,) int32 per-row positions (continuous
    batching: pool rows belong to different requests).

    ``block_tables`` ((B, MP) int32, optional) switches global-attention
    layers to the paged cache path: ``cache`` must then come from
    :func:`init_paged_cache` and ``pos`` must be per-row (DESIGN.md §5).
    ``write_pages`` ((B,) int32, optional) pins each row's K/V write to an
    allocator-certified refcount-1 page (the COW prefix-sharing guard);
    when omitted the write page is derived from the block table.
    Returns (logits (B, V), new_cache)."""
    pattern = cfg.layer_pattern
    P = len(pattern)
    x = embed(token[:, None], params["embed"])
    if cfg.is_encoder_decoder:
        half = cfg.d_model // 2
        freq = jnp.exp(-jnp.log(10000.0) * jnp.arange(half) / (half - 1))
        posf = jnp.asarray(pos)
        if posf.ndim == 0:
            ang = posf * freq
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])
            x = x + pe.astype(x.dtype)
        else:
            ang = posf[:, None].astype(jnp.float32) * freq
            pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)
            x = x + pe[:, None, :].astype(x.dtype)

    def fn_cycle(x, slices):
        if cfg.is_encoder_decoder:
            pslices, cslices, xkvs = slices
        else:
            pslices, cslices = slices
            xkvs = None
        newc = []
        for j, bt in enumerate(pattern):
            ekv = (xkvs[j]["k"], xkvs[j]["v"]) if xkvs is not None else None
            x, c = _block_decode(pslices[j], cfg, bt, x, pos, cslices[j], ekv,
                                 block_tables, write_pages)
            newc.append(c)
        return x, tuple(newc)

    K = cfg.num_layers // P
    if K > 0:
        if cfg.is_encoder_decoder:
            x, new_stack = _scan_maybe(
                fn_cycle, x, (params["stack"], cache["stack"], cache["xkv_stack"]),
                cfg.unroll)
        else:
            x, new_stack = _scan_maybe(fn_cycle, x, (params["stack"], cache["stack"]), cfg.unroll)
    else:
        new_stack = ()

    new_rem = []
    for j, bp in enumerate(params["rem"]):
        bt = pattern[j % P]
        ekv = None
        if cfg.is_encoder_decoder:
            xkv = cache["xkv_rem"][j]
            ekv = (xkv["k"], xkv["v"])
        x, c2 = _block_decode(bp, cfg, bt, x, pos, cache["rem"][j], ekv,
                              block_tables, write_pages)
        new_rem.append(c2)

    new_cache = {"stack": new_stack, "rem": tuple(new_rem)}
    if cfg.is_encoder_decoder:
        new_cache["xkv_stack"] = cache["xkv_stack"]
        new_cache["xkv_rem"] = cache["xkv_rem"]
    logits = _logits(params, cfg, x)[:, 0]
    return logits, new_cache
