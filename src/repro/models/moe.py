"""Token-choice top-k Mixture-of-Experts FFN with sort-based dispatch.

Design (TPU-native, see DESIGN.md):
  * router: softmax over E experts, top-k per token
  * dispatch: argsort tokens by expert id, pack into an (E·C, d) buffer
    (capacity C per expert, GShard-style drop on overflow)
  * expert compute: batched SwiGLU einsum over the (E, C, d) buffer —
    FLOPs ∝ active params only (not E× dense), which keeps the roofline
    MODEL_FLOPS/HLO_FLOPs ratio honest for qwen3-moe's 128 experts
  * combine: scatter-add back, weighted by router probs
  * sharding: expert axis on "model" (expert parallelism); token→expert
    routing crosses the mesh as XLA-inserted all-to-alls under pjit
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import dense_init


def init_moe(rng, d_model: int, d_ff: int, num_experts: int, dtype):
    ks = jax.random.split(rng, 4)
    e = num_experts
    return {
        "router": dense_init(ks[0], (d_model, e), dtype=jnp.float32),
        "wg": dense_init(ks[1], (e, d_model, d_ff), dtype=dtype),
        "wu": dense_init(ks[2], (e, d_model, d_ff), dtype=dtype),
        "wd": dense_init(ks[3], (e, d_ff, d_model), dtype=dtype),
    }


def moe_ffn(p, x, *, num_experts: int, experts_per_tok: int,
            capacity_factor: float = 1.25):
    """x: (B, S, d) -> (B, S, d). Returns (y, aux) where aux carries the
    router load-balance loss term (Switch-style).

    ``capacity_factor <= 0`` selects dropless mode (C = T·K): exact
    token-choice routing, used by the smoke/parity tests where
    ``prefill+decode ≡ train`` must hold bit-for-bit per token."""
    B, S, d = x.shape
    E, K = num_experts, experts_per_tok
    T = B * S
    xt = x.reshape(T, d)

    logits = (xt.astype(jnp.float32) @ p["router"])          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, K)                   # (T, K)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)   # renormalize

    # ---- load-balance aux loss (Switch Transformer eq. 4)
    density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
    density_proxy = jnp.mean(probs, axis=0)
    aux_loss = jnp.sum(density * density_proxy) * E

    # ---- sort-based dispatch
    if capacity_factor <= 0:
        C = T * K  # dropless
    else:
        C = int(max(1, (T * K / E) * capacity_factor))
    flat_e = top_e.reshape(-1)                               # (T*K,)
    flat_t = jnp.repeat(jnp.arange(T), K)                    # source token id
    flat_w = top_p.reshape(-1)

    order = jnp.argsort(flat_e, stable=True)
    se, st, sw = flat_e[order], flat_t[order], flat_w[order]
    # position within expert = rank among same-expert entries
    ar = jnp.arange(T * K)
    seg_start = jnp.searchsorted(se, jnp.arange(E))          # first idx per expert
    pos_in_e = ar - seg_start[se]
    keep = pos_in_e < C
    slot = jnp.where(keep, se * C + pos_in_e, E * C)         # E*C = drop slot

    buf = jnp.zeros((E * C + 1, d), x.dtype).at[slot].set(xt[st])
    buf = buf[:-1].reshape(E, C, d)

    # ---- expert compute (batched SwiGLU)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wg"])) \
        * jnp.einsum("ecd,edf->ecf", buf, p["wu"])
    out_buf = jnp.einsum("ecf,efd->ecd", h, p["wd"])          # (E, C, d)

    # ---- combine: weighted scatter-add back to tokens
    out_flat = out_buf.reshape(E * C, d)
    gathered = jnp.where(keep[:, None], out_flat[jnp.clip(slot, 0, E * C - 1)], 0.0)
    y = jnp.zeros((T, d), x.dtype).at[st].add(gathered * sw[:, None].astype(x.dtype))
    return y.reshape(B, S, d), aux_loss


# ---------------------------------------------------------------------------
# Expert-parallel MoE (shard_map) — §Perf hillclimb A.
#
# Under plain pjit the sort-based dispatch has data-dependent scatter
# indices, so GSPMD gives up and replicates the combine: a full (T, d)
# fp32 all-reduce per layer (measured 13.3 TB/chip for qwen3-moe train).
# The hand-written version below moves tokens with two all-to-alls over
# the "model" axis (send ≈ T_loc·K·d bytes per chip) and does the
# weighted top-k combine locally — the canonical expert-parallel flow.
# ---------------------------------------------------------------------------

_MESH = None


def set_mesh(mesh) -> None:
    """Install the mesh used by expert-parallel shard_map (launcher-set)."""
    global _MESH
    _MESH = mesh


def _sorted_pack(dest, n_dest: int, cap: int, payload):
    """Pack `payload[t]` rows into a (n_dest, cap) buffer by destination.
    Returns (buffer, slot) where slot[t] is the flat position (or n_dest*cap
    for dropped entries)."""
    n = dest.shape[0]
    order = jnp.argsort(dest, stable=True)
    sd = dest[order]
    seg_start = jnp.searchsorted(sd, jnp.arange(n_dest))
    pos = jnp.arange(n) - seg_start[sd]
    keep = pos < cap
    slot_sorted = jnp.where(keep, sd * cap + pos, n_dest * cap)
    # slot per ORIGINAL index
    slot = jnp.zeros((n,), jnp.int32).at[order].set(slot_sorted.astype(jnp.int32))
    buf = jnp.zeros((n_dest * cap + 1,) + payload.shape[1:], payload.dtype)
    buf = buf.at[slot].set(payload)
    return buf[:-1].reshape((n_dest, cap) + payload.shape[1:]), slot


def moe_ffn_expert_parallel(p, x, *, num_experts: int, experts_per_tok: int,
                            capacity_factor: float = 2.0,
                            model_axis: str = "model",
                            dp_axes=("pod", "data"),
                            seq_sharded: bool = False):
    """Expert-parallel MoE: tokens sharded on dp axes, experts on
    ``model_axis``. Must be called with a mesh installed via set_mesh().

    ``seq_sharded`` (§Perf A it.3): consume the sequence-parallel stream
    directly — x enters (dp, "model", None), each chip routes its own
    seq slice, and the y all-gather disappears (the next block's SP
    constraint keeps the stream seq-sharded)."""
    assert _MESH is not None, "call repro.models.moe.set_mesh(mesh) first"
    from jax.sharding import PartitionSpec as P

    def shard_map(f, **kw):
        # check_vma can't statically prove the post-all_gather model-axis
        # replication of y; disable the check (correctness covered by
        # scripts/validate_moe_ep.py against the dropless oracle)
        return jax.shard_map(f, check_vma=False, **kw)

    E, K = num_experts, experts_per_tok
    dp = tuple(a for a in dp_axes if a in _MESH.axis_names)
    M = _MESH.shape[model_axis]
    assert E % M == 0, f"experts {E} must divide model axis {M}"
    E_loc = E // M
    cf = capacity_factor if capacity_factor > 0 else 8.0

    def inner(router, wg, wu, wd, xl):
        B, S, d = xl.shape
        if seq_sharded:
            # xl is already this chip's seq slice: tokens are local
            T_full = T_pad = None
            T = B * S
            xt = xl.reshape(T, d)
        else:
            T_full = B * S
            xt_full = xl.reshape(T_full, d)
            # --- token-parallel over the model axis: each model chip routes
            # and combines its own 1/M slice (pad when T doesn't divide —
            # decode steps can have T < M)
            T_pad = -(-T_full // M) * M
            if T_pad != T_full:
                xt_full = jnp.pad(xt_full, ((0, T_pad - T_full), (0, 0)))
            T = T_pad // M
            idx_m = jax.lax.axis_index(model_axis)
            xt = jax.lax.dynamic_slice_in_dim(xt_full, idx_m * T, T, 0)

        logits = xt.astype(jnp.float32) @ router          # router replicated
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, K)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

        density = jnp.mean(jax.nn.one_hot(top_e[:, 0], E), axis=0)
        aux = jnp.sum(density * jnp.mean(probs, axis=0)) * E
        aux = jax.lax.pmean(aux, axis_name=dp + (model_axis,)) if dp \
            else jax.lax.pmean(aux, axis_name=model_axis)

        flat_e = top_e.reshape(-1)
        flat_t = jnp.repeat(jnp.arange(T), K)
        flat_w = top_p.reshape(-1)
        dest = flat_e // E_loc                             # target chip

        cap = max(1, int(T * K / M * cf))
        send_x, slot = _sorted_pack(dest, M, cap, xt[flat_t])
        send_e, _ = _sorted_pack(dest, M, cap,
                                 (flat_e + 1).astype(jnp.int32))  # 0 = empty

        recv_x = jax.lax.all_to_all(send_x, model_axis, 0, 0, tiled=True)
        recv_e = jax.lax.all_to_all(send_e, model_axis, 0, 0, tiled=True)

        # --- local expert compute
        my_first = jax.lax.axis_index(model_axis) * E_loc
        rex = recv_x.reshape(M * cap, d)
        re_global = recv_e.reshape(M * cap)
        valid = re_global > 0
        re_loc = jnp.clip(re_global - 1 - my_first, 0, E_loc - 1)
        re_loc = jnp.where(valid, re_loc, E_loc)           # E_loc = drop row
        c2 = max(1, int(M * cap / E_loc * 1.5))
        ebuf, eslot = _sorted_pack(re_loc, E_loc + 1, c2, rex)
        ebuf = ebuf[:E_loc]                                # (E_loc, c2, d)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", ebuf, wg)) \
            * jnp.einsum("ecd,edf->ecf", ebuf, wu)
        eout = jnp.einsum("ecf,efd->ecd", h, wd)           # (E_loc, c2, d)

        eout_flat = eout.reshape(E_loc * c2, d)
        ok = valid & (eslot < E_loc * c2)
        rows = jnp.where(ok[:, None],
                         eout_flat[jnp.clip(eslot, 0, E_loc * c2 - 1)], 0.0)
        back = rows.reshape(M, cap, d)

        ret = jax.lax.all_to_all(back, model_axis, 0, 0, tiled=True)
        ret_flat = ret.reshape(M * cap, d)

        kept = slot < M * cap
        vals = jnp.where(kept[:, None],
                         ret_flat[jnp.clip(slot, 0, M * cap - 1)], 0.0)
        y_m = jnp.zeros((T, d), xl.dtype).at[flat_t].add(
            vals.astype(xl.dtype) * flat_w[:, None].astype(xl.dtype))
        if seq_sharded:
            return y_m.reshape(B, S, d), aux
        # reassemble the model-axis token slices (Megatron-style AG)
        y = jax.lax.all_gather(y_m, model_axis, axis=0, tiled=True)
        return y[:T_full].reshape(B, S, d), aux

    B, S, d = x.shape
    if seq_sharded:
        xspec = P(dp if dp else None, model_axis, None)
    else:
        xspec = P(dp if dp else None, None, None)
    f = shard_map(
        inner, mesh=_MESH,
        in_specs=(P(), P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None), xspec),
        out_specs=(xspec, P()),
    )
    y, aux = f(p["router"], p["wg"], p["wu"], p["wd"], x)
    return y, aux
