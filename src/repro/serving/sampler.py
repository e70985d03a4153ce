"""Jittable sampling: temperature + top-k + top-p (paper §4.1:
T=0.7, k=20, p=0.95).

Two batching regimes:
  * :func:`sample` — one RNG key for a whole (B, V) batch (lockstep
    branches of a single request; the paper's setting).
  * :func:`sample_rows` — one key *per row*. This is what lets the
    continuous-batching scheduler sample every active request's rows in
    ONE fused dispatch per tick: rows belong to different requests with
    different RNG streams, so each row carries its own key, and a vmap
    over rows is bitwise identical to sampling each request separately
    (the scheduler/engine equivalence guarantee). Given
    :class:`RowStreams` instead of keys, the same program derives the
    row keys itself from a device table of request streams.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def sample(rng, logits, *, temperature: float = 0.7, top_k: int = 20,
           top_p: float = 0.95):
    """logits: (B, V) fp32 → (B,) int32 sampled tokens.
    temperature <= 0 → greedy argmax."""
    if temperature <= 0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    l = logits.astype(jnp.float32) / temperature
    k = min(top_k, l.shape[-1]) if top_k > 0 else l.shape[-1]
    vals, idx = jax.lax.top_k(l, k)                       # (B, k) sorted desc
    if 0.0 < top_p < 1.0:
        probs = jax.nn.softmax(vals, axis=-1)
        csum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose *previous* cumulative mass < p (always keep 1st)
        keep = (csum - probs) < top_p
        vals = jnp.where(keep, vals, NEG_INF)
    choice = jax.random.categorical(rng, vals, axis=-1)   # (B,)
    return jnp.take_along_axis(idx, choice[:, None], axis=-1)[:, 0].astype(jnp.int32)


def greedy(logits):
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


def _picked_lp(logits, tokens):
    """(B,) log-prob of each row's picked token (fp32 softmax)."""
    lp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return jnp.take_along_axis(lp, tokens[:, None], axis=-1)[:, 0]


_picked_logprob_jit = jax.jit(_picked_lp)

# device-dispatch counters: schedulers promise ONE fused sampling call
# per tick regardless of active-request count — tests assert against
# these (reset freely)
DISPATCHES = {"sample_rows": 0, "picked_logprob": 0}


def reset_dispatch_counters() -> None:
    for k in DISPATCHES:
        DISPATCHES[k] = 0


def picked_logprob(logits, tokens):
    DISPATCHES["picked_logprob"] += 1
    return _picked_logprob_jit(logits, tokens)


class RowStreams(NamedTuple):
    """Device-resident RNG streams, in place of per-row keys.

    table: (S, 2) uint32 raw threefry key data, one stream per active
        request. row_stream: (R,) int32 — the stream of each row.
        row_branch: (R,) int32 — the row's index among its request's
        live rows. advance: (S,) bool — streams that step this call.

    Each advancing stream steps as ``RequestState.step_keys`` does
    (``rng, kk = split(rng)``) and row ``i`` takes
    ``split(kk[row_stream[i]], R)[row_branch[i]]``, which equals
    ``split(kk, n)[j]`` for any ``n > j`` only under
    ``jax_threefry_partitionable`` (see :func:`check_partitionable`)."""
    table: jax.Array
    row_stream: jax.Array
    row_branch: jax.Array
    advance: jax.Array


def check_partitionable() -> None:
    """Raise unless ``jax_threefry_partitionable`` is on: only then is a
    key of ``split(k, n)`` independent of ``n``, which is what lets
    :class:`RowStreams` derive every request's row keys with one split
    width and still match ``RequestState.step_keys`` bit for bit."""
    if not jax.config.jax_threefry_partitionable:
        raise RuntimeError(
            "device-derived sampling keys need jax_threefry_partitionable "
            "on (JAX's default): with it off, split(k, n)[j] depends on n "
            "and the fused sampler would draw other tokens than the "
            "engine loop")


def sample_rows(keys, logits, greedy_mask, kcfg, *, want_picked_lp=False):
    """Per-row-keyed sampling — ONE device dispatch for any mix of rows.

    keys: (R,) PRNG keys (one per row; rows of the same request share a
        split of that request's stream), or a :class:`RowStreams`, from
        which the program derives those keys itself. logits: (R, V).
        greedy_mask: (R,) bool — True rows take argmax and ignore their
        key.
    Returns (R,) int32 tokens; with ``want_picked_lp`` a
    ((R,) tokens, (R,) picked-token log-prob) pair from the same fused
    dispatch (BoN-style strategies consume the log-prob, so the
    scheduler gets both for one kernel launch and one transfer). With
    :class:`RowStreams` it returns ``(tokens, picked-token log-prob or
    None, advanced table)`` and consumes (donates) ``keys.table``.

    vmap over rows with per-row keys means row i's token depends only on
    (keys[i], logits[i]) — independent of R or which other rows ride in
    the batch. The scheduler exploits this to fuse all active requests
    into one call per tick while staying token-for-token equivalent to
    sequential serving."""
    # jit keyed on the sampling hyperparameters only — NOT the whole
    # kcfg, which would retrace for every per-request max_new override
    DISPATCHES["sample_rows"] += 1
    hyper = dict(temperature=kcfg.temperature, top_k=kcfg.top_k,
                 top_p=kcfg.top_p, want_lp=want_picked_lp)
    if isinstance(keys, RowStreams):
        return _sample_rows(keys[1:], logits, greedy_mask, keys.table,
                            **hyper)
    return _sample_rows(keys, logits, greedy_mask, **hyper)


def _stream_keys(table, row_stream, row_branch, advance):
    """Advance the table's streams one step and derive each row's key:
    the advanced table and (R, 2) raw row keys."""
    streams = jax.random.wrap_key_data(table, impl="threefry2x32")
    pair = jax.vmap(jax.random.split)(streams)            # (S, 2)
    nxt = jax.random.key_data(pair[:, 0])
    kk = pair[row_stream, 1]                              # (R,)
    width = row_stream.shape[0]
    keys = jax.vmap(lambda k, j: jax.random.split(k, width)[j])(
        kk, row_branch)
    table = jnp.where(advance[:, None], nxt, table)
    return table, jax.random.key_data(keys)


@functools.partial(jax.jit,
                   static_argnames=("temperature", "top_k", "top_p",
                                    "want_lp"),
                   donate_argnames=("table",))
def _sample_rows(keys, logits, greedy_mask, table=None, *, temperature,
                 top_k, top_p, want_lp):
    """With ``table``, ``keys`` is ``RowStreams`` less its table (see
    :func:`sample_rows`)."""
    if table is not None:
        table, keys = _stream_keys(table, *keys)

    def one(key, row, g):
        s = sample(key, row[None], temperature=temperature,
                   top_k=top_k, top_p=top_p)[0]
        return jnp.where(g, jnp.argmax(row).astype(jnp.int32), s)
    toks = jax.vmap(one)(keys, logits, greedy_mask)
    lp = _picked_lp(logits, toks) if want_lp else None
    if table is not None:
        return toks, lp, table
    return (toks, lp) if want_lp else toks
