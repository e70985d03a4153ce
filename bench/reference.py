"""The plain reference: a dense GQA decoder (Qwen2 / Llama layer
equations) in float32 at the highest matmul precision, written from the
published architecture and independent of the program. It imports
nothing of ``repro``; its weights are drawn again from the seed by
``bench.weights``, never taken from the program.

It runs teacher-forced over each checked prompt with its served tokens,
one layer at a time over every sequence (so only one layer's weights
are live), with attention in blocks of queries and the unembedding in
blocks of positions, so that it fits beside nothing on one chip.

``quant=True`` gives the control: the same equations with every matmul
operand (weights per output channel, activations per token) and the K/V
entries (per token and head) rounded to symmetric int8, the precision
below the configuration's bfloat16. With ``sample`` it also draws, at
every position, the token the mix's sampling rule (temperature, then
top-k, then top-p) picks from its own logits, with keys drawn from the
seed: the control in the program's place, sampling as the program does.

Per checked position it returns the reference's best logit, its k-th
best, the served token's logit and the logit of another model's pick
(``read``); :func:`gaps` turns these into the numbers compared.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from bench import weights

HI = lax.Precision.HIGHEST
BLOCK = 512            # query block, unembedding block and padding unit


def _q8(x, axis):
    """Symmetric int8 round-trip along ``axis`` (absmax scale)."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True),
                    1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _mm(x, w, quant):
    """x (..., k) @ w (k, n), float32; int8 operands for the control."""
    if quant:
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def _rms(x, eps):
    return x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, theta):
    """Rotary embedding, rotate-half layout; x (S, heads, hd)."""
    S, _, hd = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnames=("H", "KV", "eps", "theta",
                                             "quant"))
def _layer(x, w, *, H, KV, eps, theta, quant):
    S, d = x.shape
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    hd = w["q_proj"].shape[1] // H
    G = H // KV
    h = _rms(x, eps)
    q = _mm(h, w["q_proj"], quant)
    k = _mm(h, w["k_proj"], quant)
    v = _mm(h, w["v_proj"], quant)
    if "q_bias" in w:
        q, k, v = q + w["q_bias"], k + w["k_bias"], v + w["v_bias"]
    q = _rope(q.reshape(S, H, hd), theta).reshape(S, KV, G, hd)
    k = _rope(k.reshape(S, KV, hd), theta)
    v = v.reshape(S, KV, hd)
    if quant:
        k, v = _q8(k, -1), _q8(v, -1)
    kpos = jnp.arange(S)
    outs = []
    for b in range(0, S, BLOCK):
        qb = q[b:b + BLOCK]
        s = jnp.einsum("qkgh,skh->kgqs", qb, k, precision=HI) * hd ** -0.5
        causal = kpos[None, :] <= (b + jnp.arange(qb.shape[0]))[:, None]
        s = jnp.where(causal[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("kgqs,skh->qkgh", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(S, H * hd)
    x = x + _mm(o, w["o_proj"], quant)
    h = _rms(x, eps)
    a = jax.nn.silu(_mm(h, w["gate_proj"], quant)) * _mm(h, w["up_proj"],
                                                         quant)
    return x + _mm(a, w["down_proj"], quant)


@jax.jit
def _embed(table, toks):
    return jnp.take(table, toks, axis=0).astype(jnp.float32)


def _pick(logits, keys, temperature, top_k, top_p):
    """One token per row by the sampling rule: logits over the
    temperature, the top-k of them, then the smallest prefix of those
    whose probability reaches top-p; ``keys`` one raw key per row."""
    vals, idx = lax.top_k(logits / temperature, top_k)
    probs = jax.nn.softmax(vals, axis=-1)
    keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
    pick = jax.vmap(jax.random.categorical)(keys, jnp.where(keep, vals,
                                                            -jnp.inf))
    return jnp.take_along_axis(idx, pick[:, None], 1)[:, 0]


@functools.partial(jax.jit, static_argnames=("eps", "k", "quant", "sample"))
def _head(x, table, nxt, read, keys, *, eps, k, quant, sample):
    """Unembed one block of positions and reduce it: best, k-th best,
    the next served token's logit, the logit at ``read`` (another
    model's pick) and, with ``sample`` = (temperature, top_k, top_p),
    this model's own pick by that rule."""
    h = _rms(x, eps)
    t = table.astype(jnp.float32)
    if quant:
        h, t = _q8(h, -1), _q8(t, -1)
    logits = jnp.einsum("sd,vd->sv", h, t, precision=HI)
    vals = lax.top_k(logits, k)[0]
    at = lambda i: jnp.take_along_axis(logits, i[:, None], 1)[:, 0]
    out = dict(best=vals[:, 0], kth=vals[:, -1], served=at(nxt),
               read=at(read))
    if sample is not None:
        out["sampled"] = _pick(logits, keys, *sample)
    return out


def forward(seed: int, mc: dict, seqs, *, quant: bool = False, k: int = 20,
            read=None, sample=None):
    """Teacher-forced pass over ``seqs`` (int token arrays). Returns one
    dict of per-position numpy arrays for each sequence: position i
    predicts token i+1 (the last position has no next token; its
    ``served`` is meaningless). ``read``: optional per-sequence int
    arrays of token indices whose logits to report (another model's
    picks). ``sample``: (temperature, top_k, top_p) to draw a token at
    every position (``sampled``), with keys from the seed."""
    dtype = jnp.dtype(mc["torch_dtype"])
    key = weights.seed_key(seed)
    eps = float(mc["rms_norm_eps"])
    H, KV = mc["num_attention_heads"], mc["num_key_value_heads"]
    theta = float(mc["rope_theta"])
    pads = [-(-len(s) // BLOCK) * BLOCK for s in seqs]
    toks = [np.pad(np.asarray(s, np.int32), (0, p - len(s)))
            for s, p in zip(seqs, pads)]
    gs = weights.global_shapes(mc)
    table = weights.global_weight(key, "embed", gs["embed"], dtype)
    xs = [_embed(table, jnp.asarray(t)) for t in toks]
    del table
    for layer in range(mc["num_hidden_layers"]):
        w = weights.layer_weights(seed, mc, layer, dtype)
        xs = [_layer(x, w, H=H, KV=KV, eps=eps, theta=theta, quant=quant)
              for x in xs]
        del w
    head = "embed" if mc["tie_word_embeddings"] else "lm_head"
    table = weights.global_weight(key, head, gs[head], dtype)
    skey = jax.random.fold_in(key, 13)
    out = []
    for i, (x, t, n) in enumerate(zip(xs, toks, seqs)):
        nxt = np.concatenate([t[1:], t[:1]])
        rd = np.zeros(len(t), np.int32)
        if read is not None:
            rd[:len(read[i])] = read[i]
        ki = jax.random.fold_in(skey, i)
        keys = jax.vmap(lambda p: jax.random.fold_in(ki, p))(
            jnp.arange(len(t)))
        parts = [_head(x[b:b + BLOCK], table, jnp.asarray(nxt[b:b + BLOCK]),
                       jnp.asarray(rd[b:b + BLOCK]), keys[b:b + BLOCK],
                       eps=eps, k=k, quant=quant, sample=sample)
                 for b in range(0, len(t), BLOCK)]
        parts = jax.device_get(parts)
        out.append({f: np.concatenate([p[f] for p in parts])[:len(n)]
                    for f in parts[0]})
    return out


def gaps(ref, starts, field: str = "served"):
    """The numbers compared, from a float32 reference pass, over every
    served token (positions ``start-1 ..`` of each sequence): the gap by
    which the token's reference logit lies below the reference's k-th
    best (0 inside the top k; the mix samples from the top k). With
    ``field="read"`` the tokens are another model's picks at the same
    positions. Returns ``({"kappa_topk_gap": widest gap,
    "kappa_topk_gap_mean": mean gap, "kappa_topk_miss": % of tokens
    outside the top k}, tokens checked)``."""
    all_gaps = []
    for r, s0 in zip(ref, starts):
        sl = slice(s0 - 1, len(r["best"]) - 1)
        all_gaps.append(np.maximum(r["kth"][sl] - r[field][sl], 0.0))
    g = np.concatenate(all_gaps) if all_gaps else np.zeros(0)
    if not g.size:
        return {"kappa_topk_gap": 0.0, "kappa_topk_gap_mean": 0.0,
                "kappa_topk_miss": 0.0}, 0
    return {"kappa_topk_gap": float(g.max()),
            "kappa_topk_gap_mean": float(g.mean()),
            "kappa_topk_miss": float(100.0 * np.mean(g > 0))}, int(g.size)
