"""Operations and bytes that the served work requires, from the shapes
of a published configuration: what the algorithm needs, not what an
implementation happens to do (no padding rows, no page rounding, no
recomputation).

A token at position ``p`` (0-based) attends over ``p + 1`` keys. A
decode row at position ``p`` feeds one token there; a prefill chunk
feeds ``c`` tokens at ``p0 .. p0 + c - 1`` and needs logits only for its
last one.
"""
from __future__ import annotations

import numpy as np


def dims(mc: dict) -> dict:
    d, H, KV = mc["hidden_size"], mc["num_attention_heads"], \
        mc["num_key_value_heads"]
    hd = mc.get("head_dim") or d // H
    return dict(L=mc["num_hidden_layers"], d=d, H=H, KV=KV, hd=hd,
                ff=mc["intermediate_size"], V=mc["vocab_size"],
                tied=bool(mc["tie_word_embeddings"]))


def matmul_params(mc: dict) -> int:
    """Weights one token multiplies through, per layer (no embedding)."""
    g = dims(mc)
    d, H, KV, hd, ff = g["d"], g["H"], g["KV"], g["hd"], g["ff"]
    return d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * ff


def weight_bytes(mc: dict, itemsize: int) -> int:
    """Bytes of every weight a step reads: the layers, the norms, and
    the unembedding table (the embedding rows gathered are negligible)."""
    g = dims(mc)
    biases = (g["H"] + 2 * g["KV"]) * g["hd"] if mc["model_type"] == "qwen2" \
        else 0
    per_layer = (matmul_params(mc) + biases) * itemsize + 2 * g["d"] * 4
    return g["L"] * per_layer + g["V"] * g["d"] * itemsize + g["d"] * 4


def attn_flops(mc: dict, keys) -> float:
    """QK^T and PV over ``keys`` keys (scalar or array), all layers."""
    g = dims(mc)
    return 4.0 * g["L"] * g["H"] * g["hd"] * np.sum(keys)


def decode_flops(mc: dict, pos) -> float:
    """A decode step of rows at positions ``pos`` (array): matmuls,
    attention over each row's context, and one row of logits each."""
    g = dims(mc)
    pos = np.asarray(pos, np.float64)
    n = pos.size
    return (2.0 * g["L"] * matmul_params(mc) * n + attn_flops(mc, pos + 1)
            + 2.0 * g["d"] * g["V"] * n)


def chunk_flops(mc: dict, p0: int, c: int) -> float:
    """A prefill chunk of ``c`` tokens from position ``p0``."""
    g = dims(mc)
    keys = np.arange(p0 + 1, p0 + c + 1, dtype=np.float64)
    return (2.0 * g["L"] * matmul_params(mc) * c + attn_flops(mc, keys)
            + 2.0 * g["d"] * g["V"])


def decode_attn_cost(mc: dict, pos, itemsize: int):
    """(flops, bytes) of the paged decode attention kernel over rows at
    ``pos``: each row reads its ``pos + 1`` cached keys and values, its
    query (served dtype) and writes a float32 output, in every layer."""
    g = dims(mc)
    pos = np.asarray(pos, np.float64)
    kv = 2.0 * g["KV"] * g["hd"] * itemsize * np.sum(pos + 1)
    qo = pos.size * g["H"] * g["hd"] * (itemsize + 4)
    return attn_flops(mc, pos + 1), g["L"] * (kv + qo)


def chunk_attn_cost(mc: dict, p0: int, c: int, itemsize: int):
    """(flops, bytes) of the paged prefill kernel for one chunk: the
    chunk's queries read the ``p0 + c`` keys and values once."""
    g = dims(mc)
    keys = np.arange(p0 + 1, p0 + c + 1, dtype=np.float64)
    kv = 2.0 * g["KV"] * g["hd"] * itemsize * (p0 + c)
    qo = c * g["H"] * g["hd"] * (itemsize + 4)
    return attn_flops(mc, keys), g["L"] * (kv + qo)


def kv_bytes_per_token(mc: dict, itemsize: int) -> int:
    g = dims(mc)
    return g["L"] * 2 * g["KV"] * g["hd"] * itemsize
