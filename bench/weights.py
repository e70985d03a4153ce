"""Seeded random weights, shared by the system under test and the plain
reference without either seeing the other's arrays.

Every weight is named (``q_proj``, ``gate_proj``, ``embed`` ...) and is a
pure function of (seed, name, layer): uniform values of the spread the
usual fan-in initialisation gives, drawn from threefry bits and rounded
to the served dtype. The harness lays them into the program's parameter
tree in one jitted call (:func:`program_params`); the reference draws the
same named weights again, one layer at a time (:func:`layer_weights`),
after the program's state is gone. Norm scales stay at their neutral
value (1), as the program's own initializer leaves them.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp
import numpy as np

# standard deviation of the biases: large enough that dropping one
# moves the logits well past rounding
BIAS_STD = 0.5
EMBED_STD = 0.02


def seed_key(seed: int):
    """Raw threefry key data for a seed of up to 64 bits."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} outside [0, 2**64)")
    return jnp.asarray(np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32))


def layer_shapes(mc: dict) -> dict:
    """Named per-layer weight shapes of a dense GQA decoder layer."""
    d, H, KV = mc["hidden_size"], mc["num_attention_heads"], \
        mc["num_key_value_heads"]
    hd = mc.get("head_dim") or d // H
    ff = mc["intermediate_size"]
    shapes = {"q_proj": (d, H * hd), "k_proj": (d, KV * hd),
              "v_proj": (d, KV * hd), "o_proj": (H * hd, d),
              "gate_proj": (d, ff), "up_proj": (d, ff), "down_proj": (ff, d)}
    if mc.get("model_type") == "qwen2":
        shapes.update(q_bias=(H * hd,), k_bias=(KV * hd,), v_bias=(KV * hd,))
    return shapes


def global_shapes(mc: dict) -> dict:
    V, d = mc["vocab_size"], mc["hidden_size"]
    out = {"embed": (V, d)}
    if not mc["tie_word_embeddings"]:
        out["lm_head"] = (V, d)
    return out


def _std(name: str, shape) -> float:
    if name.endswith("_bias"):
        return BIAS_STD
    if name in ("embed", "lm_head"):
        return EMBED_STD
    return 1.0 / math.sqrt(shape[0])


def _name_key(key, name: str):
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def draw(key, name: str, shape, dtype):
    """One named weight from its own key: uniform with the std of
    :func:`_std`, computed exactly in float32, then rounded to ``dtype``."""
    bits = jax.random.bits(key, shape, jnp.uint32)
    u = (bits >> 8).astype(jnp.float32) * (2.0 ** -24)      # [0, 1), exact
    half_width = math.sqrt(3.0) * _std(name, shape)
    return ((u - 0.5) * (2.0 * half_width)).astype(dtype)


def layer_weight(key, name: str, layer: int, shape, dtype):
    return draw(jax.random.fold_in(_name_key(key, name), layer), name, shape,
                dtype)


def global_weight(key, name: str, shape, dtype):
    return draw(_name_key(key, name), name, shape, dtype)


def layer_weights(seed: int, mc: dict, layer: int, dtype=jnp.bfloat16):
    """The named weights of one layer, as served (rounded to ``dtype``)."""
    key = seed_key(seed)
    return {n: layer_weight(key, n, layer, s, dtype)
            for n, s in layer_shapes(mc).items()}


# program parameter-tree leaf -> named weight (per-layer stacked leaves)
_PROGRAM_LAYER_LEAVES = {
    ("attn", "wq"): "q_proj", ("attn", "wk"): "k_proj",
    ("attn", "wv"): "v_proj", ("attn", "wo"): "o_proj",
    ("attn", "bq"): "q_bias", ("attn", "bk"): "k_bias",
    ("attn", "bv"): "v_bias",
    ("ffn", "wg"): "gate_proj", ("ffn", "wu"): "up_proj",
    ("ffn", "wd"): "down_proj",
}
_PROGRAM_GLOBAL_LEAVES = {("embed",): "embed", ("unembed",): "lm_head"}
# norm scales: the program computes x * (1 + scale); 0 is weight 1
_PROGRAM_NEUTRAL = {"ln1", "ln2", "final_norm"}


def _path_names(path):
    return tuple(getattr(p, "key", getattr(p, "idx", None)) for p in path)


def program_params(template, mc: dict, key):
    """Fill the program's parameter tree (``template`` from
    ``jax.eval_shape(init_params, ...)``: a single-pattern stack of
    ``num_hidden_layers`` layers) with the named weights of the seed
    whose :func:`seed_key` is ``key``. Call under ``jax.jit`` so the
    whole tree is made on the device in one program."""
    L = mc["num_hidden_layers"]
    layers = jnp.arange(L)

    def fill(path, leaf):
        names = _path_names(path)
        if names[-1] in _PROGRAM_NEUTRAL:
            return jnp.zeros(leaf.shape, leaf.dtype)
        if names in _PROGRAM_GLOBAL_LEAVES:
            return global_weight(key, _PROGRAM_GLOBAL_LEAVES[names],
                                 leaf.shape, leaf.dtype)
        if names[:2] == ("stack", 0) and names[2:] in _PROGRAM_LAYER_LEAVES:
            name = _PROGRAM_LAYER_LEAVES[names[2:]]
            if leaf.shape[0] != L:
                raise ValueError(f"{names}: {leaf.shape[0]} stacked layers, "
                                 f"the configuration has {L}")
            nk = _name_key(key, name)
            return jax.vmap(lambda l: draw(jax.random.fold_in(nk, l), name,
                                           leaf.shape[1:], leaf.dtype))(layers)
        raise ValueError(f"no named weight for program leaf {names}")

    return jax.tree_util.tree_map_with_path(fill, template)
