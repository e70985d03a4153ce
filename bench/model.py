"""The system under test, as the benchmark builds it: a configuration
file of published keys turned into the program's ``ModelConfig``, its
weights, and a paged serving pool.

Configuration files hold the model's published ``config.json`` keys
(with any cut listed under ``reduced``) and a ``serving`` group: page
size, pool pages, rows, maximum sequence and prefill chunk, each sized
as the file's ``sizing`` notes say.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import weights

SUPPORTED = {"qwen2", "llama"}


def model_config(mc: dict):
    """The program's ``ModelConfig`` for a published configuration."""
    from repro.configs.base import ModelConfig
    if mc["model_type"] not in SUPPORTED:
        raise ValueError(f"model_type {mc['model_type']!r}: the benchmark "
                         f"builds {sorted(SUPPORTED)}")
    if mc.get("use_sliding_window"):
        raise ValueError("sliding-window layers are not built here")
    return ModelConfig(
        name=mc["name"], family="dense",
        num_layers=mc["num_hidden_layers"], d_model=mc["hidden_size"],
        num_heads=mc["num_attention_heads"],
        num_kv_heads=mc["num_key_value_heads"],
        head_dim=mc.get("head_dim"), d_ff=mc["intermediate_size"],
        vocab_size=mc["vocab_size"], qkv_bias=mc["model_type"] == "qwen2",
        layer_pattern=("global",), rope_theta=float(mc["rope_theta"]),
        tie_embeddings=bool(mc["tie_word_embeddings"]),
        norm_eps=float(mc["rms_norm_eps"]), dtype=mc["torch_dtype"],
        source=mc["source"])


def make_params(cfg, mc: dict, seed: int):
    """All weights on the device, in the served dtype, from one jitted
    call."""
    from repro.models import init_params
    template = jax.eval_shape(lambda k: init_params(k, cfg),
                              jax.random.PRNGKey(0))
    # the seed's key is an argument, so every seed runs one compiled program
    params = jax.jit(lambda key: weights.program_params(template, mc, key))(
        weights.seed_key(seed))
    return jax.block_until_ready(params)


def make_scheduler(params, cfg, mc: dict, kcfg, serving: dict):
    from repro.serving.scheduler import PagedScheduler
    return PagedScheduler(
        params, cfg, kcfg, rows=serving["rows"], max_seq=serving["max_seq"],
        page_size=serving["page_size"], num_pages=serving["num_pages"],
        prefill_chunk=serving["prefill_chunk"],
        eos_id=int(mc["eos_token_id"]), bos_id=int(mc["bos_token_id"]))


def kappa_config(traffic: dict):
    from repro.configs.base import KappaConfig
    return KappaConfig(**traffic.get("kappa", {}))


def dtype_bytes(mc: dict) -> int:
    return jnp.dtype(mc["torch_dtype"]).itemsize
