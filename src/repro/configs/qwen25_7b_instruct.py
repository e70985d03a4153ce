"""qwen2.5-7b-instruct — the paper's large evaluation model.
28L d_model=3584 28H (GQA kv=4) d_ff=18944 vocab=152064, untied head,
rope_theta 1e6, rms_norm_eps 1e-6 (bos 151643 and eos 151645 are the
serving configuration's: ``bench/configs/qwen2.5-7b-instruct-l14.json``).
[hf:Qwen/Qwen2.5-7B-Instruct]
"""
from repro.configs.base import ModelConfig


def config() -> ModelConfig:
    return ModelConfig(
        name="qwen2.5-7b-instruct",
        family="dense",
        num_layers=28,
        d_model=3584,
        num_heads=28,
        num_kv_heads=4,
        d_ff=18_944,
        vocab_size=152_064,
        qkv_bias=True,
        layer_pattern=("global",),
        rope_theta=1_000_000.0,
        tie_embeddings=False,
        source="hf:Qwen/Qwen2.5-7B-Instruct",
    )
