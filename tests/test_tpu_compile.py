"""Compile the served path's paged attention kernel for a described TPU
v5e, at the published widths of deepseek-r1-distill-qwen-1.5b: 12 query
heads over 2 KV heads, head dim 128, 64-token pages, bf16 and int8
pages, one decode token and one prefill chunk.

Interpret mode, which every other kernel test runs, checks none of the
TPU lowering's rules (block tiling, VMEM size). The TPU compiler is
installed with jaxlib's TPU plugin and compiles for a chip that is
described, not attached, so these tests need no chip.

The topology is described inside a fixture and nowhere else: only one
process at a time may load the TPU library, and it holds it until it
exits. A worker that collects this file without running it never loads
it."""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attn.kernel import (paged_decode_attn_pallas,
                                              paged_prefill_attn_pallas)

PAGE_SIZE = 64
PREFILL_CHUNK = 16      # the chunk size chip_smoke.py serves with
ROWS = 10               # decode pool rows: two N=5 fan-outs
MAX_PAGES = 32          # 2048-token block tables
NUM_PAGES = 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without one: keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("chunk", [1, PREFILL_CHUNK],
                         ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_kernel_compiles_for_v5e(one_chip, kv_dtype, chunk):
    cfg = get_config("deepseek-r1-distill-qwen-1.5b")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    assert (H, KV, hd) == (12, 2, 128)
    B = ROWS if chunk == 1 else 1       # prefill chunks run batch-1

    def shape(shp, dtype):
        return jax.ShapeDtypeStruct(shp, jnp.dtype(dtype), sharding=one_chip)

    pages = shape((NUM_PAGES, KV, PAGE_SIZE, hd), kv_dtype)
    scales = None
    if kv_dtype == "int8":
        scales = shape((NUM_PAGES, KV, 1, PAGE_SIZE), "float32")
    bt = shape((B, MAX_PAGES), "int32")
    pos = shape((B,), "int32")
    if chunk == 1:
        fn = paged_decode_attn_pallas
        q = shape((B, H, hd), cfg.dtype)
    else:
        fn = paged_prefill_attn_pallas
        q = shape((B, chunk, H, hd), cfg.dtype)
    step = jax.jit(functools.partial(fn, interpret=False))
    compiled = step.lower(q, pages, pages, bt, pos, k_scales=scales,
                          v_scales=scales).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel did not reach the TPU program"
