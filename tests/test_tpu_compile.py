"""Compile the served path's paged attention kernel for a described TPU
v5e, at the published widths of the benchmark's models:
deepseek-r1-distill-qwen-1.5b (12 query heads over 2 KV heads) and
qwen2.5-7b-instruct (28 over 4, an odd group of 7), head dim 128,
64-token pages, bf16 and int8 pages, one decode token and one prefill
chunk, and the decode call at each benchmark cell's own rows and table
width; and a 64-wide head, whose pages the kernel copies another way.

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
# model -> (H, KV, hd) and the prefill chunk compiled: the one
# chip_smoke.py serves r1d with, and the one the benchmark serves
# qwen2.5-7b with (3,584 query rows of a KV head in VMEM)
MODELS = {"deepseek-r1-distill-qwen-1.5b": ((12, 2, 128), 16),
          "qwen2.5-7b-instruct": ((28, 4, 128), 512)}
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


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", list(MODELS))
def test_paged_kernel_compiles_for_v5e(one_chip, arch, kv_dtype, prefill):
    cfg = get_config(arch)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    widths, prefill_chunk = MODELS[arch]
    assert (H, KV, hd) == widths
    chunk = prefill_chunk if prefill else 1
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


# model -> the benchmark cell's decode rows and block-table width
# (max_seq 5,120 over 64-token pages) and its pool's pages, trash page
# included
CELL_DECODE = {"deepseek-r1-distill-qwen-1.5b": (104, 80, 2305),
               "qwen2.5-7b-instruct": (48, 80, 1153)}


@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
@pytest.mark.parametrize("arch", list(CELL_DECODE))
def test_paged_decode_compiles_at_cell_shapes(one_chip, arch, kv_dtype):
    """The decode call at a benchmark cell's own rows and table width:
    the kernel's page tile and its buffers fit the v5e's VMEM there."""
    cfg = get_config(arch)
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    B, MP, P = CELL_DECODE[arch]

    def shape(shp, dtype):
        return jax.ShapeDtypeStruct(shp, jnp.dtype(dtype), sharding=one_chip)

    pages = shape((P, KV, PAGE_SIZE, hd), kv_dtype)
    scales = None
    if kv_dtype == "int8":
        scales = shape((P, KV, 1, PAGE_SIZE), "float32")
    step = jax.jit(functools.partial(paged_decode_attn_pallas,
                                     interpret=False))
    compiled = step.lower(shape((B, H, hd), cfg.dtype), pages, pages,
                          shape((B, MP), "int32"), shape((B,), "int32"),
                          k_scales=scales, v_scales=scales).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel did not reach the TPU program"


@pytest.mark.parametrize("prefill", [False, True],
                         ids=["decode", "prefill_chunk"])
@pytest.mark.parametrize("kv_dtype", ["bfloat16", "int8"])
def test_paged_kernel_compiles_for_v5e_at_head_dim_64(one_chip, kv_dtype,
                                                      prefill):
    """A head dim short of 128 lanes (granite-moe-3b-a800m's 64, 24
    query heads over 8 KV heads): Mosaic cannot slice such an HBM page
    by hand, so the kernel's pipeline copies the pages as blocks; that
    path compiles too."""
    cfg = get_config("granite-moe-3b-a800m")
    H, KV, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    assert (H, KV, hd) == (24, 8, 64)
    chunk = 512 if prefill else 1
    B = ROWS if chunk == 1 else 1

    def shape(shp, dtype):
        return jax.ShapeDtypeStruct(shp, jnp.dtype(dtype), sharding=one_chip)

    pages = shape((NUM_PAGES, KV, PAGE_SIZE, hd), kv_dtype)
    scales = None
    if kv_dtype == "int8":
        scales = shape((NUM_PAGES, KV, 1, PAGE_SIZE), "float32")
    if chunk == 1:
        fn, q = paged_decode_attn_pallas, shape((B, H, hd), "bfloat16")
    else:
        fn = paged_prefill_attn_pallas
        q = shape((B, chunk, H, hd), "bfloat16")
    step = jax.jit(functools.partial(fn, interpret=False))
    compiled = step.lower(q, pages, pages, shape((B, MAX_PAGES), "int32"),
                          shape((B,), "int32"), k_scales=scales,
                          v_scales=scales).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        "the Pallas kernel did not reach the TPU program"
