"""Size a configuration's page pool without a chip: compile the served
decode tick (and the tick with one prefill chunk fused in) for a
described TPU v5e at the configuration's widths and a candidate pool,
and print what ``memory_analysis`` says the program holds.

  JAX_PLATFORMS=cpu python3 bench/size_pool.py <config> <num_pages> <rows>

The pool fits when weights + arguments' pool + temporaries + a margin
stay under the chip's memory; each configuration file records the
numbers it was sized from.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import SingleDeviceSharding  # noqa: E402

from bench import harness, model  # noqa: E402


def main(config: str, num_pages: int, rows: int) -> None:
    from jax.experimental import topologies
    from repro.models import init_paged_cache, init_cache, init_params
    from repro.serving import engine
    from repro.serving.scheduler import _paged_step
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    mc = harness.Bench().config(config)
    sv = mc["serving"]
    cfg = model.model_config(mc)
    ps, max_seq = sv["page_size"], sv["max_seq"]
    mp = max_seq // ps

    def spec(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    params = spec(jax.eval_shape(lambda k: init_params(k, cfg),
                                 jax.random.PRNGKey(0)))
    pool = spec(jax.eval_shape(
        lambda: init_paged_cache(cfg, rows, num_pages, ps, max_seq)))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one)
    wbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    pbytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pool))
    print(f"{config}: weights {wbytes} B, pool {num_pages} pages {pbytes} B, "
          f"rows {rows}", flush=True)
    dec = _paged_step.lower(params, cfg, i32(rows), i32(rows), pool,
                            i32(rows, mp), i32(rows)).compile()
    print("decode tick:", dec.memory_analysis(), flush=True)
    c = sv["prefill_chunk"]
    aux = spec(jax.eval_shape(lambda: init_cache(cfg, 1, 1)))
    chunk = (i32(1, c), i32(1), i32(1, min(mp, -(-(c // ps) // 8) * 8)),
             i32(1, c))
    fused = engine._fused_decode_chunks.lower(
        params, cfg, i32(rows), i32(rows), pool, i32(rows, mp), i32(rows),
        (chunk,), (aux,)).compile()
    print(f"tick + one {c}-token chunk:", fused.memory_analysis(), flush=True)


if __name__ == "__main__":
    main(sys.argv[1], int(sys.argv[2]), int(sys.argv[3]))
