"""Validate the expert-parallel shard_map MoE against the dropless einsum
oracle on an 8-device host mesh (separate process: forces device count)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import moe

mesh = jax.make_mesh((2, 4), ("data", "model"))
moe.set_mesh(mesh)

E, K, d, ff = 8, 2, 16, 32
p = moe.init_moe(jax.random.PRNGKey(0), d, ff, E, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, d))

with mesh:
    y_ref, aux_ref = moe.moe_ffn(p, x, num_experts=E, experts_per_tok=K,
                                 capacity_factor=0.0)
    y_ep, aux_ep = jax.jit(
        lambda p_, x_: moe.moe_ffn_expert_parallel(
            p_, x_, num_experts=E, experts_per_tok=K, capacity_factor=16.0)
    )(p, x)

np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
print("expert-parallel MoE == dropless oracle: OK")

with mesh:
    y_seq, _ = jax.jit(
        lambda p_, x_: moe.moe_ffn_expert_parallel(
            p_, x_, num_experts=E, experts_per_tok=K, capacity_factor=16.0,
            seq_sharded=True)
    )(p, x)
np.testing.assert_allclose(np.asarray(y_seq), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
print("expert-parallel MoE (seq-sharded) == dropless oracle: OK")
print(f"aux ref={float(aux_ref):.4f} ep={float(aux_ep):.4f}")

# gradient flows
def loss_ep(p_, x_):
    y, aux = moe.moe_ffn_expert_parallel(p_, x_, num_experts=E,
                                         experts_per_tok=K,
                                         capacity_factor=16.0)
    return jnp.sum(y ** 2) + 0.01 * aux

g = jax.jit(jax.grad(loss_ep))(p, x)
assert all(bool(jnp.all(jnp.isfinite(l))) for l in jax.tree.leaves(g))
assert float(jnp.abs(g["wg"]).sum()) > 0
print("expert-parallel MoE gradients: OK")
