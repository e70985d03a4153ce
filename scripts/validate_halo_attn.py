"""Validate halo-exchange local attention against the plain sliding-window
oracle on an 8-device host mesh (separate process)."""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import attention as attn

mesh = jax.make_mesh((2, 4), ("data", "model"))
attn.set_halo_mesh(mesh)

B, S, d, H, KV, hd, W = 2, 64, 32, 4, 2, 8, 8
assert attn.halo_attn_available(S, W, 4)
p = attn.init_attn(jax.random.PRNGKey(0), d, H, KV, hd, True, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d)) * 0.5
positions = jnp.arange(S)

y_ref = attn.attn_forward(p, x, positions, num_heads=H, num_kv_heads=KV,
                          head_dim=hd, window=W, rope_theta=1e4, use_rope=True)

with mesh:
    y_halo, k, v = jax.jit(
        lambda p_, x_: attn.attn_forward_halo(
            p_, x_, num_heads=H, num_kv_heads=KV, head_dim=hd, window=W,
            rope_theta=1e4, use_rope=True, return_kv=True))(p, x)

np.testing.assert_allclose(np.asarray(y_halo), np.asarray(y_ref),
                           rtol=2e-4, atol=2e-4)
print("halo attention == sliding-window oracle: OK")

# gradient flows through the ppermute
g = jax.grad(lambda x_: jnp.sum(attn.attn_forward_halo(
    p, x_, num_heads=H, num_kv_heads=KV, head_dim=hd, window=W,
    rope_theta=1e4, use_rope=True) ** 2))(x)
assert bool(jnp.all(jnp.isfinite(g)))
print("halo attention gradients: OK")
