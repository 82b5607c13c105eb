"""Pure-jnp oracles for every Pallas kernel (shape-for-shape reference)."""
from __future__ import annotations

import jax
import jax.numpy as jnp


def matmul_ref(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.einsum("...k,kn->...n", x, w)


def attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                  offset, kv_valid_len, window: int | None = None,
                  softcap: float | None = None) -> jax.Array:
    """Same contract as kernels.flash_attention (query i at offset+i;
    k/v heads-major (B, K, T, D))."""
    from repro.models.layers import attend
    b, s = q.shape[:2]
    qpos = jnp.asarray(offset, jnp.int32) + jnp.arange(s, dtype=jnp.int32)
    qpos = jnp.broadcast_to(qpos[None], (b, s))
    return attend(q, k, v, q_positions=qpos, kv_valid_len=kv_valid_len,
                  window=window, softcap=softcap, use_kernel_hook=False)


def ssd_ref(x, dt, a, b, c, *, chunk_size, initial_state=None):
    from repro.models.ssm import ssd_reference
    return ssd_reference(x, dt, a, b, c, chunk_size=chunk_size,
                         initial_state=initial_state)
