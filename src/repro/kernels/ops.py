"""Jit'd wrappers around the Pallas kernels (the dispatch contract).

These adapt model-side calling conventions (leading batch dims, per-token
position arrays) to the kernels' layouts, and are what
``repro.kernels.dispatch`` routes to in "interpret"/"pallas" modes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels import block_matmul as _bm
from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd


def block_matmul(x: jax.Array, w: jax.Array, *, bm: int = 256, bk: int = 512,
                 bn: int = 256, interpret: bool = False) -> jax.Array:
    """x (..., K) @ w (K, N) with explicit VMEM tiling."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    out = _bm.block_matmul_2d(x2, w, bm=bm, bk=bk, bn=bn,
                              interpret=interpret)
    return out.reshape(*lead, w.shape[-1])


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    q_positions: jax.Array, kv_valid_len, window=None,
                    softcap=None, bq: int = 512, bkv: int = 512,
                    interpret: bool = False) -> jax.Array:
    """Adapter: models pass q_positions (B,S); the kernel takes a per-row
    offset with query i of row b at offset[b]+i (all our call sites use
    row-contiguous positions — prefill offset 0, decode offset t[b], which
    differs per slot under continuous batching)."""
    offset = q_positions[..., 0].reshape(-1)  # per-row first-query position
    return _fa.flash_attention(q, k, v, offset=offset,
                               kv_valid_len=kv_valid_len, bq=bq, bkv=bkv,
                               window=window, softcap=softcap,
                               interpret=interpret)


def flash_attention_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          *, page_table: jax.Array, q_positions: jax.Array,
                          kv_valid_len, window=None, softcap=None,
                          interpret: bool = False) -> jax.Array:
    """Adapter for the page-table decode kernel: k/v are physical page
    pools (P, K, page_size, D) and ``page_table`` (B, pages_per_slot)
    maps each row's logical pages.  No tile knob — the page size IS the
    kv block size (one page per DMA), so adaptive tile tables don't
    shape this op."""
    offset = q_positions[..., 0].reshape(-1)
    return _fa.flash_attention_paged(q, k_pool, v_pool, page_table,
                                     offset=offset,
                                     kv_valid_len=kv_valid_len,
                                     window=window, softcap=softcap,
                                     interpret=interpret)


def ssd_scan(x, dt, a, b, c, *, chunk_size: int = 256, initial_state=None,
             interpret: bool = False):
    return _ssd.ssd_scan(x, dt, a, b, c, chunk_size=chunk_size,
                         initial_state=initial_state, interpret=interpret)
