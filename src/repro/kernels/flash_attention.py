"""Causal GQA flash attention Pallas kernel (online softmax).

Layout is heads-major: q enters as (B, S, H, D) and is transposed to
(B, H, S, D) here (a query-sized copy); k/v are (B, K, T, D), the layout
the model's KV cache is stored in, so no per-call cache transpose
happens.  Every block is ``(1, 1, rows, D)``: its last two dims are
(rows, D) with D the full head dim, which the TPU compiler accepts for
any rows that is a multiple of 8 or the whole axis.

Grid: (B, H, Sq/bq, T/bkv) with the KV axis innermost; running max /
denominator / fp32 output accumulator live in VMEM scratch and persist
across KV steps (TPU grid iteration is sequential).  Supports:

  * GQA/MQA: kv head = query head // (H/K)  (via BlockSpec index_map)
  * causal masking with a query position offset (decode: offset = t);
    offset may be per-batch-row (continuous batching decodes every slot
    at its own absolute position)
  * sliding-window masking (starcoder2 / recurrentgemma local attention)
  * kv_valid_len: cache slots beyond the valid length are masked
    (scalar or per-batch-row)
  * logit softcap (tanh)

KV blocks that lie wholly past ``kv_valid_len`` or wholly above the
causal diagonal skip their compute (they would contribute exactly zero).

The (bq, bkv) block shape is a locality/parallelism knob exposed to the
adaptive compiler alongside the matmul tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.3819763e38


def _flash_kernel(scalars_ref, q_ref, k_ref, v_ref, o_ref,
                  m_ref, l_ref, acc_ref, *,
                  kv_steps: int, bq: int, bkv: int, scale: float,
                  window: int | None, softcap: float | None):
    bi = pl.program_id(0)
    offset = scalars_ref[0, bi]
    kv_valid = scalars_ref[1, bi]
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    k_first = ki * bkv
    q_last = offset + qi * bq + (bq - 1)

    @pl.when((k_first < kv_valid) & (k_first <= q_last))
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0, 0].astype(jnp.float32)                  # (bkv, D)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        q_pos = offset + qi * bq + jax.lax.broadcasted_iota(
            jnp.int32, (bq, bkv), 0)
        k_pos = k_first + jax.lax.broadcasted_iota(jnp.int32, (bq, bkv), 1)
        mask = (k_pos <= q_pos) & (k_pos < kv_valid)
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                  # (bq, 1)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        p = jnp.where(mask, p, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    @pl.when(ki == kv_steps - 1)
    def _flush():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / denom).astype(o_ref.dtype)


def _kv_block(bkv: int, t: int) -> int:
    """KV block size for a cache of ``t`` slots: the whole axis when it
    fits, else the largest multiple of 8 <= ``bkv`` that divides ``t``
    (so the cache is never padded, i.e. copied, per call).  Falls back to
    ``bkv`` itself — and a padded cache — only when no such divisor
    exists."""
    if t <= bkv:
        return t
    for cand in range(bkv - bkv % 8, 7, -8):
        if t % cand == 0:
            return cand
    return bkv


def _row_scalars(offset, kv_valid_len, b: int, t: int) -> jax.Array:
    """(2, B) int32 scalar-prefetch operand: per-row query offset and
    kv-valid horizon (scalars broadcast to every row)."""
    off = jnp.broadcast_to(jnp.asarray(offset, jnp.int32).reshape(-1), (b,))
    kvl = jnp.broadcast_to(
        jnp.minimum(jnp.asarray(kv_valid_len, jnp.int32), t).reshape(-1),
        (b,))
    return jnp.stack([off, kvl])


@functools.partial(
    jax.jit,
    static_argnames=("bq", "bkv", "window", "softcap", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    offset, kv_valid_len, bq: int = 512, bkv: int = 512,
                    window: int | None = None, softcap: float | None = None,
                    interpret: bool = False) -> jax.Array:
    """q (B,S,H,D); k/v (B,K,T,D) heads-major; query i of batch row b
    has absolute position offset[b]+i.  -> (B,S,H,D).

    offset / kv_valid_len may be traced int32 scalars or (B,) vectors
    (scalar-prefetched, broadcast to per-row).
    """
    b, s, h, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    g = h // kh
    bq = min(bq, s)
    bkv = _kv_block(bkv, t)
    # pad S (and, only when no block divides it, T) to block multiples;
    # extra kv is masked via kv_valid_len, extra q rows are discarded
    sp = ((s + bq - 1) // bq) * bq
    tp = ((t + bkv - 1) // bkv) * bkv
    qh = jnp.swapaxes(q, 1, 2)                                # (B,H,S,D)
    if sp != s:
        qh = jnp.pad(qh, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    if tp != t:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, tp - t), (0, 0)))
    kv_steps = tp // bkv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, sp // bq, kv_steps),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d),
                         lambda bi, hi, qi, ki, sc: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, sc: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bkv, d),
                         lambda bi, hi, qi, ki, sc: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda bi, hi, qi, ki, sc: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_flash_kernel, kv_steps=kv_steps, bq=bq, bkv=bkv,
                          scale=d ** -0.5, window=window, softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, sp, d), q.dtype),
        interpret=interpret,
        name="flash_attention",
    )(_row_scalars(offset, kv_valid_len, b, t), qh, k, v)
    return jnp.swapaxes(out[:, :, :s], 1, 2)


def _paged_flash_kernel(scalars_ref, table_ref, *rest, **kw):
    # the page table is consumed entirely by the KV BlockSpec index_maps;
    # the kernel body is the dense flash kernel (block ki IS logical page
    # ki, so its position arithmetic holds unchanged)
    return _flash_kernel(scalars_ref, *rest, **kw)


@functools.partial(jax.jit, static_argnames=("window", "softcap", "interpret"))
def flash_attention_paged(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                          page_table: jax.Array, *, offset, kv_valid_len,
                          window: int | None = None,
                          softcap: float | None = None,
                          interpret: bool = False) -> jax.Array:
    """Decode flash attention reading KV through a per-slot page table.

    q (B,S,H,D) with small S (decode: 1); k/v pools (P, K, page_size, D)
    heads-major, where P counts physical pages (index 0 is the pinned
    trash page); page_table (B, pages_per_slot) int32 maps each row's
    logical page to a physical one.  The table is the *second*
    scalar-prefetch operand — the KV BlockSpec index_map reads
    ``table[bi, ki]``, so each grid step DMAs exactly one physical page
    of one kv head and the kv block size is the page size.  Unallocated
    entries point at trash; their garbage keys sit at logical positions
    >= kv_valid and are masked like any invalid slot.
    """
    b, s, h, d = q.shape
    kh, ps_sz = k_pool.shape[1], k_pool.shape[2]
    g = h // kh
    n_slot = page_table.shape[1]
    t = n_slot * ps_sz
    table = page_table.astype(jnp.int32)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, h, 1, n_slot),
        in_specs=[
            pl.BlockSpec((1, 1, s, d),
                         lambda bi, hi, qi, ki, sc, tb: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, ps_sz, d),
                         lambda bi, hi, qi, ki, sc, tb: (tb[bi, ki],
                                                         hi // g, 0, 0)),
            pl.BlockSpec((1, 1, ps_sz, d),
                         lambda bi, hi, qi, ki, sc, tb: (tb[bi, ki],
                                                         hi // g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, s, d),
                               lambda bi, hi, qi, ki, sc, tb: (bi, hi, qi, 0)),
        scratch_shapes=[
            pltpu.VMEM((s, 1), jnp.float32),
            pltpu.VMEM((s, 1), jnp.float32),
            pltpu.VMEM((s, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_flash_kernel, kv_steps=n_slot, bq=s,
                          bkv=ps_sz, scale=d ** -0.5, window=window,
                          softcap=softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        interpret=interpret,
        name="flash_attention_paged",
    )(_row_scalars(offset, kv_valid_len, b, t), table,
      jnp.swapaxes(q, 1, 2), k_pool, v_pool)
    return jnp.swapaxes(out, 1, 2)
