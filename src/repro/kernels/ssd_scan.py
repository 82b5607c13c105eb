"""Mamba-2 SSD chunked-scan Pallas kernel.

Grid: (B, H, L/Q) with the chunk axis innermost.  The running SSD state
(P, N) lives in VMEM scratch and carries across chunk steps — TPU grid
iteration is sequential, so the inter-chunk recurrence needs no extra pass.
Per chunk the work is three small MXU matmuls ((Q,N)x(N,Q), (Q,Q)x(Q,P),
(N,Q)x(Q,P)): the "duality" that makes SSDs MXU-friendly.

Layout is heads-major: x/b/c are transposed to (B, H, L, ·) here, so every
block is ``(1, 1, Q, ·)`` whose last two dims are (Q, full width) — the
shape the TPU compiler accepts.  The per-position log-decay prefix sums
``seg`` are computed outside the kernel (the TPU kernel language has no
cumsum) and enter twice: as a (Q, 1) column and as a (1, Q) row, the two
orientations the intra-chunk decay matrix exp(seg_i - seg_j) needs.  The
step size dt is folded into B (``C (dt*B)^T == (C B^T) diag(dt)``), so it
enters only as a column.

The chunk size Q trades VMEM locality (larger intra-chunk matmuls, fewer
state round-trips) against parallel grid width — the SSD variant knob used
by the adaptive compiler for the mamba2/recurrentgemma cells.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_CONTRACT_LAST = (((1,), (1,)), ((), ()))    # a (m,k), b (n,k) -> (m,n)
_CONTRACT_FIRST = (((0,), (0,)), ((), ()))   # a (k,m), b (k,n) -> (m,n)


def _ssd_kernel(x_ref, segc_ref, segr_ref, dtc_ref, b_ref, c_ref, h0_ref,
                y_ref, state_ref, h_scratch, *, n_chunks: int, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        h_scratch[...] = h0_ref[0, 0].astype(jnp.float32)

    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)
    seg_c = segc_ref[0, 0]                           # (Q, 1) inclusive
    seg_r = segr_ref[0, 0, 0]                        # (1, Q) same values
    dt_c = dtc_ref[0, 0]                             # (Q, 1)
    bm = b_ref[0, 0].astype(jnp.float32)             # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)             # (Q, N)
    # (1, 1) chunk decay = seg at the last position, picked by a masked
    # sum (exact: every other term is 0) rather than a lane-offset slice
    last = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1) == q - 1
    total = jnp.sum(jnp.where(last, seg_r, 0.0), axis=1, keepdims=True)

    # intra-chunk (attention-like masked matmul)
    i_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    j_pos = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    gate = jnp.where(j_pos <= i_pos, jnp.exp(seg_c - seg_r), 0.0)
    cb = jax.lax.dot_general(cm, bm * dt_c, _CONTRACT_LAST,
                             preferred_element_type=jnp.float32)  # (Q,Q)
    y = jnp.dot(cb * gate, x, preferred_element_type=jnp.float32)  # (Q,P)

    # inter-chunk: y += exp(seg_i) * C_i . h_in   (h (P,N))
    h = h_scratch[...]
    y += jnp.exp(seg_c) * jax.lax.dot_general(
        cm, h, _CONTRACT_LAST, preferred_element_type=jnp.float32)

    # state update: h' = exp(total) h + X^T (w * B),  w_j = exp(total-seg_j)dt_j
    w = jnp.exp(total - seg_c) * dt_c                # (Q, 1)
    h_scratch[...] = jnp.exp(total) * h + jax.lax.dot_general(
        x, bm * w, _CONTRACT_FIRST, preferred_element_type=jnp.float32)

    y_ref[0, 0] = y.astype(y_ref.dtype)

    @pl.when(ci == n_chunks - 1)
    def _flush():
        state_ref[0, 0] = h_scratch[...]


@functools.partial(jax.jit, static_argnames=("chunk_size", "interpret"))
def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
             c: jax.Array, *, chunk_size: int = 256,
             initial_state: jax.Array | None = None,
             interpret: bool = False) -> tuple[jax.Array, jax.Array]:
    """x (B,L,H,P); dt (B,L,H) fp32; a (H,) fp32; b/c (B,L,H,N).

    -> (y (B,L,H,P), final_state (B,H,P,N) fp32).  L is padded to a chunk
    multiple with dt=0 (exact: zero step contributes nothing, decay 1)."""
    bsz, l, h, p = x.shape
    n = b.shape[-1]
    q = min(chunk_size, l)
    orig_l = l
    if l % q:
        pad = q - l % q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0), (0, 0)))
        l = x.shape[1]
    n_chunks = l // q
    h0 = (jnp.zeros((bsz, h, p, n), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))

    # heads-major per-position scalars: dt and the in-chunk inclusive
    # prefix sums of dt*a, as (B,H,L,1) columns and (B,H,NC,1,Q) rows
    dth = jnp.swapaxes(dt.astype(jnp.float32), 1, 2)          # (B,H,L)
    seg = jnp.cumsum(
        (dth * a.astype(jnp.float32)[None, :, None]).reshape(
            bsz, h, n_chunks, q), axis=-1)                    # (B,H,NC,Q)
    seg_c = seg.reshape(bsz, h, l, 1)
    seg_r = seg.reshape(bsz, h, n_chunks, 1, q)
    dt_c = dth.reshape(bsz, h, l, 1)

    def heads_major(t):
        return jnp.swapaxes(t, 1, 2)                          # (B,H,L,·)

    y, state = pl.pallas_call(
        functools.partial(_ssd_kernel, n_chunks=n_chunks, q=q),
        grid=(bsz, h, n_chunks),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, 1, 1, q),
                         lambda bi, hi, ci: (bi, hi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, 1), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, p, n), lambda bi, hi, ci: (bi, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, h, l, p), x.dtype),
            jax.ShapeDtypeStruct((bsz, h, p, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        interpret=interpret,
        name="ssd_scan",
    )(heads_major(x), seg_c, seg_r, dt_c, heads_major(b), heads_major(c), h0)
    return jnp.swapaxes(y, 1, 2)[:, :orig_l], state
