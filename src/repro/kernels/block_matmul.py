"""Tiled matmul Pallas kernel with parametric BlockSpec VMEM tiling.

This kernel is the *multi-version compilation target* of the VELTAIR
reproduction: the (bm, bk, bn) tile shape is the TPU locality knob (bigger
tiles => fewer HBM round-trips => higher arithmetic intensity, but a larger
VMEM working set), and the grid size is the parallelism knob.  The adaptive
compiler (repro.core.multiversion) enumerates tile variants and retains the
Pareto frontier; the runtime selects among them by interference level via
repro.kernels.dispatch.set_tile_overrides.

Grid: (M/bm, N/bn, K/bk) with K innermost; an fp32 VMEM scratch accumulates
partial products across K steps (revisiting output tiles is TPU-idiomatic:
the MXU consumes (bm,bk)x(bk,bn) blocks; accumulation stays on-chip).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _matmul_kernel(x_ref, w_ref, o_ref, acc_ref, *, k_steps: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(x_ref[...], w_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


def _pad_to(x: jax.Array, mult: tuple[int, int]) -> jax.Array:
    pads = [(0, (-x.shape[i]) % mult[i]) for i in range(2)]
    if any(p[1] for p in pads):
        x = jnp.pad(x, pads)
    return x


@functools.partial(jax.jit, static_argnames=("bm", "bk", "bn", "interpret"))
def block_matmul_2d(x: jax.Array, w: jax.Array, *, bm: int = 256,
                    bk: int = 512, bn: int = 256,
                    interpret: bool = False) -> jax.Array:
    """x (M,K) @ w (K,N) -> (M,N) with explicit VMEM tiling.

    Compiled (non-interpret) calls round every tile up to the chip's
    tiling first — bm to a multiple of 8, bk and bn to multiples of 128 —
    so any tile table (built-in levels, a compiled VersionSet, an
    autotuned ladder) compiles; interpret mode keeps the requested tiles
    so tests can exercise small ones."""
    m0, k0 = x.shape
    _, n0 = w.shape
    if not interpret:
        bm, bk, bn = _chip_tiles(bm, bk, bn)
    bm, bk, bn = min(bm, _ceil_mult(m0, 8)), min(bk, _ceil_mult(k0, 128)), \
        min(bn, _ceil_mult(n0, 128))
    xp = _pad_to(x, (bm, bk))
    wp = _pad_to(w, (bk, bn))
    m, k = xp.shape
    n = wp.shape[1]
    k_steps = k // bk
    out = pl.pallas_call(
        functools.partial(_matmul_kernel, k_steps=k_steps),
        grid=(m // bm, n // bn, k_steps),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, s: (i, s)),
            pl.BlockSpec((bk, bn), lambda i, j, s: (s, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, s: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="block_matmul",
    )(xp, wp)
    return out[:m0, :n0]


def _ceil_mult(v: int, mult: int) -> int:
    return ((v + mult - 1) // mult) * mult


def _chip_tiles(bm: int, bk: int, bn: int) -> tuple[int, int, int]:
    """(bm, bk, bn) rounded up to the TPU tiling: the x block (bm, bk)
    and w block (bk, bn) need their last two dims divisible by (8, 128)."""
    return _ceil_mult(bm, 8), _ceil_mult(bk, 128), _ceil_mult(bn, 128)


def vmem_bytes(bm: int, bk: int, bn: int, itemsize: int = 2) -> int:
    """VMEM working set of one grid step (x tile + w tile + fp32 acc)."""
    return bm * bk * itemsize + bk * bn * itemsize + bm * bn * 4
