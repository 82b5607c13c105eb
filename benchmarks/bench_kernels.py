"""Kernel microbenchmarks: correctness vs oracle + interpret-mode timing.

Interpret-mode wall times are NOT TPU performance (the kernel body runs in
Python); the perf-relevant numbers are the structural ones — VMEM working
set per tile variant and arithmetic intensity — which feed the adaptive
compiler's version space.
"""
from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ops, ref
from repro.kernels.block_matmul import vmem_bytes


def bench_matmul_variants():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 512)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
    want = np.asarray(ref.matmul_ref(x, w))
    for bm, bk, bn in ((32, 64, 32), (64, 128, 64), (128, 256, 128)):
        t0 = time.time()
        got = ops.block_matmul(x, w, bm=bm, bk=bk, bn=bn, interpret=True)
        us = (time.time() - t0) * 1e6
        err = float(np.max(np.abs(np.asarray(got) - want)))
        flops = 2 * 256 * 512 * 256
        vmem = vmem_bytes(bm, bk, bn, 4)
        emit(f"kernel.matmul.{bm}x{bk}x{bn}", us,
             f"max_err={err:.2e};vmem_tile_bytes={vmem};"
             f"intensity={flops / max(vmem, 1):.1f}")


def bench_flash_attention():
    rng = np.random.default_rng(1)
    B, S, H, K, D = 2, 64, 4, 2, 32
    q = jnp.asarray(rng.standard_normal((B, S, H, D)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, K, S, D)), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    want = np.asarray(ref.attention_ref(q, k, v, offset=0, kv_valid_len=S))
    for bq, bkv in ((16, 16), (32, 32)):
        t0 = time.time()
        got = ops.flash_attention(q, k, v, q_positions=qpos, kv_valid_len=S,
                                  bq=bq, bkv=bkv, interpret=True)
        us = (time.time() - t0) * 1e6
        err = float(np.max(np.abs(np.asarray(got) - want)))
        emit(f"kernel.flash.bq{bq}_bkv{bkv}", us, f"max_err={err:.2e}")


def bench_ssd():
    rng = np.random.default_rng(2)
    B, L, H, P, N = 2, 64, 2, 16, 8
    x = jnp.asarray(rng.standard_normal((B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    bmat = jnp.asarray(rng.standard_normal((B, L, H, N)), jnp.float32)
    cmat = jnp.asarray(rng.standard_normal((B, L, H, N)), jnp.float32)
    yref, sref = ref.ssd_ref(x, dt, a, bmat, cmat, chunk_size=8)
    for chunk in (8, 16, 32):
        t0 = time.time()
        y, s = ops.ssd_scan(x, dt, a, bmat, cmat, chunk_size=chunk,
                            interpret=True)
        us = (time.time() - t0) * 1e6
        err = float(np.max(np.abs(np.asarray(y) - np.asarray(yref))))
        emit(f"kernel.ssd.chunk{chunk}", us, f"max_err={err:.2e}")


def run_all():
    bench_matmul_variants()
    bench_flash_attention()
    bench_ssd()


if __name__ == "__main__":
    print("name,us_per_call,derived")
    run_all()
