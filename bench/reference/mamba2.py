"""Plain float32 reference of Mamba-2 (arXiv:2405.21060).

Attention-free stack of Mamba-2 blocks, as the published ``mamba_ssm``
``Mamba2`` mixer computes them: per layer ``x + out_proj(norm(y *
silu(z)))`` where ``in_proj(rms_norm(x))`` splits into ``z``, ``xBC`` and
``dt``; ``xBC`` passes a depthwise causal conv of width ``d_conv`` (with
bias) and SiLU and splits into ``x``, ``B``, ``C`` (``ngroups`` groups
shared by the heads); ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``
and, per head, the selective state-space recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T,   y_t = h_t C_t + D x_t

run step by step over the sequence (not the chunked SSD form); a final
RMSNorm and logits against the tied embedding.

Everything runs in float32 with exact matmuls, the residual stream
included (the published ``residual_in_fp32``), with no cache or kernels.
Departures from the published model, as the configuration states:
``norm_epsilon``, and ``vocab_size`` (the padded table as run).

Weights, layers stacked on a leading axis L: ``embedding (V, D)``,
``final_w (D,)``, ``norm_w (L, D)``, ``in_proj (L, D, 2*di + 2*G*N + H)``,
``conv_w (L, d_conv, di + 2*G*N)``, ``conv_b (L, di + 2*G*N)``,
``dt_bias``/``A_log``/``D (L, H)``, ``gate_norm_w (L, di)``,
``out_proj (L, di, D)``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench.reference.common import HI, linear, rms_norm

LAYER_KEYS = ("norm_w", "in_proj", "conv_w", "conv_b", "dt_bias", "A_log",
              "D", "gate_norm_w", "out_proj")


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(cfg: tuple, stacked: dict, i, x, quant: bool):
    eps, di, groups, n, heads, p = cfg
    w = {k: jax.lax.dynamic_index_in_dim(stacked[k], i, 0, keepdims=False)
         .astype(jnp.float32) for k in LAYER_KEYS}
    b, t, _ = x.shape
    zxbcdt = linear(rms_norm(x, w["norm_w"], eps), w["in_proj"], 1, quant)
    z = zxbcdt[..., :di]
    xbc = zxbcdt[..., di:2 * di + 2 * groups * n]
    dt = zxbcdt[..., 2 * di + 2 * groups * n:]
    width = w["conv_w"].shape[0]
    xp = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    xbc = sum(xp[:, j:j + t] * w["conv_w"][j] for j in range(width)) \
        + w["conv_b"]
    xbc = jax.nn.silu(xbc)
    xs = xbc[..., :di].reshape(b, t, heads, p)
    rep = heads // groups                      # head h reads group h // rep
    bm = jnp.repeat(xbc[..., di:di + groups * n].reshape(b, t, groups, n),
                    rep, axis=2)
    cm = jnp.repeat(xbc[..., di + groups * n:].reshape(b, t, groups, n),
                    rep, axis=2)
    dt = jax.nn.softplus(dt + w["dt_bias"])                # (B, T, H)
    a = -jnp.exp(w["A_log"])

    def step(h, inp):
        x_t, b_t, c_t, dt_t = inp
        h = jnp.exp(dt_t * a)[:, :, None, None] * h \
            + (dt_t[:, :, None] * x_t)[..., None] * b_t[:, :, None, :]
        y = jnp.einsum("bhpn,bhn->bhp", h, c_t, precision=HI)
        return h, y

    h0 = jnp.zeros((b, heads, p, n), jnp.float32)
    _, y = jax.lax.scan(step, h0, (jnp.moveaxis(xs, 1, 0),
                                   jnp.moveaxis(bm, 1, 0),
                                   jnp.moveaxis(cm, 1, 0),
                                   jnp.moveaxis(dt, 1, 0)))
    y = jnp.moveaxis(y, 0, 1) + w["D"][None, None, :, None] * xs
    y = y.reshape(b, t, di) * jax.nn.silu(z)
    y = rms_norm(y, w["gate_norm_w"], eps)
    return x + linear(y, w["out_proj"], 1, quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(eps: float, final_w, x, emb, quant: bool):
    return linear(rms_norm(x, final_w, eps), emb.T, 1, quant)


def logits(spec: dict, weights: dict, tokens, quant: bool = False):
    """Logits (B, T, V) float32 at every position of ``tokens (B, T)``."""
    d = int(spec["d_model"])
    di = int(spec["expand"]) * d
    p = int(spec["headdim"])
    cfg = (float(spec["norm_epsilon"]), di, int(spec["ngroups"]),
           int(spec["d_state"]), di // p, p)
    emb = weights["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
    stacked = {k: weights[k] for k in LAYER_KEYS}
    for i in range(int(spec["n_layer"])):
        x = _layer(cfg, stacked, jnp.int32(i), x, quant)
    return _head(cfg[0], weights["final_w"], x, emb,
                 quant)
