"""Plain float32 reference of StarCoder2 (arXiv:2402.19173).

Decoder-only transformer, as the published Hugging Face ``Starcoder2``
model computes it: token embedding; per layer a pre-LayerNorm
grouped-query attention with rotary position embedding (rotate-half
convention, base ``rope_theta``) over a causal ``sliding_window``, then a
pre-LayerNorm MLP ``c_proj(gelu_tanh(c_fc(x)))``, each added to the
residual; a final LayerNorm; logits against the tied embedding.

Everything runs in float32 with exact matmuls (``common.HI``), the
residual stream included, with no cache, batching or kernels: one full
causal forward over each whole sequence, layer by layer so it fits.

Departures from the published model, both as the configuration states:
``use_bias`` false (the attention projections carry no bias; the MLP
biases are kept and read from the weights) and ``norm_epsilon``.

Weights (float32 or bfloat16), layers stacked on a leading axis L:
``embedding (V, D)``, ``final_w``/``final_b (D,)``, ``ln1_w``/``ln1_b``/
``ln2_w``/``ln2_b (L, D)``, ``wq (L, D, H, hd)``, ``wk``/``wv
(L, D, K, hd)``, ``wo (L, H, hd, D)``, ``w_up (L, D, F)``, ``b_up (L, F)``,
``w_down (L, F, D)``, ``b_down (L, D)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.common import HI, layer_norm, linear

LAYER_KEYS = ("ln1_w", "ln1_b", "wq", "wk", "wv", "wo", "ln2_w", "ln2_b",
              "w_up", "b_up", "w_down", "b_down")


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def rope(x, theta: float):
    """x (B, T, heads, hd) at positions 0..T-1, rotate-half convention."""
    t, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(cfg: tuple, stacked: dict, i, x, quant: bool):
    eps, theta, window = cfg
    w = {k: jax.lax.dynamic_index_in_dim(stacked[k], i, 0, keepdims=False)
         .astype(jnp.float32) for k in LAYER_KEYS}
    _, t, _ = x.shape
    h = layer_norm(x, w["ln1_w"], w["ln1_b"], eps)
    q = rope(linear(h, w["wq"], 1, quant), theta)         # (B,T,H,hd)
    k = rope(linear(h, w["wk"], 1, quant), theta)         # (B,T,K,hd)
    v = linear(h, w["wv"], 1, quant)
    heads, kv = q.shape[2], k.shape[2]
    rep = heads // kv                                     # query head h reads
    k = jnp.repeat(k, rep, axis=2)                        # kv head h // rep
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bihd,bjhd->bhij", q, k, precision=HI) \
        / math.sqrt(q.shape[-1])
    i_pos = jnp.arange(t)[:, None]
    j_pos = jnp.arange(t)[None, :]
    allowed = (j_pos <= i_pos) & (j_pos > i_pos - window)
    s = jnp.where(allowed[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", p, v, precision=HI)
    x = x + linear(o, w["wo"], 2, quant)
    h = layer_norm(x, w["ln2_w"], w["ln2_b"], eps)
    m = gelu_tanh(linear(h, w["w_up"], 1, quant) + w["b_up"])
    return x + linear(m, w["w_down"], 1, quant) + w["b_down"]


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(eps: float, wts: dict, x, emb, quant: bool):
    h = layer_norm(x, wts["final_w"], wts["final_b"], eps)
    return linear(h, emb.T, 1, quant)


def logits(spec: dict, weights: dict, tokens, quant: bool = False):
    """Logits (B, T, V) float32 at every position of ``tokens (B, T)``."""
    cfg = (float(spec["norm_epsilon"]), float(spec["rope_theta"]),
           int(spec.get("sliding_window") or 1 << 30))
    emb = weights["embedding"]
    x = jnp.take(emb, tokens, axis=0).astype(jnp.float32)
    stacked = {k: weights[k] for k in LAYER_KEYS}
    for i in range(int(spec["num_hidden_layers"])):
        x = _layer(cfg, stacked, jnp.int32(i), x, quant)
    return _head(cfg[0], {"final_w": weights["final_w"],
                          "final_b": weights["final_b"]},
                 x, emb, quant)
