"""Plain float32 reference of DeepSeek-V2 (arXiv:2405.04434), as the
published Hugging Face ``DeepseekV2`` model computes it, for one chip's
share of its routed experts.

Token embedding; per layer a pre-RMSNorm multi-head latent attention
(MLA) in its expanded form, then a pre-RMSNorm feed-forward block, each
added to the residual; a final RMSNorm; logits against the untied output
matrix.  Attention: ``q = h Wq`` split into ``q_nope (dn)`` and ``q_rope
(dr)``; ``[c, k_rope] = h Wdkv`` with ``c`` RMS-normed; keys
``[c Wuk, k_rope]`` (one rope key shared by every head) and values ``c
Wuv``; causal softmax at scale ``(dn + dr) ** -0.5 * mscale ** 2``, rope
scaled by YaRN (both written out below from the paper's formulas).  The
first layer's block is a dense SwiGLU; the others are MoE: a softmax
router over all routed experts, the top-k probabilities as gates
(divided by their sum where ``norm_topk_prob`` says so; DeepSeek-V2-Lite
does not; routed scaling factor 1), of which the experts held here add
``gate * expert(h)``, plus the shared experts.  The experts held elsewhere
are left out, as in the program.

Everything runs in float32 with exact matmuls (``common.HI``), the
residual stream included, with no cache, batching or kernels: one full
causal forward over each whole sequence, layer by layer and one held
expert at a time, so it fits.

Layout: the rope columns of ``wq`` and ``w_dkv`` are in the program's
rotate-half order; the published checkpoint stores them interleaved and
permutes them before rotating, which for drawn weights is a relabelling
of those columns.

Weights (float32 or bfloat16): ``embedding (V, D)``, ``unembed (D, V)``,
``final_w (D,)``; ``dense`` (layer 0) and ``moe`` (layers 1.., stacked on
a leading axis L) each hold ``ln1``, ``ln2 (D,)``, ``wq (D, H, dn + dr)``,
``w_dkv (D, r + dr)``, ``kv_norm (r,)``, ``w_uk (r, H, dn)``, ``w_uv (r,
H, dv)``, ``wo (H, dv, D)``; ``dense`` also ``w_gate``/``w_up (D, F)``,
``w_down (F, D)``; ``moe`` also ``router (D, E)``, the held experts'
``e_gate``/``e_up (X, D, f)``, ``e_down (X, f, D)`` and the fused shared
experts' ``s_gate``/``s_up (D, S)``, ``s_down (S, D)``.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from bench.reference.common import HI, linear, rms_norm

ATTN_KEYS = ("ln1", "wq", "w_dkv", "kv_norm", "w_uk", "w_uv", "wo", "ln2")
MOE_KEYS = ATTN_KEYS + ("router", "e_gate", "e_up", "e_down", "s_gate",
                        "s_up", "s_down")


def yarn(spec: dict) -> tuple[jax.Array, float, float]:
    """(inverse frequencies (dr/2,), cos/sin factor, softmax scale) of
    ``rope_scaling`` type yarn: pair i's frequency ``theta^(-2i/dr)`` is
    divided by ``factor`` in proportion ``ramp_i``, a linear ramp from 0 at
    ``floor(corr(beta_fast))`` to 1 at ``ceil(corr(beta_slow))``, with
    ``corr(n) = dr ln(L / (2 pi n)) / (2 ln theta)``; ``mscale(s, m) =
    0.1 m ln(s) + 1``."""
    rs, theta = spec["rope_scaling"], float(spec["rope_theta"])
    dr, s = int(spec["qk_rope_head_dim"]), float(rs["factor"])
    big_l = float(rs["original_max_position_embeddings"])

    def corr(n):
        return dr * math.log(big_l / (2 * math.pi * n)) / (2 * math.log(theta))

    def mscale(m):
        return 0.1 * m * math.log(s) + 1.0 if s > 1 else 1.0
    lo = max(math.floor(corr(float(rs["beta_fast"]))), 0)
    hi = min(math.ceil(corr(float(rs["beta_slow"]))), dr - 1)
    i = jnp.arange(dr // 2, dtype=jnp.float32)
    ramp = jnp.clip((i - lo) / max(hi - lo, 1e-3), 0.0, 1.0)
    freq = theta ** (-2.0 * i / dr)
    inv = freq / s * ramp + freq * (1.0 - ramp)
    dn = int(spec["qk_nope_head_dim"])
    scale = (dn + dr) ** -0.5 * mscale(float(rs["mscale_all_dim"])) ** 2
    return inv, mscale(float(rs["mscale"])) / mscale(
        float(rs["mscale_all_dim"])), scale


def rope(x, inv, factor: float):
    """x (B, T, heads, dr) at positions 0..T-1, rotate-half convention."""
    t, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = factor * jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = factor * jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _attention(cfg: tuple, w: dict, x, quant: bool):
    eps, dn, r, inv, factor, scale = cfg
    inv = jnp.asarray(inv, jnp.float32)
    t = x.shape[1]
    h = rms_norm(x, w["ln1"], eps)
    q = linear(h, w["wq"], 1, quant)                      # (B,T,H,dn+dr)
    ckv = linear(h, w["w_dkv"], 1, quant)                 # (B,T,r+dr)
    c = rms_norm(ckv[..., :r], w["kv_norm"], eps)
    k_rope = rope(ckv[..., r:][:, :, None, :], inv, factor)
    q_rope = rope(q[..., dn:], inv, factor)
    k_nope = linear(c, w["w_uk"], 1, quant)               # (B,T,H,dn)
    v = linear(c, w["w_uv"], 1, quant)                    # (B,T,H,dv)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(
        k_rope, k_nope.shape[:3] + k_rope.shape[-1:])], -1)
    qq = jnp.concatenate([q[..., :dn], q_rope], -1)
    s = jnp.einsum("bihd,bjhd->bhij", qq, k, precision=HI) * scale
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("bhij,bjhd->bihd", p, v, precision=HI)
    return x + linear(o, w["wo"], 2, quant)


def _swiglu(h, wg, wu, wd, quant: bool):
    return linear(jax.nn.silu(linear(h, wg, 1, quant))
                  * linear(h, wu, 1, quant), wd, 1, quant)


def _f32(w: dict) -> dict:
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnums=(0, 3))
def _dense_layer(cfg: tuple, w: dict, x, quant: bool):
    w = _f32(w)
    x = _attention(cfg, w, x, quant)
    h = rms_norm(x, w["ln2"], cfg[0])
    return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"], quant)


def moe_ffn(h, w: dict, route: tuple, quant: bool):
    """An MoE layer's feed-forward output for normed inputs ``h``: the
    held experts' part of the routed sum (held expert ``j`` is routed
    expert ``first + j``) plus the shared experts; expert weights are cast
    one expert at a time.  ``route`` = (top_k, first, norm_topk_prob)."""
    top_k, first, norm = route
    probs = jax.nn.softmax(linear(h, w["router"], 1, quant), axis=-1)
    gates, picks = jax.lax.top_k(probs, top_k)            # (..., k)
    if norm:
        gates = gates / jnp.sum(gates, -1, keepdims=True)

    def expert(j, out):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(
            w[k], j, 0, keepdims=False).astype(jnp.float32)
            for k in ("e_gate", "e_up", "e_down"))
        gate = jnp.sum(jnp.where(picks == first + j, gates, 0.0), -1)
        return out + gate[..., None] * _swiglu(h, wg, wu, wd, quant)
    routed = jax.lax.fori_loop(0, w["e_gate"].shape[0], expert,
                               jnp.zeros_like(h))
    shared = _swiglu(h, w["s_gate"].astype(jnp.float32),
                     w["s_up"].astype(jnp.float32),
                     w["s_down"].astype(jnp.float32), quant)
    return routed + shared


@functools.partial(jax.jit, static_argnums=(0, 1, 5))
def _moe_layer(cfg: tuple, route: tuple, stacked: dict, i, x, quant: bool):
    w = {k: jax.lax.dynamic_index_in_dim(stacked[k], i, 0, keepdims=False)
         for k in MOE_KEYS}
    attn = _f32({k: w[k] for k in ATTN_KEYS})
    x = _attention(cfg, attn, x, quant)
    h = rms_norm(x, attn["ln2"], cfg[0])
    return x + moe_ffn(h, w, route, quant)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _head(eps: float, final_w, x, unembed, quant: bool):
    return linear(rms_norm(x, final_w, eps), unembed, 1, quant)


def logits(spec: dict, weights: dict, tokens, quant: bool = False):
    """Logits (B, T, V) float32 at every position of ``tokens (B, T)``."""
    if spec["routed_scaling_factor"] != 1:
        raise ValueError("the reference scales routed outputs by 1")
    inv, factor, scale = yarn(spec)
    eps = float(spec["rms_norm_eps"])
    cfg = (eps, int(spec["qk_nope_head_dim"]), int(spec["kv_lora_rank"]),
           tuple(float(v) for v in inv), factor, scale)
    x = jnp.take(weights["embedding"], tokens, axis=0).astype(jnp.float32)
    x = _dense_layer(cfg, weights["dense"], x, quant)
    route = (int(spec["num_experts_per_tok"]), int(spec["first_held_expert"]),
             bool(spec["norm_topk_prob"]))
    for i in range(int(spec["num_hidden_layers"]) - 1):
        x = _moe_layer(cfg, route, weights["moe"], jnp.int32(i), x, quant)
    return _head(eps, weights["final_w"], x, weights["unembed"], quant)
