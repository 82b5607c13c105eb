"""Multi-head Latent Attention (DeepSeek-V2).

KV is compressed to a ``kv_lora_rank`` latent (plus a single shared RoPE key
head), which is what gets cached: 512+64 dims/token instead of
2*H*head_dim.  The form follows the call's shape:

  * expanded (prefill chunks, speculative verify: several query tokens)
               — cached latents are re-expanded through W_uk/W_uv into
               per-head keys and values, as the published model computes;
  * absorbed (decode steps and quanta: one query token per row) — W_uk is
               folded into the query and W_uv applied after attention, so
               attention reads only the 576-wide latents:
               O(H*T*(lora+rope)) per step instead of O(T*lora*H*(dn+dv)).

Rope follows the config: plain ``rope_theta``, or YaRN
(:func:`yarn_inv_freq`), which also scales the softmax
(:func:`softmax_scale`).  The rope columns use the rotate-half layout of
``layers.apply_rope``; the published checkpoint stores them interleaved
and permutes them before rotating, which for drawn weights is a
permutation of the ``wq``/``w_dkv`` rope columns.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import MLAConfig, ModelConfig, YarnConfig
from repro.models.layers import (NEG_INF, apply_norm, apply_rope,
                                 cache_layer, norm_specs, write_cache)
from repro.models.params import ParamSpec


# lanes of a TPU memory tile: the cached latent's width is rounded up to it
LANES = 128


def latent_width(mla: MLAConfig) -> int:
    """Width of a cached position: ``c_kv`` (r) and the rope key (dr),
    then zeros up to a multiple of LANES.  At a width the lanes do not
    divide (576) the TPU compiler lays the cache out position-minor as a
    program argument and width-minor inside the decode loop, and copies
    the whole cache between the two on the way in and out."""
    return -(-(mla.kv_lora_rank + mla.qk_rope_head_dim) // LANES) * LANES


def mla_specs(cfg: ModelConfig, mla: MLAConfig) -> dict:
    m, h = cfg.d_model, cfg.num_heads
    dn, dr, dv = mla.qk_nope_head_dim, mla.qk_rope_head_dim, mla.v_head_dim
    r = mla.kv_lora_rank
    return {
        "wq": ParamSpec((m, h, dn + dr), axes=("embed", "heads", "head_dim")),
        "w_dkv": ParamSpec((m, r + dr), axes=("embed", "kv_lora")),
        "kv_norm": norm_specs(cfg, r),
        "w_uk": ParamSpec((r, h, dn), axes=("kv_lora", "heads", "head_dim")),
        "w_uv": ParamSpec((r, h, dv), axes=("kv_lora", "heads", "head_dim")),
        "wo": ParamSpec((h, dv, m), axes=("heads", "head_dim", "embed")),
    }


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor ``0.1 * mscale * ln(factor) + 1``."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: YarnConfig) -> np.ndarray:
    """Rotary inverse frequencies (dim // 2,) under YaRN, as DeepSeek-V2's
    ``DeepseekV2YarnRotaryEmbedding`` computes them: pair ``i``'s
    frequency ``f_i = theta ** (-2i / dim)`` becomes
    ``f_i / factor * ramp_i + f_i * (1 - ramp_i)``, with ``ramp`` rising
    linearly from 0 at ``floor(corr(beta_fast))`` to 1 at
    ``ceil(corr(beta_slow))``, where ``corr(r) = dim * ln(L / (2 pi r)) /
    (2 ln theta)`` and ``L`` is ``original_max_position_embeddings``."""
    def corr(rot):
        return dim * math.log(yarn.original_max_position_embeddings
                              / (rot * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    freq = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return freq / yarn.factor * ramp + freq * (1.0 - ramp)


def softmax_scale(mla: MLAConfig) -> float:
    """``(dn + dr) ** -0.5``, times ``mscale(factor, mscale_all_dim) ** 2``
    under YaRN."""
    scale = (mla.qk_nope_head_dim + mla.qk_rope_head_dim) ** -0.5
    if mla.yarn is not None and mla.yarn.mscale_all_dim:
        scale *= yarn_mscale(mla.yarn.factor, mla.yarn.mscale_all_dim) ** 2
    return scale


def _rope(x, positions, cfg: ModelConfig, mla: MLAConfig):
    """Rotate ``x (B,S,heads,dr)`` at ``positions`` by the config's rope."""
    y = mla.yarn
    if y is None:
        return apply_rope(x, positions, cfg.rope_theta)
    dr = mla.qk_rope_head_dim
    out = apply_rope(x, positions, cfg.rope_theta,
                     inv_freq=yarn_inv_freq(dr, cfg.rope_theta, y))
    # cos and sin scaled by mscale(mscale) / mscale(mscale_all_dim)
    ratio = yarn_mscale(y.factor, y.mscale) / yarn_mscale(y.factor,
                                                          y.mscale_all_dim)
    return out if ratio == 1.0 else (out.astype(jnp.float32)
                                     * ratio).astype(out.dtype)


def _compress(params, x, cfg: ModelConfig, mla: MLAConfig, positions):
    """x -> (c_kv (B,S,r) normalized, k_rope (B,S,dr) rotated)."""
    r = mla.kv_lora_rank
    ckv_full = jnp.einsum("bsm,mr->bsr", x, params["w_dkv"].astype(x.dtype))
    c_kv = apply_norm(params["kv_norm"], ckv_full[..., :r], cfg.norm_type)
    k_rope = ckv_full[..., r:][:, :, None, :]          # one shared head
    return c_kv, _rope(k_rope, positions, cfg, mla)[:, :, 0]


def _queries(params, x, cfg: ModelConfig, mla: MLAConfig, positions):
    dn = mla.qk_nope_head_dim
    q = jnp.einsum("bsm,mhd->bshd", x, params["wq"].astype(x.dtype))
    return q[..., :dn], _rope(q[..., dn:], positions, cfg, mla)


def _mask(t: int, q_positions: jax.Array, kv_valid_len) -> jax.Array:
    j = jnp.arange(t)[None, None, :]
    mask = j <= q_positions[:, :, None]
    kvl = jnp.asarray(kv_valid_len)
    mask &= j < (kvl if kvl.ndim == 0 else kvl.reshape(-1, 1, 1))
    return mask                                        # (B,S,T)


def mla_attention(params: dict, x: jax.Array, *, cfg: ModelConfig,
                  positions: jax.Array, cache: dict | None = None,
                  cache_index: jax.Array | None = None,
                  page_table: jax.Array | None = None,
                  layer: jax.Array | None = None,
                  ) -> tuple[jax.Array, dict | None]:
    """MLA self-attention; cache = {"latent": (B,T,W)}: each position's
    normalized latent ``c_kv`` and its rotated rope key side by side in
    one leaf, zero-padded to ``W = latent_width(mla)`` (one layout, and
    decode scores both halves in one product).

    With ``page_table`` (B, pages_per_slot) the cached latents live in a
    physical page pool ``(n_pages + 1, page_size, W)``: decode scatters
    the new latent into its slot's page and gathers the full horizon
    through the table (latents are already memory-compressed, so the
    gather reference path is the paged MLA path — no kernel variant).
    With ``layer`` the cache leaf is the layer scan's whole stack, written
    in place as ``layers.attention`` writes it."""
    mla = cfg.mla
    b, s, m = x.shape
    r, dr = mla.kv_lora_rank, mla.qk_rope_head_dim
    scale = softmax_scale(mla)

    q_nope, q_rope = _queries(params, x, cfg, mla, positions)
    c_kv, k_rope = _compress(params, x, cfg, mla, positions)
    pad = latent_width(mla) - r - dr
    lat = jnp.concatenate([c_kv, k_rope.astype(c_kv.dtype),
                           jnp.zeros((b, s, pad), c_kv.dtype)], -1)

    if cache is None:
        lat_all, c_all, kv_len = lat, c_kv, s
        new_cache = None
    else:
        pool = write_cache(cache["latent"], lat, cache_index, seq_axis=1,
                           layer=layer, page_table=page_table)
        new_cache = {"latent": pool}
        kv_len = jnp.asarray(cache_index, jnp.int32) + s
        if page_table is None:
            # c_kv read through a slice of its own: decode's two products
            # each fuse their slice, and the layer is never copied out
            lat_all = cache_layer(pool, layer)
            c_all = cache_layer(pool, layer, r)
        else:
            kv_len = jnp.broadcast_to(kv_len.reshape(-1), (b,))
            lat_all = cache_layer(pool, layer)[page_table].reshape(
                b, -1, pool.shape[-1])
            c_all = lat_all[..., :r]
    t = lat_all.shape[1]
    w_uk = params["w_uk"].astype(x.dtype)
    w_uv = params["w_uv"].astype(x.dtype)

    if s == 1:
        # absorbed: one query per row scores the raw latents, [W_uk-folded
        # query, rope query] against [c_kv, k_rope] in one product; the
        # folded query and the latent context stay in float32
        def attn_chunk(q_nope_c, q_rope_c, pos_c):
            mask = _mask(t, pos_c, kv_len)[:, None]
            q_lat = jnp.einsum("bshd,rhd->bshr", q_nope_c.astype(jnp.float32),
                               w_uk.astype(jnp.float32))
            q_cat = jnp.concatenate(
                [q_lat, q_rope_c.astype(jnp.float32),
                 jnp.zeros(q_lat.shape[:-1] + (pad,), jnp.float32)], -1)
            scores = jnp.einsum("bshr,btr->bhst", q_cat,
                                lat_all.astype(jnp.float32))
            scores = jnp.where(mask, scores * scale, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            ctx = jnp.einsum("bhst,btr->bshr", probs,
                             c_all.astype(jnp.float32))
            return jnp.einsum("bshr,rhd->bshd", ctx,
                              w_uv.astype(jnp.float32)).astype(x.dtype)
    else:
        ckv32 = c_all.astype(jnp.float32)
        k_nope = jnp.einsum("btr,rhd->bthd", ckv32,
                            w_uk.astype(jnp.float32))         # (B,T,H,dn)
        v = jnp.einsum("btr,rhd->bthd", ckv32,
                       w_uv.astype(jnp.float32))              # (B,T,H,dv)
        k_full = jnp.concatenate(
            [k_nope, jnp.broadcast_to(lat_all[:, :, None, r:r + dr],
                                      (b, t, cfg.num_heads, dr))], axis=-1)

        def attn_chunk(q_nope_c, q_rope_c, pos_c):
            mask = _mask(t, pos_c, kv_len)[:, None]
            q = jnp.concatenate([q_nope_c, q_rope_c], axis=-1)
            scores = jnp.einsum("bshd,bthd->bhst", q.astype(jnp.float32),
                                k_full.astype(jnp.float32)) * scale
            scores = jnp.where(mask, scores, NEG_INF)
            probs = jax.nn.softmax(scores, axis=-1)
            return jnp.einsum("bhst,bthd->bshd", probs,
                              v.astype(jnp.float32)).astype(x.dtype)

    from repro.models import layers as _L
    from repro.models.layers import SCORE_CHUNK_ELEMS, _chunk_len
    if s * t <= SCORE_CHUNK_ELEMS or s == 1:
        y = attn_chunk(q_nope, q_rope, positions)
    else:
        cs = _chunk_len(s, t)
        n = s // cs

        def split(a):
            return jnp.moveaxis(a.reshape(b, n, cs, *a.shape[2:]), 1, 0)

        qn, qr, ps = split(q_nope), split(q_rope), split(positions)
        if _L.ANALYSIS_UNROLL:
            out = jnp.stack([attn_chunk(qn[i], qr[i], ps[i])
                             for i in range(n)])
        else:
            out = jax.lax.map(lambda args: attn_chunk(*args), (qn, qr, ps))
        y = jnp.moveaxis(out, 0, 1).reshape(b, s, cfg.num_heads, -1)
    out = jnp.einsum("bshd,hdm->bsm", y, params["wo"].astype(x.dtype))
    return out, new_cache
