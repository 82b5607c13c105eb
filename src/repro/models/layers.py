"""Common model layers (pure JAX, functional).

Every layer is a pair of functions:
  ``*_specs(cfg) -> pytree[ParamSpec]``   parameter declaration
  ``apply(params, x, ...) -> y``          application

Conventions:
  x           (B, S, M)    activations, bf16
  q           (B, S, H, D)
  k, v        (B, K, T, D) K = kv heads; heads-major, the KV cache layout
              the Pallas attention kernels read without a transpose
  positions   (B, S) int32, or (3, B, S) for M-RoPE
  softmax / norms / rope run in fp32 and cast back.

Attention math lives here as the XLA reference path; the Pallas flash kernel
(repro.kernels) is validated against it and selected via repro.kernels.dispatch.
"""
from __future__ import annotations

import math
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.params import ParamSpec

NEG_INF = -2.3819763e38  # large negative, bf16-safe after cast


# --------------------------------------------------------------------------
# Norms
# --------------------------------------------------------------------------
def norm_specs(cfg: ModelConfig, width: int | None = None) -> dict:
    w = width or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": ParamSpec((w,), jnp.float32, ("embed",), init="ones"),
                "bias": ParamSpec((w,), jnp.float32, ("embed",), init="zeros")}
    return {"scale": ParamSpec((w,), jnp.float32, ("embed",), init="ones")}


def apply_norm(params: dict, x: jax.Array, norm_type: str = "rmsnorm",
               eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    if norm_type == "layernorm":
        mean = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.var(xf, axis=-1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps)
        out = out * params["scale"] + params["bias"]
    else:
        ms = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        out = xf * jax.lax.rsqrt(ms + eps) * params["scale"]
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# Rotary embeddings (RoPE and qwen2-vl M-RoPE)
# --------------------------------------------------------------------------
def _rope_angles(positions: jax.Array, head_dim: int, theta: float,
                 mrope_sections: tuple[int, int, int] | None) -> jax.Array:
    """-> (B, S, D/2) fp32 angles."""
    half = head_dim // 2
    freq_idx = jnp.arange(half, dtype=jnp.float32)
    inv_freq = theta ** (-2.0 * freq_idx / head_dim)   # (half,)
    if mrope_sections is None:
        pos = positions.astype(jnp.float32)            # (B, S)
        return pos[..., None] * inv_freq               # (B, S, half)
    # M-RoPE: positions (3, B, S) for (t, h, w); frequency bands are assigned
    # to sections [0:s0] -> t, [s0:s0+s1] -> h, rest -> w.
    s0, s1, s2 = mrope_sections
    assert s0 + s1 + s2 == half, (mrope_sections, half)
    posf = positions.astype(jnp.float32)               # (3, B, S)
    sel = jnp.concatenate([
        jnp.zeros((s0,), jnp.int32),
        jnp.ones((s1,), jnp.int32),
        jnp.full((s2,), 2, jnp.int32)])                # (half,)
    pos_sel = jnp.take(posf, sel, axis=0)              # (half, B, S)
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)             # (B, S, half)
    return pos_sel * inv_freq


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: tuple[int, int, int] | None = None) -> jax.Array:
    """x: (B, S, H, D). Split-halves convention (llama / gemma)."""
    d = x.shape[-1]
    ang = _rope_angles(positions, d, theta, mrope_sections)  # (B,S,half)
    sin = jnp.sin(ang)[:, :, None, :]
    cos = jnp.cos(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_pe(positions: jax.Array, width: int) -> jax.Array:
    """(B, S) -> (B, S, width) fp32 sinusoidal position encoding."""
    half = width // 2
    freq = jnp.exp(-math.log(10000.0)
                   * jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


# --------------------------------------------------------------------------
# Attention (GQA / MQA / MHA; causal; optional sliding window)
# --------------------------------------------------------------------------
def attention_specs(cfg: ModelConfig) -> dict:
    m, h, k, d = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    return {
        "wq": ParamSpec((m, h, d), axes=("embed", "heads", "head_dim")),
        "wk": ParamSpec((m, k, d), axes=("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((m, k, d), axes=("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((h, d, m), axes=("heads", "head_dim", "embed")),
    }


# max fp32 score elements per (q-chunk x T) slab — bounds the transient
# attention buffer on the XLA reference path (the Pallas kernel tiles in
# VMEM instead); 4M => <=1 GiB/chip-class transients at 32k context.
SCORE_CHUNK_ELEMS = 1 << 22

# Roofline-analysis mode: XLA cost_analysis counts while-loop bodies ONCE
# (no trip-count multiply), so benchmarks/roofline.py lowers depth-reduced
# models with every lax.scan/map replaced by an unrolled python loop.
ANALYSIS_UNROLL = False


def _attend_core(q, k, v, *, q_positions, kv_valid_len, window, softcap):
    from repro.dist.sharding import hint
    b, s, h, d = q.shape
    kh, t = k.shape[1], k.shape[2]
    g = h // kh
    qf = q.reshape(b, s, kh, g, d).astype(jnp.float32) * (d ** -0.5)
    scores = jnp.einsum("bskgd,bktd->bkgst", qf, k.astype(jnp.float32))
    # keep scores sharded like the KV sequence (stops GSPMD from
    # all-gathering a seq-sharded cache; softmax runs as partial max/sum)
    scores = hint(scores, ("batch", None, None, None, "seq"))
    if softcap is not None:
        scores = jnp.tanh(scores / softcap) * softcap
    j = jnp.arange(t)[None, None, :]                      # (1, 1, T)
    qpos = q_positions[:, :, None]                        # (B, S, 1)
    mask = j <= qpos
    if window is not None:
        mask &= j > qpos - window
    if not isinstance(kv_valid_len, int) or kv_valid_len < t:
        kvl = jnp.asarray(kv_valid_len)
        mask &= j < kvl.reshape(-1, 1, 1) if kvl.ndim else j < kvl
    scores = jnp.where(mask[:, None, None, :, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(b, s, h, d).astype(q.dtype)


def _chunk_len(s: int, t: int, budget: int = SCORE_CHUNK_ELEMS) -> int:
    """Largest divisor of s with chunk*t <= budget (>=1)."""
    target = max(budget // max(t, 1), 1)
    best = 1
    for c in range(1, min(target, s) + 1):
        if s % c == 0:
            best = c
    return best


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           q_positions: jax.Array, kv_valid_len: jax.Array | int,
           window: int | None = None, softcap: float | None = None,
           use_kernel_hook: bool = True) -> jax.Array:
    """Masked GQA attention.

    q: (B, S, H, D); k/v: (B, K, T, D).  q_positions (B, S): absolute position
    of each query token (so decode passes S=1 with its position).  kv slot j
    holds absolute position j; slots >= kv_valid_len are invalid (future cache
    slots).  Causal: attend to j <= q_pos; window w: j > q_pos - w.

    Long sequences run q-chunked (lax.map over query blocks) so the fp32
    score transient stays bounded at 32k/500k context.
    """
    if use_kernel_hook:
        from repro.kernels import dispatch
        fn = dispatch.get_attention()
        if fn is not None:
            return fn(q, k, v, q_positions=q_positions,
                      kv_valid_len=kv_valid_len, window=window,
                      softcap=softcap)
    b, s, _, _ = q.shape
    t = k.shape[2]
    if s * t <= SCORE_CHUNK_ELEMS or s == 1:
        return _attend_core(q, k, v, q_positions=q_positions,
                            kv_valid_len=kv_valid_len, window=window,
                            softcap=softcap)
    cs = _chunk_len(s, t)
    n = s // cs
    qc = jnp.moveaxis(q.reshape(b, n, cs, *q.shape[2:]), 1, 0)
    pc = jnp.moveaxis(q_positions.reshape(b, n, cs), 1, 0)

    def one(args):
        qi, pi = args
        return _attend_core(qi, k, v, q_positions=pi,
                            kv_valid_len=kv_valid_len, window=window,
                            softcap=softcap)

    if ANALYSIS_UNROLL:
        out = jnp.stack([one((qc[i], pc[i])) for i in range(n)])
    else:
        out = jax.lax.map(one, (qc, pc))
    return jnp.moveaxis(out, 0, 1).reshape(b, s, *q.shape[2:])


def attention(params: dict, x: jax.Array, *, cfg: ModelConfig,
              positions: jax.Array, cache: dict | None = None,
              cache_index: jax.Array | None = None,
              page_table: jax.Array | None = None,
              ) -> tuple[jax.Array, dict | None]:
    """Self-attention with optional KV cache.

    cache: {"k": (B, K, Tmax, D), "v": ...}; cache_index: absolute position
    of the first new token (0 for prefill-from-empty) — a scalar int32, or
    a (B,) int32 vector when batch rows sit at different positions
    (continuous batching: each serving slot decodes at its own position
    with its own kv-valid horizon).  Returns (y, updated_cache).

    With ``page_table`` (B, pages_per_slot) the cache leaves are physical
    page pools ``(n_pages + 1, K, page_size, D)``: the new token's KV is
    scattered into its slot's page at ``cache_index``, and attention reads
    through the table (a scalar-prefetched Pallas kernel when a paged
    kernel is dispatched, a pool gather on the XLA reference path).
    Decode-only — prefill accumulates into dense row caches, which the
    serving engine scatters into pages at admission.
    """
    b, s, m = x.shape
    q = jnp.einsum("bsm,mhd->bshd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsm,mkd->bskd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsm,mkd->bskd", x, params["wv"].astype(x.dtype))
    mrope = cfg.mrope_sections if cfg.pos_embed == "mrope" else None
    if cfg.pos_embed in ("rope", "mrope"):
        q = apply_rope(q, positions, cfg.rope_theta, mrope)
        k = apply_rope(k, positions, cfg.rope_theta, mrope)
    qpos = positions[-1] if positions.ndim == 3 else positions  # t-axis for mrope
    if cache is None:
        y = attend(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                   q_positions=qpos, kv_valid_len=s,
                   window=cfg.sliding_window)
        new_cache = None
    elif page_table is not None:
        idx = jnp.broadcast_to(
            jnp.asarray(cache_index, jnp.int32).reshape(-1), (b,))
        ps_sz = cache["k"].shape[2]
        if s == 1:
            bidx = jnp.arange(b, dtype=jnp.int32)
            phys = page_table[bidx, idx // ps_sz]   # (B,) physical page
            off = idx % ps_sz
            ck = cache["k"].at[phys, :, off].set(
                k[:, 0].astype(cache["k"].dtype))
            cv = cache["v"].at[phys, :, off].set(
                v[:, 0].astype(cache["v"].dtype))
        else:
            # multi-token (speculative verify): scatter each row's S new
            # tokens through the table.  Unmapped spans point at the trash
            # page, so over-draft writes land harmlessly there.
            rows = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
            phys = page_table[bidx, rows // ps_sz]  # (B,S)
            off = rows % ps_sz
            ck = cache["k"].at[phys, :, off].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[phys, :, off].set(v.astype(cache["v"].dtype))
        new_cache = {"k": ck, "v": cv}
        from repro.kernels import dispatch
        fn = dispatch.get_paged_attention() if s == 1 else None
        if fn is not None:
            y = fn(q, ck, cv, page_table=page_table, q_positions=qpos,
                   kv_valid_len=idx + 1, window=cfg.sliding_window,
                   softcap=None)
        else:
            def gather(pool):               # (B, K, n_slot * ps, D)
                pages = jnp.moveaxis(pool[page_table], 1, 2)
                return pages.reshape(b, pool.shape[1], -1, pool.shape[3])
            y = attend(q, gather(ck), gather(cv), q_positions=qpos,
                       kv_valid_len=idx + s, window=cfg.sliding_window,
                       use_kernel_hook=False)
    else:
        idx = jnp.asarray(cache_index, jnp.int32)
        if idx.ndim:
            # per-row positions: scatter each row's new tokens at its own
            # index; kv-valid horizon is per-row too
            rows = idx[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
            bidx = jnp.arange(b, dtype=jnp.int32)[:, None]
            ck = cache["k"].at[bidx, :, rows].set(k.astype(cache["k"].dtype))
            cv = cache["v"].at[bidx, :, rows].set(v.astype(cache["v"].dtype))
        else:
            ck = jax.lax.dynamic_update_slice(
                cache["k"], jnp.swapaxes(k, 1, 2).astype(cache["k"].dtype),
                (0, 0, idx, 0))
            cv = jax.lax.dynamic_update_slice(
                cache["v"], jnp.swapaxes(v, 1, 2).astype(cache["v"].dtype),
                (0, 0, idx, 0))
        y = attend(q, ck, cv, q_positions=qpos, kv_valid_len=idx + s,
                   window=cfg.sliding_window)
        new_cache = {"k": ck, "v": cv}
    y = jnp.einsum("bshd,hdm->bsm", y, params["wo"].astype(x.dtype))
    return y, new_cache


# --------------------------------------------------------------------------
# MLPs: swiglu / geglu (gated) and plain gelu
# --------------------------------------------------------------------------
def mlp_specs(cfg: ModelConfig, d_ff: int | None = None) -> dict:
    m, f = cfg.d_model, d_ff or cfg.d_ff
    if cfg.activation in ("swiglu", "geglu"):
        return {
            "w_gate": ParamSpec((m, f), axes=("embed", "mlp")),
            "w_up": ParamSpec((m, f), axes=("embed", "mlp")),
            "w_down": ParamSpec((f, m), axes=("mlp", "embed")),
        }
    return {
        "w_up": ParamSpec((m, f), axes=("embed", "mlp")),
        "b_up": ParamSpec((f,), jnp.float32, ("mlp",), init="zeros"),
        "w_down": ParamSpec((f, m), axes=("mlp", "embed")),
        "b_down": ParamSpec((m,), jnp.float32, ("embed",), init="zeros"),
    }


def apply_mlp(params: dict, x: jax.Array, activation: str) -> jax.Array:
    from repro.kernels import dispatch
    mm = dispatch.get_matmul()
    if activation in ("swiglu", "geglu"):
        gate = mm(x, params["w_gate"].astype(x.dtype))
        up = mm(x, params["w_up"].astype(x.dtype))
        act = jax.nn.silu if activation == "swiglu" else (
            lambda a: jax.nn.gelu(a, approximate=True))
        h = act(gate.astype(jnp.float32)).astype(x.dtype) * up
        return mm(h, params["w_down"].astype(x.dtype))
    h = mm(x, params["w_up"].astype(x.dtype))
    h = h + params["b_up"].astype(h.dtype)
    h = jax.nn.gelu(h.astype(jnp.float32), approximate=True).astype(x.dtype)
    out = mm(h, params["w_down"].astype(x.dtype))
    return out + params["b_down"].astype(out.dtype)


# --------------------------------------------------------------------------
# Embedding / unembedding
# --------------------------------------------------------------------------
def embed_specs(cfg: ModelConfig) -> dict:
    s = {"embedding": ParamSpec((cfg.vocab_size, cfg.d_model),
                                axes=("vocab", "embed"), init="embed")}
    if not cfg.tie_embeddings:
        s["unembed"] = ParamSpec((cfg.d_model, cfg.vocab_size),
                                 axes=("embed", "vocab"))
    return s


def embed(params: dict, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    x = jnp.take(params["embedding"], tokens, axis=0)
    if cfg.embed_scale:
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    return x


def unembed(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    from repro.dist.sharding import hint
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsm,vm->bsv", x, params["embedding"].astype(x.dtype))
    else:
        logits = jnp.einsum("bsm,mv->bsv", x, params["unembed"].astype(x.dtype))
    logits = hint(logits.astype(jnp.float32), ("batch", "seq", "vocab"))
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return logits


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Mean CE over masked tokens. logits fp32 (B,S,V); labels (B,S) int32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        maskf = mask.astype(jnp.float32)
        return jnp.sum(nll * maskf) / jnp.maximum(jnp.sum(maskf), 1.0)
    return jnp.mean(nll)
