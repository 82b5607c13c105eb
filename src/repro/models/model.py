"""Model assembly: config -> functional Model (init / forward / prefill / decode).

All stacks of identical layers run under ``lax.scan`` with parameters stacked
on a leading "layers" axis (essential to keep 126-layer HLO small).
Heterogeneous structures (deepseek's dense layer 0, recurrentgemma's
(rec, rec, attn) pattern) scan over the repeating unit and unroll remainders.

Inputs dict:
  {"tokens": (B,S) int32}                        LM archs
  {"embeds": (B,S,M), "labels": (B,S) int32}     vlm/audio stub frontends
  optional {"positions": (B,S) or (3,B,S)}       (M-RoPE)
Decode inputs: {"tokens": (B,) } or {"embeds": (B,M)} plus position t —
scalar int32, or (B,) int32 per-row positions (continuous batching).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import hint
from repro.models import layers as L
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rglru as rg_mod
from repro.models import ssm as ssm_mod
from repro.models.params import ParamSpec, abstract_params, init_params

PyTree = Any


def cache_batch_axis(path) -> int:
    """Batch axis of a cache leaf: scanned block caches carry a leading
    layer axis, so batch is axis 1 under the ``blocks`` subtree and
    axis 0 everywhere else.  Shared by the serving engine's row
    slice/write helpers and the fused-quantum row masking."""
    return 1 if any(getattr(p, "key", None) == "blocks" for p in path) else 0


def path_keys(path) -> tuple:
    """A tree path as a plain tuple of dict keys (hashable, comparable
    against :meth:`Model.paged_leaf_paths`)."""
    return tuple(getattr(p, "key", None) for p in path)


def stack_specs(tree: PyTree, n: int) -> PyTree:
    return jax.tree_util.tree_map(
        lambda s: ParamSpec((n,) + s.shape, s.dtype, ("layers",) + s.axes,
                            init=s.init, init_scale=s.init_scale),
        tree, is_leaf=lambda x: isinstance(x, ParamSpec))


# --------------------------------------------------------------------------
# Per-block specs
# --------------------------------------------------------------------------
def _block_specs(cfg: ModelConfig, kind: str) -> dict:
    s: dict = {"ln1": L.norm_specs(cfg)}
    if kind == "dense":
        s["attn"] = L.attention_specs(cfg)
        s["ln2"] = L.norm_specs(cfg)
        s["mlp"] = L.mlp_specs(cfg)
    elif kind == "moe_arctic":
        s["attn"] = L.attention_specs(cfg)
        s["ln2"] = L.norm_specs(cfg)
        s["mlp"] = L.mlp_specs(cfg)                     # dense residual branch
        s["moe"] = moe_mod.moe_specs(cfg, cfg.moe)
    elif kind == "moe_ds":
        s["attn"] = mla_mod.mla_specs(cfg, cfg.mla)
        s["ln2"] = L.norm_specs(cfg)
        s["moe"] = moe_mod.moe_specs(cfg, cfg.moe)
        if cfg.moe.num_shared_experts:
            s["shared"] = L.mlp_specs(cfg, cfg.moe.shared_d_ff)
    elif kind == "ds_dense0":
        s["attn"] = mla_mod.mla_specs(cfg, cfg.mla)
        s["ln2"] = L.norm_specs(cfg)
        s["mlp"] = L.mlp_specs(cfg, cfg.first_dense_d_ff)
    elif kind == "ssm":
        s["mixer"] = ssm_mod.ssm_specs(cfg, cfg.ssm)
    elif kind == "rec":
        s["mixer"] = rg_mod.rglru_specs(cfg, cfg.rglru)
        s["ln2"] = L.norm_specs(cfg)
        s["mlp"] = L.mlp_specs(cfg)
    elif kind == "attn_local":
        s["attn"] = L.attention_specs(cfg)
        s["ln2"] = L.norm_specs(cfg)
        s["mlp"] = L.mlp_specs(cfg)
    else:
        raise ValueError(kind)
    return s


def _attn_cache_specs(cfg: ModelConfig, batch: int, t_max: int) -> dict:
    if cfg.mla is not None:
        r, dr = cfg.mla.kv_lora_rank, cfg.mla.qk_rope_head_dim
        return {"c_kv": ParamSpec((batch, t_max, r), jnp.bfloat16,
                                  ("batch", "seq", "kv_lora"), init="zeros"),
                "k_rope": ParamSpec((batch, t_max, 1, dr), jnp.bfloat16,
                                    ("batch", "seq", None, "head_dim"),
                                    init="zeros")}
    # heads-major (B, K, T, D): the layout the Pallas attention kernels
    # read block-by-block, so decode never transposes the cache
    k, d = cfg.num_kv_heads, cfg.head_dim
    return {"k": ParamSpec((batch, k, t_max, d), jnp.bfloat16,
                           ("batch", "kv_heads", "seq", "head_dim"),
                           init="zeros"),
            "v": ParamSpec((batch, k, t_max, d), jnp.bfloat16,
                           ("batch", "kv_heads", "seq", "head_dim"),
                           init="zeros")}


def _block_cache_specs(cfg: ModelConfig, kind: str, batch: int,
                       t_max: int) -> dict:
    if kind in ("dense", "moe_arctic", "moe_ds", "ds_dense0"):
        return _attn_cache_specs(cfg, batch, t_max)
    if kind == "ssm":
        ssm = cfg.ssm
        conv_ch = ssm.d_inner + 2 * ssm.num_groups * ssm.state_dim
        return {
            "conv": ParamSpec((batch, ssm.conv_width - 1, conv_ch),
                              jnp.bfloat16, ("batch", None, "inner"),
                              init="zeros"),
            "ssd": ParamSpec((batch, ssm.num_heads, ssm.head_dim,
                              ssm.state_dim), jnp.float32,
                             ("batch", "inner", None, "state"),
                             init="zeros"),
        }
    if kind == "rec":
        rg = cfg.rglru
        return {
            "h": ParamSpec((batch, rg.lru_width), jnp.float32,
                           ("batch", "inner"), init="zeros"),
            "conv": ParamSpec((batch, rg.conv_width - 1, rg.lru_width),
                              jnp.bfloat16, ("batch", None, "inner"),
                              init="zeros"),
        }
    if kind == "attn_local":
        return rg_mod.window_cache_specs(cfg, batch)
    raise ValueError(kind)


# --------------------------------------------------------------------------
# Per-block application
# --------------------------------------------------------------------------
def _apply_block(cfg: ModelConfig, kind: str, params: dict, x: jax.Array, *,
                 positions: jax.Array, cache: dict | None,
                 t: jax.Array | int, valid_len: jax.Array | None = None,
                 page_table: jax.Array | None = None,
                 ) -> tuple[jax.Array, dict | None, jax.Array]:
    """-> (x, new_cache, aux_loss).

    ``valid_len`` (chunked-prefill padding): tokens past it must be exact
    no-ops for carried state.  Recurrent mixers and the window ring cache
    mask explicitly; linear KV caches need nothing — a padded row is
    causally invisible until decode reaches its position, and the decode
    write at that position overwrites it first."""
    aux = jnp.zeros((), jnp.float32)
    if kind == "ssm":
        h, new_cache = ssm_mod.mamba2_block(
            params["mixer"], L.apply_norm(params["ln1"], x, cfg.norm_type),
            cfg=cfg, cache=cache, valid_len=valid_len)
        return x + h, new_cache, aux

    if kind == "rec":
        h, new_cache = rg_mod.rglru_block(
            params["mixer"], L.apply_norm(params["ln1"], x, cfg.norm_type),
            cfg=cfg, cache=cache, valid_len=valid_len)
        x = x + h
        m = L.apply_mlp(params["mlp"],
                        L.apply_norm(params["ln2"], x, cfg.norm_type),
                        cfg.activation)
        return x + m, new_cache, aux

    # attention-bearing blocks -------------------------------------------
    xa = L.apply_norm(params["ln1"], x, cfg.norm_type)
    if kind in ("moe_ds", "ds_dense0"):
        h, new_cache = mla_mod.mla_attention(
            params["attn"], xa, cfg=cfg, positions=positions, cache=cache,
            cache_index=t if cache is not None else None,
            page_table=page_table)
    elif kind == "attn_local":
        h, new_cache = _local_attention(cfg, params["attn"], xa,
                                        positions=positions, cache=cache,
                                        t=t, valid_len=valid_len)
    else:
        h, new_cache = L.attention(
            params["attn"], xa, cfg=cfg, positions=positions, cache=cache,
            cache_index=t if cache is not None else None,
            page_table=page_table)
    x = x + h
    x = hint(x, ("batch", "seq", "embed"))
    xm = L.apply_norm(params["ln2"], x, cfg.norm_type)

    if kind in ("dense", "ds_dense0", "attn_local"):
        x = x + L.apply_mlp(params["mlp"], xm, cfg.activation)
    elif kind == "moe_arctic":
        moe_out, aux = moe_mod.apply_moe(params["moe"], xm, cfg, cfg.moe)
        x = x + L.apply_mlp(params["mlp"], xm, cfg.activation) + moe_out
    elif kind == "moe_ds":
        moe_out, aux = moe_mod.apply_moe(params["moe"], xm, cfg, cfg.moe)
        if "shared" in params:
            moe_out = moe_out + L.apply_mlp(params["shared"], xm,
                                            cfg.activation)
        x = x + moe_out
    return x, new_cache, aux


def _local_attention(cfg: ModelConfig, params: dict, x: jax.Array, *,
                     positions: jax.Array, cache: dict | None,
                     t: jax.Array | int, valid_len: jax.Array | None = None,
                     ) -> tuple[jax.Array, dict | None]:
    """RecurrentGemma local-attention layer (window ring-buffer cache)."""
    window = cfg.rglru.window_size
    b, s, _ = x.shape
    q = jnp.einsum("bsm,mhd->bshd", x, params["wq"].astype(x.dtype))
    k = jnp.einsum("bsm,mkd->bskd", x, params["wk"].astype(x.dtype))
    v = jnp.einsum("bsm,mkd->bskd", x, params["wv"].astype(x.dtype))
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    if cache is not None and s == 1:
        y, new_cache = rg_mod.window_attention_decode(q, cache, k, v, t,
                                                      window)
    elif cache is not None and valid_len is not None:
        # chunked prefill: attend across the ring cache (earlier chunks)
        # and the in-chunk keys; only real tokens are written back
        y, new_cache = rg_mod.window_attention_chunk(q, cache, k, v, t,
                                                     valid_len, window)
    else:
        y = L.attend(q, jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2),
                     q_positions=positions, kv_valid_len=s, window=window)
        new_cache = (rg_mod.fill_window_cache(cache, k, v, window)
                     if cache is not None else None)
    return jnp.einsum("bshd,hdm->bsm", y, params["wo"].astype(x.dtype)), \
        new_cache


# --------------------------------------------------------------------------
# Model
# --------------------------------------------------------------------------
@dataclasses.dataclass
class LayerPlan:
    """How cfg.num_layers decomposes into scanned stacks / unrolled layers."""
    prologue: tuple[str, ...]          # unrolled kinds before the scan
    scan_kinds: tuple[str, ...]        # kinds inside one scanned group
    n_groups: int
    epilogue: tuple[str, ...]          # unrolled kinds after the scan


def make_plan(cfg: ModelConfig) -> LayerPlan:
    if cfg.family == "ssm":
        return LayerPlan((), ("ssm",), cfg.num_layers, ())
    if cfg.family == "hybrid":
        pat = tuple("rec" if p == "rec" else "attn_local"
                    for p in cfg.rglru.block_pattern)
        n_groups = cfg.num_layers // len(pat)
        rem = cfg.num_layers - n_groups * len(pat)
        return LayerPlan((), pat, n_groups, pat[:rem])
    if cfg.family == "moe":
        kind = "moe_arctic" if cfg.moe.dense_residual else "moe_ds"
        if cfg.first_dense_layers:
            return LayerPlan(("ds_dense0",) * cfg.first_dense_layers, (kind,),
                             cfg.num_layers - cfg.first_dense_layers, ())
        return LayerPlan((), (kind,), cfg.num_layers, ())
    return LayerPlan((), ("dense",), cfg.num_layers, ())


class Model:
    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.plan = make_plan(cfg)

    # -- parameters ------------------------------------------------------
    def param_specs(self) -> dict:
        cfg, plan = self.cfg, self.plan
        specs: dict = {"embed": L.embed_specs(cfg)}
        for i, kind in enumerate(plan.prologue):
            specs[f"pro_{i}"] = _block_specs(cfg, kind)
        if plan.n_groups:
            group = {k if len(plan.scan_kinds) == 1 else f"{k}_{j}":
                     _block_specs(cfg, k)
                     for j, k in enumerate(plan.scan_kinds)}
            specs["blocks"] = stack_specs(group, plan.n_groups)
        for i, kind in enumerate(plan.epilogue):
            specs[f"epi_{i}"] = _block_specs(cfg, kind)
        specs["final_norm"] = L.norm_specs(cfg)
        return specs

    def init(self, rng: jax.Array) -> PyTree:
        return init_params(rng, self.param_specs())

    def abstract(self) -> PyTree:
        return abstract_params(self.param_specs())

    # -- caches ------------------------------------------------------------
    def cache_specs(self, batch: int, t_max: int) -> dict:
        cfg, plan = self.cfg, self.plan
        out: dict = {}
        for i, kind in enumerate(plan.prologue):
            out[f"pro_{i}"] = _block_cache_specs(cfg, kind, batch, t_max)
        if plan.n_groups:
            group = {k if len(plan.scan_kinds) == 1 else f"{k}_{j}":
                     _block_cache_specs(cfg, k, batch, t_max)
                     for j, k in enumerate(plan.scan_kinds)}
            out["blocks"] = stack_specs(group, plan.n_groups)
        for i, kind in enumerate(plan.epilogue):
            out[f"epi_{i}"] = _block_cache_specs(cfg, kind, batch, t_max)
        return out

    def init_cache(self, batch: int, t_max: int) -> PyTree:
        cache = init_params(jax.random.PRNGKey(0),
                            self.cache_specs(batch, t_max))
        # ring-buffer position slots start invalid
        def fix(path, leaf):
            if any(getattr(p, "key", None) == "pos" for p in path):
                return jnp.full_like(leaf, -1)
            return jnp.zeros_like(leaf)
        return jax.tree_util.tree_map_with_path(fix, cache)

    # -- paged caches -------------------------------------------------------
    def paged_leaf_paths(self) -> frozenset:
        """Key-paths of cache leaves that page: linear KV leaves, i.e.
        those whose spec carries a ``"seq"`` axis (attention k/v, MLA
        c_kv/k_rope).  Recurrent state (SSM/RG-LRU) and the local-window
        ring cache are O(1)-or-O(window) per slot and stay dense."""
        return frozenset(self.paged_leaf_axes())

    def paged_leaf_axes(self) -> dict:
        """Key-path -> (batch axis, seq axis) of every pageable leaf, in
        its dense layout.  In the leaf's page pool the batch axis indexes
        physical pages and the seq axis holds one page's positions
        (:meth:`paged_cache_specs`); the serving engine moves rows in and
        out of pages along these two axes."""
        cached = getattr(self, "_paged_axes", None)
        if cached is None:
            flat, _ = jax.tree_util.tree_flatten_with_path(
                self.cache_specs(1, 8),
                is_leaf=lambda x: isinstance(x, ParamSpec))
            cached = {path_keys(p): (s.axes.index("batch"),
                                     s.axes.index("seq"))
                      for p, s in flat if "seq" in s.axes}
            self._paged_axes = cached
        return cached

    def all_cache_leaves_paged(self) -> bool:
        """True when every cache leaf pages (pure-attention families).
        Prefix sharing requires this: skipping prefill of a shared prefix
        is only sound when no dense recurrent state would be skipped."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.cache_specs(1, 8),
            is_leaf=lambda x: isinstance(x, ParamSpec))
        paged = self.paged_leaf_paths()
        return bool(paged) and all(path_keys(p) in paged for p, _ in flat)

    def paged_cache_specs(self, batch: int, t_max: int, n_pages: int,
                          page_size: int) -> dict:
        """Cache specs with every ``"seq"``-axis leaf reshaped from dense
        rows to a physical page pool: the batch axis becomes
        ``n_pages + 1`` physical pages (index 0 = pinned trash page) and
        the seq axis ``page_size`` — ``(B, K, T, D)`` attention rows give
        ``(n_pages + 1, K, page_size, D)`` pools, ``(B, T, r)`` MLA
        latents ``(n_pages + 1, page_size, r)``.  One logical page uses
        the same physical index in every layer's pool, so a single
        per-slot page table addresses all layers."""
        if t_max % page_size:
            raise ValueError(f"t_max={t_max} must be a multiple of "
                             f"page_size={page_size}")

        def to_pool(spec):
            if not isinstance(spec, ParamSpec) or "seq" not in spec.axes:
                return spec
            bi, si = spec.axes.index("batch"), spec.axes.index("seq")
            shape = list(spec.shape)
            shape[bi] = n_pages + 1            # batch axis -> physical pages
            shape[si] = page_size
            axes = list(spec.axes)
            axes[bi], axes[si] = "pages", None
            return ParamSpec(tuple(shape), spec.dtype, tuple(axes),
                             init="zeros")

        return jax.tree_util.tree_map(
            to_pool, self.cache_specs(batch, t_max),
            is_leaf=lambda x: isinstance(x, ParamSpec))

    def init_paged_cache(self, batch: int, t_max: int, n_pages: int,
                         page_size: int) -> PyTree:
        """Paged variant of :meth:`init_cache`.  Adds a per-slot
        ``"page_table"`` leaf (batch, t_max // page_size) int32 of
        physical page indices — all zeros parks every entry on the trash
        page.  The table rides inside the cache pytree so every compiled
        executable (decode, quanta, version-cache entries) is keyed on
        the page-table shape with no signature changes."""
        cache = init_params(
            jax.random.PRNGKey(0),
            self.paged_cache_specs(batch, t_max, n_pages, page_size))

        def fix(path, leaf):
            if any(getattr(p, "key", None) == "pos" for p in path):
                return jnp.full_like(leaf, -1)
            return jnp.zeros_like(leaf)
        cache = jax.tree_util.tree_map_with_path(fix, cache)
        cache["page_table"] = jnp.zeros((batch, t_max // page_size),
                                        jnp.int32)
        return cache

    # -- embedding / head ---------------------------------------------------
    def _embed_inputs(self, params, inputs, positions):
        cfg = self.cfg
        if "embeds" in inputs:
            x = inputs["embeds"].astype(jnp.bfloat16)
        else:
            x = L.embed(params["embed"], inputs["tokens"], cfg)
        if cfg.pos_embed == "sinusoidal":
            pe = L.sinusoidal_pe(
                positions if positions.ndim == 2 else positions[-1],
                cfg.d_model)
            x = x + pe.astype(x.dtype)
        return x

    def _default_positions(self, b: int, s: int, t0: int | jax.Array = 0):
        """Row-contiguous positions from ``t0``: scalar (all rows aligned)
        or (B,) per-row offsets (continuous batching)."""
        t0 = jnp.asarray(t0, jnp.int32)
        if t0.ndim == 1:
            t0 = t0[:, None]
        pos = t0 + jnp.arange(s, dtype=jnp.int32)[None, :]
        pos = jnp.broadcast_to(pos, (b, s))
        if self.cfg.pos_embed == "mrope":
            pos = jnp.broadcast_to(pos[None], (3, b, s))
        return pos

    # -- stacks ------------------------------------------------------------
    def _run_blocks(self, params, x, *, positions, caches, t, remat="none",
                    valid_len=None, page_table=None):
        cfg, plan = self.cfg, self.plan
        aux_total = jnp.zeros((), jnp.float32)
        new_caches: dict = {}

        def group_fn(gp, x, gcache):
            aux_g = jnp.zeros((), jnp.float32)
            ncache: dict = {}
            for j, kind in enumerate(plan.scan_kinds):
                key = kind if len(plan.scan_kinds) == 1 else f"{kind}_{j}"
                c = gcache.get(key) if gcache is not None else None
                x2, nc, a = _apply_block(cfg, kind, gp[key], x,
                                         positions=positions, cache=c, t=t,
                                         valid_len=valid_len,
                                         page_table=page_table)
                x = x2
                aux_g = aux_g + a
                if nc is not None:
                    ncache[key] = nc
            return x, (ncache or None), aux_g

        if remat == "full":
            group_fn = jax.checkpoint(group_fn)
        elif remat == "dots":
            group_fn = jax.checkpoint(
                group_fn,
                policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)

        for i, kind in enumerate(plan.prologue):
            c = caches.get(f"pro_{i}") if caches is not None else None
            x, nc, a = _apply_block(cfg, kind, params[f"pro_{i}"], x,
                                    positions=positions, cache=c, t=t,
                                    valid_len=valid_len,
                                    page_table=page_table)
            aux_total += a
            if nc is not None:
                new_caches[f"pro_{i}"] = nc

        if plan.n_groups:
            bcaches = caches.get("blocks") if caches is not None else None

            if bcaches is None and L.ANALYSIS_UNROLL:
                # roofline-analysis mode: unrolled so cost_analysis counts
                # every group (see benchmarks/roofline.py)
                for gi in range(plan.n_groups):
                    gp = jax.tree_util.tree_map(lambda p: p[gi],
                                                params["blocks"])
                    x, _, a = group_fn(gp, x, None)
                    aux_total = aux_total + a
            elif bcaches is None:
                def body(carry, gp):
                    xx, aux = carry
                    xx, _, a = group_fn(gp, xx, None)
                    return (xx, aux + a), None
                (x, aux_total), _ = jax.lax.scan(
                    body, (x, aux_total), params["blocks"])
            elif L.ANALYSIS_UNROLL:
                ncs_list = []
                for gi in range(plan.n_groups):
                    gp = jax.tree_util.tree_map(lambda p: p[gi],
                                                params["blocks"])
                    gc = jax.tree_util.tree_map(lambda c: c[gi], bcaches)
                    x, nc, a = group_fn(gp, x, gc)
                    aux_total = aux_total + a
                    ncs_list.append(nc)
                new_caches["blocks"] = jax.tree_util.tree_map(
                    lambda *cs: jnp.stack(cs), *ncs_list)
            else:
                def body(carry, xs):
                    xx, aux = carry
                    gp, gc = xs
                    xx, nc, a = group_fn(gp, xx, gc)
                    return (xx, aux + a), nc
                (x, aux_total), ncs = jax.lax.scan(
                    body, (x, aux_total), (params["blocks"], bcaches))
                new_caches["blocks"] = ncs

        for i, kind in enumerate(plan.epilogue):
            c = caches.get(f"epi_{i}") if caches is not None else None
            x, nc, a = _apply_block(cfg, kind, params[f"epi_{i}"], x,
                                    positions=positions, cache=c, t=t,
                                    valid_len=valid_len,
                                    page_table=page_table)
            aux_total += a
            if nc is not None:
                new_caches[f"epi_{i}"] = nc
        return x, (new_caches or None), aux_total

    # -- entry points --------------------------------------------------------
    def forward(self, params, inputs, *, positions=None, remat="none"):
        """Full-sequence forward -> (logits (B,S,V) fp32, aux)."""
        cfg = self.cfg
        b, s = (inputs["tokens"].shape if "tokens" in inputs
                else inputs["embeds"].shape[:2])
        if positions is None:
            positions = inputs.get("positions")
        if positions is None:
            positions = self._default_positions(b, s)
        x = self._embed_inputs(params, inputs, positions)
        x = hint(x, ("batch", "seq", "embed"))
        x, _, aux = self._run_blocks(params, x, positions=positions,
                                     caches=None, t=0, remat=remat)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type)
        return L.unembed(params["embed"], x, cfg), aux

    def loss(self, params, batch, *, remat="none"):
        """Next-token CE (+ MoE aux).  batch needs tokens or embeds+labels."""
        cfg = self.cfg
        logits, aux = self.forward(params, batch, remat=remat)
        if "labels" in batch:
            labels, mask = batch["labels"], batch.get("mask")
            lg = logits
        else:
            tokens = batch["tokens"]
            labels, lg = tokens[:, 1:], logits[:, :-1]
            mask = batch.get("mask")
            mask = mask[:, 1:] if mask is not None else None
        ce = L.cross_entropy(lg, labels, mask)
        aux_w = cfg.moe.aux_loss_weight if cfg.moe else 0.0
        total = ce + aux_w * aux / max(cfg.num_layers, 1)
        return total, {"ce": ce, "aux": aux}

    def prefill(self, params, inputs, cache, *, positions=None):
        """Process a prompt, filling the cache.  -> (last logits (B,V), cache)."""
        cfg = self.cfg
        b, s = (inputs["tokens"].shape if "tokens" in inputs
                else inputs["embeds"].shape[:2])
        if positions is None:
            positions = inputs.get("positions")
        if positions is None:
            positions = self._default_positions(b, s)
        x = self._embed_inputs(params, inputs, positions)
        x, new_cache, _ = self._run_blocks(params, x, positions=positions,
                                           caches=cache, t=0)
        x = L.apply_norm(params["final_norm"], x[:, -1:], cfg.norm_type)
        logits = L.unembed(params["embed"], x, cfg)
        return logits[:, 0], new_cache

    def prefill_chunk(self, params, inputs, cache, t0, valid_len, *,
                      positions=None):
        """Incremental prefill of one fixed-size chunk at absolute start
        position ``t0`` (traced scalar) — the schedulable prefill quantum.

        ``inputs["tokens"]`` is (B, C) with only the first ``valid_len``
        tokens real; the tail is length-bucket padding and is an *exact*
        no-op for all carried state: recurrent mixers (SSM / RG-LRU) mask
        their updates to the last real token, the window ring cache
        refuses pad writes, and a pad row in a linear KV cache is
        causally invisible until the decode step at its position
        overwrites it.  Chaining chunks (t0 = 0, C, 2C, ...) over a
        prompt therefore yields a cache bit-identical to one monolithic
        :meth:`prefill` — while the compiled shapes are the fixed bucket
        set, not the prompt-length distribution.  (Exception: capacity
        MoE routing drops tokens per routing *group*, whose size follows
        the batch shape — so MoE families are chunk-schedule-dependent
        whenever any token exceeds expert capacity, exactly as in any
        chunked-prefill serving system.)

        Returns (logits (B, V) at the last *valid* token, updated cache);
        only the final chunk's logits are meaningful to sample from."""
        cfg = self.cfg
        b, s = (inputs["tokens"].shape if "tokens" in inputs
                else inputs["embeds"].shape[:2])
        t0 = jnp.asarray(t0, jnp.int32)
        vl = jnp.asarray(valid_len, jnp.int32)
        if positions is None:
            positions = inputs.get("positions")
        if positions is None:
            positions = self._default_positions(b, s, t0)
        x = self._embed_inputs(params, inputs, positions)
        x, new_cache, _ = self._run_blocks(params, x, positions=positions,
                                           caches=cache, t=t0, valid_len=vl)
        last = jax.lax.dynamic_slice_in_dim(x, vl - 1, 1, axis=1)
        last = L.apply_norm(params["final_norm"], last, cfg.norm_type)
        logits = L.unembed(params["embed"], last, cfg)
        return logits[:, 0], new_cache

    def decode_step(self, params, inputs, cache, t):
        """One-token decode at absolute position ``t`` — a scalar int32
        (all rows aligned) or a (B,) int32 vector of per-row positions
        (continuous batching: each slot advances independently; attention
        masks each row at its own kv-valid horizon).

        A paged cache (one holding a ``"page_table"`` leaf — see
        :meth:`init_paged_cache`) routes KV reads/writes through the
        per-slot page table; the table itself passes through unchanged
        (the host owns it)."""
        cfg = self.cfg
        t = jnp.asarray(t, jnp.int32)
        page_table = cache.get("page_table") if isinstance(cache, dict) \
            else None
        caches = cache
        if page_table is not None:
            caches = {kk: v for kk, v in cache.items() if kk != "page_table"}
        if "tokens" in inputs:
            b = inputs["tokens"].shape[0]
            toks = inputs["tokens"].reshape(b, 1)
            step_in = {"tokens": toks}
        else:
            b = inputs["embeds"].shape[0]
            step_in = {"embeds": inputs["embeds"].reshape(b, 1, -1)}
        positions = self._default_positions(b, 1, t)
        x = self._embed_inputs(params, step_in, positions)
        x, new_cache, _ = self._run_blocks(params, x, positions=positions,
                                           caches=caches, t=t,
                                           page_table=page_table)
        x = L.apply_norm(params["final_norm"], x, cfg.norm_type)
        logits = L.unembed(params["embed"], x, cfg)
        if page_table is not None:
            new_cache = dict(new_cache)
            new_cache["page_table"] = page_table
        return logits[:, 0], new_cache

    def select_cache_rows(self, live: jax.Array, new_cache: PyTree,
                          old_cache: PyTree) -> PyTree:
        """Per-row cache select: rows where ``live`` is True take
        ``new_cache``, frozen rows keep ``old_cache`` bit-exact.  This is
        what lets a fused multi-step decode freeze finished slots: a
        frozen row's recurrent state (SSM/RG-LRU) and KV writes are fully
        reverted, so its cache is indistinguishable from one that was
        never stepped.

        Page-pool leaves have no per-row batch axis and are kept as
        written: a frozen row replays the *same* KV write at its frozen
        (token, position) — its own pages and dense state are bit-exact
        reverted, so the recomputation is idempotent — and a free row's
        table maps every entry to the pinned trash page."""
        paged = (self.paged_leaf_paths()
                 if isinstance(new_cache, dict) and "page_table" in new_cache
                 else frozenset())

        def sel(path, n, o):
            keys = path_keys(path)
            if keys == ("page_table",) or keys in paged:
                return n
            shape = [1] * n.ndim
            shape[cache_batch_axis(path)] = live.shape[0]
            return jnp.where(live.reshape(shape), n, o).astype(o.dtype)
        return jax.tree_util.tree_map_with_path(sel, new_cache, old_cache)

    def decode_quantum(self, params, tokens, cache, pos, n_left, k: int):
        """Fused on-device decode of up to ``k`` greedy tokens per row.

        A ``lax.scan`` over :meth:`decode_step` — the whole dispatch
        quantum runs as ONE executable with on-device argmax sampling, so
        the host syncs once per quantum instead of once per token.

        Args: ``tokens`` (B,) int32 last-sampled token per row; ``pos``
        (B,) int32 absolute positions; ``n_left`` (B,) int32 per-row step
        budget (rows stop advancing after their budget: token, position
        and cache all freeze, so mid-quantum completions and slots
        shorter than the quantum stay exact).  ``k`` is static — the
        serving layer compiles one executable per K-bucket.

        Returns ``(block (k, B) int32, cache, pos)``; column ``i`` of
        ``block`` is valid for the first ``n_left[i]`` rows.
        """
        def body(carry, j):
            toks, cache_c, pos_c = carry
            logits, new_cache = self.decode_step(
                params, {"tokens": toks}, cache_c, pos_c)
            live = j < n_left
            nxt = jnp.where(live,
                            jnp.argmax(logits, axis=-1).astype(jnp.int32),
                            toks)
            new_cache = self.select_cache_rows(live, new_cache, cache_c)
            pos_c = jnp.where(live, pos_c + 1, pos_c)
            return (nxt, new_cache, pos_c), nxt

        (_, cache, pos), block = jax.lax.scan(
            body,
            (jnp.asarray(tokens, jnp.int32), cache,
             jnp.asarray(pos, jnp.int32)),
            jnp.arange(int(k), dtype=jnp.int32))
        return block, cache, pos

    def _has_nonseq_cache_leaves(self) -> bool:
        """True when any cache leaf carries recurrent / ring state (no
        ``"seq"`` axis) — those leaves need the speculative restore pass."""
        flat, _ = jax.tree_util.tree_flatten_with_path(
            self.cache_specs(1, 8),
            is_leaf=lambda x: isinstance(x, ParamSpec))
        paged = self.paged_leaf_paths()
        return any(path_keys(p) not in paged for p, _ in flat)

    def verify_quantum(self, params, tokens, drafts, cache, pos, n_left):
        """Speculative decode quantum: score a per-row draft block in ONE
        batched forward and greedily accept the longest matching prefix
        plus one corrected token.

        ``tokens`` (B,) is each row's last sampled token, ``drafts``
        (B, d) a drafter's proposed continuation (``d`` static — the
        serving layer compiles one executable per draft depth).  The
        d+1-token sequence [token, draft_0, ..., draft_{d-1}] runs as one
        chunk at per-row start positions ``pos`` (B,) — the same pad-exact
        machinery as :meth:`prefill_chunk`, so a verify forward costs one
        sequence-parallel pass instead of d+1 sequential steps.  Greedy
        acceptance per row: ``accepted`` = length of the longest draft
        prefix matching the model's own argmax, and the row emits
        ``n_emit = min(accepted + 1, n_left)`` tokens (the +1 is the
        corrected/bonus token at the first mismatch; ``n_left`` (B,) is
        the per-row emission budget, 0 freezes a row).

        Rollback of the d+1 optimistic writes is per cache family:

        * linear KV leaves (attention k/v, MLA latents; dense or paged)
          keep the pass-1 writes — entries past ``pos + n_emit`` are
          causally invisible (reads mask ``j <= q_pos``) and the next
          quantum overwrites them before they ever enter a softmax, the
          same argument that makes prefill padding exact.  Paged pools:
          writes beyond the mapped span land on the pinned trash page, so
          the serving layer caps ``n_left`` at the preflighted span.
        * recurrent / ring leaves (SSM conv+ssd, RG-LRU h+conv, local
          window ring) cannot keep optimistic updates, so a second
          forward from the ORIGINAL cache replays the chunk with per-row
          ``valid_len = n_emit``: pads are exact no-ops (dt=0 identity
          recurrence, refused ring writes), leaving each row's state
          bit-identical to stepping exactly ``n_emit`` tokens.  This is
          the functional form of snapshot/restore; it is statically
          skipped for pure linear-KV families.

        Returns ``(block (d+1, B) int32, n_emit (B,), accepted (B,),
        cache, pos)``; column ``i`` of ``block`` holds the row's emitted
        tokens in its first ``n_emit[i]`` entries.
        """
        cfg = self.cfg
        tokens = jnp.asarray(tokens, jnp.int32)
        drafts = jnp.asarray(drafts, jnp.int32)
        pos = jnp.asarray(pos, jnp.int32)
        n_left = jnp.asarray(n_left, jnp.int32)
        b, d = drafts.shape
        s = d + 1
        page_table = cache.get("page_table") if isinstance(cache, dict) \
            else None
        caches = cache
        if page_table is not None:
            caches = {kk: v for kk, v in cache.items() if kk != "page_table"}

        seq = jnp.concatenate([tokens[:, None], drafts], axis=1)  # (B,d+1)
        positions = self._default_positions(b, s, pos)
        x = self._embed_inputs(params, {"tokens": seq}, positions)

        # pass 1: full-validity forward — logits at every candidate
        x1, cache1, _ = self._run_blocks(
            params, x, positions=positions, caches=caches, t=pos,
            valid_len=jnp.full((b,), s, jnp.int32), page_table=page_table)
        h = L.apply_norm(params["final_norm"], x1, cfg.norm_type)
        logits = L.unembed(params["embed"], h, cfg)       # (B,d+1,V) fp32
        g = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # (B,d+1)

        match = (g[:, :d] == drafts).astype(jnp.int32)
        accepted = jnp.sum(jnp.cumprod(match, axis=1), axis=1)    # (B,)
        n_emit = jnp.minimum(accepted + 1, n_left)
        n_emit = jnp.where(n_left > 0, n_emit, 0)

        if self._has_nonseq_cache_leaves():
            # restore pass: exact recurrent/ring state after n_emit tokens
            _, cache2, _ = self._run_blocks(
                params, x, positions=positions, caches=caches, t=pos,
                valid_len=n_emit, page_table=page_table)
            seq_paths = self.paged_leaf_paths()

            def merge(path, c1, c2):
                return c1 if path_keys(path) in seq_paths else c2
            new_cache = jax.tree_util.tree_map_with_path(
                merge, cache1, cache2)
        else:
            new_cache = cache1
        if page_table is not None:
            new_cache = dict(new_cache)
            new_cache["page_table"] = page_table
        return g.T, n_emit, accepted, new_cache, pos + n_emit


@functools.lru_cache(maxsize=None)
def _cached_model(cfg: ModelConfig) -> Model:
    return Model(cfg)


def build_model(cfg: ModelConfig) -> Model:
    return _cached_model(cfg)
