"""Precompiled multi-version executable cache for the serving engine.

VELTAIR's premise is that switching code versions under interference
pressure is cheap enough to do *online*.  A naive engine pays a full
``jax.jit`` retrace every time the tile overrides change — exactly the
overhead adaptive compilation is supposed to amortize.  This module makes
the switch a dictionary lookup: one :class:`VersionEntry` per tile
configuration holds its own jitted prefill-chunk/decode callables,
traced under a :func:`repro.kernels.dispatch.tile_context` that bakes
that entry's tiles into the executable.  Because every entry owns its
trace cache,

  * revisiting a level never retraces (jit hits its own cache);
  * multiple engines with different active versions coexist in one
    process — no engine's switch can invalidate another's compiled code,
    since nothing reads the process-global override table at trace time.

Keys are derived from the same tile dictionaries the engine's level
tables produce (``VersionSet`` selections or ``DEFAULT_LEVEL_TILES``), so
the cache holds at most one entry per distinct code version (<= NUM_LEVELS
per engine).  Memory footprint: one decode executable per entry, one
chunked-prefill executable per (entry, chunk bucket) — the serving
admission path, which is why mixed-length traffic never retraces after
warmup — plus one fused quantum-decode executable per (entry, K-bucket)
actually used and, for speculative engines, one verify executable per
(entry, K-bucket, draft-depth).

Donation: the decode and quantum executables donate their cache argument
(``donate_argnums``), so every step updates the KV/SSM buffers in place
instead of allocating a fresh cache — the caller must treat the cache it
passed in as consumed and adopt the returned one.  The prefill-chunk
callable deliberately does NOT donate: the engine reuses one pristine
cache row for every admission, and donating it would invalidate that
row after the first prefill.

Paged engines need no special handling here: the per-slot ``page_table``
rides *inside* the cache pytree, so every executable this cache holds is
keyed on the paged layout's shapes (pool + table) exactly like any other
cache leaf — a dense and a paged engine of the same model simply trace
distinct executables, and :meth:`ServingEngine.warmup` prebuilds the
paged gather/scatter helpers alongside these entries.

``traces`` counts *actual* jax traces (the counter increments inside the
traced body, so it fires on first-call tracing and any shape-driven
retrace, and stays flat on cache hits) — tests assert a full level sweep
after :meth:`ServingEngine.warmup` leaves it unchanged.
"""
from __future__ import annotations

import dataclasses
import operator
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.kernels import dispatch
from repro.serving.spans import VCACHE_BUILD, span


def tiles_key(tiles: dict[str, dict]) -> tuple:
    """Canonical hashable key for an op -> tiling-kwargs table."""
    return tuple(sorted(
        (op, tuple(sorted(kw.items()))) for op, kw in tiles.items()))


class StaticArgError(TypeError):
    """A static compile-key argument (K-bucket, draft depth) is not a
    hashable integer from the sanctioned bucket space.  Raised eagerly
    at the :class:`VersionCache` boundary: an unhashable or unbucketed
    key would otherwise silently trace + AOT-compile a fresh executable
    per distinct value — the exact retrace hazard the static analyzer's
    ``retrace-hazard`` rule guards at the call sites."""


def _static_int(name: str, v: Any, minimum: int = 1) -> int:
    """Validate a static compile key: a plain integer (no bools, no
    floats, nothing unhashable) of at least ``minimum``."""
    if isinstance(v, bool):
        raise StaticArgError(
            f"{name} must be a plain int compile key, got bool {v!r}")
    try:
        i = operator.index(v)
    except TypeError:
        raise StaticArgError(
            f"{name} must be a hashable int compile key, got "
            f"{type(v).__name__} {v!r} — a non-int key would trace a "
            f"fresh executable per call") from None
    if i < minimum:
        raise StaticArgError(f"{name}={i} must be >= {minimum}")
    return i


def _pow2_bucket(name: str, v: Any) -> int:
    """Validate a K-bucket key: a power-of-two ``_static_int``."""
    i = _static_int(name, v)
    if i & (i - 1):
        raise StaticArgError(
            f"{name}={i} is not a power-of-two bucket — every distinct "
            f"unbucketed value compiles its own executable (the "
            f"zero-post-warmup-retrace contract); round up via "
            f"_next_pow2 or pick from the engine's quantum_buckets")
    return i


@dataclasses.dataclass
class VersionEntry:
    """One code version: jitted executables with the tiles baked in."""
    key: tuple
    tiles: dict[str, dict]
    decode: Callable           # (params, {"tokens": (B,)}, cache, t) -> ...
    # bucketed prefill quantum: (params, tokens (1,C), row_cache,
    #   t0, valid_len) -> (logits, row_cache, routing counts).  One trace
    #   per chunk bucket C — t0/valid_len are traced, so mixed-length
    #   traffic shares the bucket's executable instead of retracing per
    #   length.
    prefill_chunk: Callable
    # K-bucket -> AOT-compiled fused quantum decode
    #   (params, tokens (B,), cache, pos (B,), n_left (B,)) -> (block, cache, pos)
    quanta: dict[int, Callable] = dataclasses.field(default_factory=dict)
    # (K-bucket, draft depth) -> AOT-compiled speculative verify quantum
    #   (params, tokens (B,), drafts (B,d), cache, pos (B,), n_left (B,))
    #   -> (block (d+1,B), n_emit (B,), accepted (B,), cache, pos)
    spec: dict[tuple[int, int], Callable] = dataclasses.field(
        default_factory=dict)


class VersionCache:
    """tiles -> VersionEntry, building (and counting) on first use."""

    def __init__(self, model: Any):
        self.model = model
        self._entries: dict[tuple, VersionEntry] = {}
        self.hits = 0              # get() found an existing entry
        self.misses = 0            # get() had to build one
        self.traces = 0            # actual jax traces across all entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def stats(self) -> dict:
        return {"entries": len(self._entries), "hits": self.hits,
                "misses": self.misses, "traces": self.traces}

    def warmup(self, tile_tables) -> list[VersionEntry]:
        """Pre-create one entry per tile table (``LadderSpec`` levels, a
        level-grid sweep, ...) so serve-time version switches are
        dictionary lookups.  Returns the entries in input order (the
        engine's warmup then executes each to force the actual
        compiles); duplicate tables resolve to the same entry."""
        return [self.get(tiles) for tiles in tile_tables]

    def get(self, tiles: dict[str, dict]) -> VersionEntry:
        if dispatch.get_mode() == "xla":
            # the reference path ignores tiling entirely: all versions
            # share one executable (keying by tiles here would trace
            # NUM_LEVELS byte-identical programs for nothing)
            tiles = {}
        key = tiles_key(tiles)
        entry = self._entries.get(key)
        if entry is None:
            entry = self._build(tiles, key)
            self._entries[key] = entry
            self.misses += 1
        else:
            self.hits += 1
        return entry

    # ------------------------------------------------------------------
    def _build(self, tiles: dict[str, dict], key: tuple) -> VersionEntry:
        snap = {op: dict(kw) for op, kw in tiles.items()}
        model = self.model

        # The tile_context wraps the *trace*: the body below runs as python
        # only while jax traces it (first call per shape), which is exactly
        # when kernels.dispatch reads the overrides.  Compiled re-runs never
        # enter it, so the process-global override table is irrelevant to
        # this entry's numerics — and the trace counter stays honest.
        def decode(params, inputs, cache, t):
            self.traces += 1
            with span(VCACHE_BUILD, kind="decode", bucket=1), \
                    dispatch.tile_context(snap):
                return model.decode_step(params, inputs, cache, t)

        def prefill_chunk(params, tokens, row_cache, t0, valid_len):
            self.traces += 1
            with span(VCACHE_BUILD, kind="prefill_chunk",
                      bucket=tokens.shape[1]), dispatch.tile_context(snap):
                return model.prefill_chunk(params, {"tokens": tokens},
                                           row_cache, t0, valid_len)

        # decode donates its cache (in-place KV/SSM update; the engine
        # adopts the returned cache every step); prefill_chunk must NOT —
        # its cache argument may be the shared pristine row (see module
        # docstring)
        return VersionEntry(key=key, tiles=snap,
                            decode=jax.jit(decode, donate_argnums=(2,)),
                            prefill_chunk=jax.jit(prefill_chunk))

    # ------------------------------------------------------------------
    def quantum(self, entry: VersionEntry, k: int, params: Any,
                cache: Any, batch: int) -> Callable:
        """The fused K-step decode executable for ``entry`` (built on
        first use, then cached on the entry).

        ``k`` is the static K-bucket; ``cache`` supplies the shapes to
        compile against (it is only read for shape/dtype here).  The
        executable is AOT-lowered and compiled against abstract shapes —
        warmup can pre-build every bucket without executing a single
        decode step — and donates the cache argument, so each of the K
        on-device steps updates the KV/SSM state in place.

        Raises :class:`StaticArgError` when ``k`` is not a hashable
        power-of-two int (unbucketed keys would silently compile one
        executable per distinct value)."""
        k = _pow2_bucket("k", k)
        fn = entry.quanta.get(k)
        if fn is not None:
            self.hits += 1
            return fn
        self.misses += 1
        snap = entry.tiles
        model = self.model

        def qfn(params, tokens, cache, pos, n_left):
            # -> (block, cache, (pos, routing counts or None))
            self.traces += 1
            with dispatch.tile_context(snap):
                block, cache, pos, routed = model.decode_quantum(
                    params, tokens, cache, pos, n_left, k)
            return block, cache, (pos, routed)

        vec = jax.ShapeDtypeStruct((int(batch),), jnp.int32)
        cache_sds = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        with span(VCACHE_BUILD, kind="quantum", bucket=k):
            fn = (jax.jit(qfn, donate_argnums=(2,))
                  .lower(params, vec, cache_sds, vec, vec).compile())
        entry.quanta[k] = fn
        return fn

    # ------------------------------------------------------------------
    def spec_quantum(self, entry: VersionEntry, k: int, d: int,
                     params: Any, cache: Any, batch: int) -> Callable:
        """The speculative verify executable for ``entry``, keyed per
        (K-bucket, draft depth) — like :meth:`quantum`, AOT-lowered
        against abstract shapes so warmup pre-builds every reachable
        (bucket, depth) pair and serve-time level switches stay a dict
        swap with zero retraces.

        ``k`` statically caps the per-row emission budget (a spec
        quantum emits at most ``min(k, d+1)`` tokens per row); ``d`` is
        the static draft depth that fixes the (B, d+1) verify shape.

        Raises :class:`StaticArgError` for a non-pow2/unhashable ``k``
        or a non-int ``d`` (see :meth:`quantum`)."""
        k, d = _pow2_bucket("k", k), _static_int("d", d)
        fn = entry.spec.get((k, d))
        if fn is not None:
            self.hits += 1
            return fn
        self.misses += 1
        snap = entry.tiles
        model = self.model

        def sfn(params, tokens, drafts, cache, pos, n_left):
            self.traces += 1
            with dispatch.tile_context(snap):
                return model.verify_quantum(
                    params, tokens, drafts, cache, pos,
                    jnp.minimum(n_left, jnp.int32(k)))

        vec = jax.ShapeDtypeStruct((int(batch),), jnp.int32)
        mat = jax.ShapeDtypeStruct((int(batch), d), jnp.int32)
        cache_sds = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), cache)
        with span(VCACHE_BUILD, kind="spec", bucket=k):
            fn = (jax.jit(sfn, donate_argnums=(3,))
                  .lower(params, vec, mat, cache_sds, vec, vec).compile())
        entry.spec[(k, d)] = fn
        return fn
