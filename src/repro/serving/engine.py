"""Batched serving engine (real JAX execution path).

Wraps a model's prefill/decode with continuous batching over request
slots: requests join free slots, prefill fills their cache rows, decode
steps run the whole batch, finished rows free their slots.  Decode is
*per-slot*: every slot advances at its own absolute position with its own
kv-valid horizon, so staggered admissions and mixed-length prompts are
exact — each slot's tokens match a sequential one-request-at-a-time
reference.  This is the engine the examples drive on CPU with reduced
models; at pod scale the same functions are jitted with the serve-mode
shardings (launch/serve.py).

Admission is chunked and length-bucketed: ``admit_request`` validates
the prompt and queues power-of-two-bucketed prefill chunks;
``prefill_step`` runs one chunk — the prefill-side dispatch quantum —
into the slot's accumulating row cache.  Compiled prefill shapes are
the bucket table, never the prompt-length distribution, so mixed-length
traffic performs zero post-warmup retraces, and the runtimes order
chunks against decode quanta by deadline (docs/ARCHITECTURE.md §5).

The VELTAIR integration point: ``set_interference_level`` selects the
code version the adaptive compiler produced for that pressure — either
from a compiled ``VersionSet`` (the multi-version tables of an analytical
ModelPlan) or from the built-in level table, which shrinks tiles as
pressure rises (locality -> parallelism, paper Fig. 6/9).  Executables
come from a per-engine :class:`~repro.serving.version_cache.VersionCache`
keyed by the tile configuration: every version is traced once (its tiles
baked in through a ``kernels.dispatch.tile_context``), after which a
level switch is a dictionary swap of already-compiled callables — no
retrace, and no interference between engines sharing the process.
``warmup()`` pre-builds the whole table ahead of time.  The engine is
oblivious to how the level was derived; repro.serving.runtime queries the
scheduling policy for it every step.
"""
from __future__ import annotations

import collections
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import cost_model as cm
from repro.core.counters import CounterBank
from repro.kernels import dispatch
from repro.models.model import Model, build_model, cache_batch_axis, path_keys
from repro.serving.paging import TRASH_PAGE, PagePool
from repro.serving.speculative import NgramDrafter
from repro.serving.spans import (
    ENGINE_ADMIT, ENGINE_BEGIN_QUANTUM, ENGINE_FINISH_QUANTUM,
    ENGINE_PREFILL_STEP, ENGINE_SET_LEVEL, PREFILL_DISPATCH,
    PREFILL_ROW_WRITE, PREFILL_SYNC, QUANTUM_BOOKKEEP, QUANTUM_DISPATCH,
    QUANTUM_SYNC, span)
from repro.serving.version_cache import VersionCache

# Fused-quantum executable sizes: a quantum of k decode steps runs as the
# smallest warmed bucket >= k (rows past their budget freeze on device, so
# an oversized bucket stays token-exact and only wastes the frozen tail).
# Quanta larger than the top bucket split into multiple fused calls.
QUANTUM_BUCKETS = (1, 2, 4, 8, 16)

# Default prefill chunk: prompts are split into chunks of this many
# tokens, each a schedulable quantum; the tail is padded UP to a
# power-of-two bucket, so the compiled prefill shapes are the bucket
# table {1, 2, ..., PREFILL_CHUNK_LEN}, not the prompt-length
# distribution — mixed-length traffic performs zero post-warmup retraces.
PREFILL_CHUNK_LEN = 16


def _next_pow2(n: int) -> int:
    return 1 << (max(int(n), 1) - 1).bit_length()

# Built-in interference-level -> tile table (one entry per grid level).
# Low pressure: big tiles, maximal reuse of the shared cache; high
# pressure: small private-cache-resident tiles that cede the LLC.  Every
# level is a distinct tile set the TPU compiler accepts as written: bm is
# a multiple of 16 (bf16 sublane tiling), bk and bn multiples of 128 (lane
# tiling), bkv a power of two that divides any power-of-two cache length,
# and the matmul working set shrinks strictly with the level.  Serving
# matmuls have M = batch x chunk <= 16 rows, so bm clamps there and the
# (bk, bn) pairs, all distinct, carry the level.
_LEVEL_TILES = (   # (bm, bk, bn, bkv)
    (256, 1024, 512, 512),
    (224, 768, 512, 512),
    (192, 512, 512, 512),
    (160, 512, 384, 512),
    (128, 512, 256, 256),
    (112, 384, 256, 256),
    (96, 256, 256, 256),
    (80, 384, 128, 128),
    (64, 256, 128, 128),
    (48, 128, 128, 128),
)
DEFAULT_LEVEL_TILES = tuple(
    {"matmul": {"bm": bm, "bk": bk, "bn": bn},
     "attention": {"bq": max(bm, 64), "bkv": bkv}}
    for bm, bk, bn, bkv in _LEVEL_TILES)
assert len(DEFAULT_LEVEL_TILES) == cm.NUM_LEVELS


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray            # (len,) int32
    max_new_tokens: int = 16
    output: list = dataclasses.field(default_factory=list)
    done: bool = False
    tier: str | None = None       # SLO tier (core.qos.TIER_ORDER); None =
                                  # untiered legacy request (standard urgency,
                                  # legacy qos_s-relative satisfaction)


@dataclasses.dataclass
class _PrefillState:
    """An in-flight chunked prefill occupying a slot (not yet decodable)."""
    req: Request
    row_cache: object              # accumulating batch-1 row cache
    schedule: collections.deque    # remaining chunk sizes (bucket table)
    done: int = 0                  # real prompt tokens prefilled so far
    # on-device routing counts of the chunks run so far (MoE models), read
    # back with the finishing chunk's sync
    routed: list = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class PrefillQuantum:
    """Result of one executed prefill chunk (``prefill_step``)."""
    slot: int
    rid: int
    chunk: int                     # padded chunk size dispatched
    tokens: int                    # real prompt tokens consumed
    finished: bool                 # prompt fully prefilled, first token out


@dataclasses.dataclass
class QuantumHandle:
    """An in-flight fused dispatch quantum.

    ``begin_quantum`` returns one of these *without* syncing: ``block``
    is still an on-device (possibly not-yet-computed) array, so a caller
    co-locating several engines can issue every engine's quantum before
    blocking on any of them — the device work overlaps instead of
    serializing through Python.  ``finish_quantum`` performs the single
    device->host sync and the request bookkeeping."""
    block: jax.Array               # (K, B) int32 on-device token block
    n_left: np.ndarray             # (B,) per-row steps actually budgeted
    steps: int                     # quantum length (max over rows)
    active: list[int]              # slots live at dispatch time
    row_steps: dict = dataclasses.field(default_factory=dict)  # rid -> steps
    # measured-counter bookkeeping: t0 is stamped AFTER the version-cache
    # lookup (and any AOT compile it performed), so the wall time closed
    # out by finish_quantum covers device work only — host-side scheduling
    # and compile time are charged by the runtimes, never double-counted
    # here.  traces0 snapshots the version-cache trace counter; a quantum
    # that traced inside its timed span is not observed at all.
    t0: float = 0.0                # perf_counter at dispatch (0 = untimed)
    traces0: int = -1              # version-cache traces at dispatch
    bucket: int = 0                # K-bucket the executable ran
    tiles: tuple = ()              # tiles key of the dispatched version
    # speculative quanta: kind == "spec" carries the on-device per-row
    # emission counts / pure acceptance counts; finish_quantum folds the
    # synced emission back into n_left so downstream accounting is shared
    kind: str = "decode"           # "decode" | "spec"
    emitted: jax.Array | None = None    # (B,) device n_emit (spec only)
    accepted: jax.Array | None = None   # (B,) device acceptance (spec only)
    drafted: int = 0               # draft depth dispatched (spec only)
    # on-device routing counts of a fused quantum (MoE models), read back
    # with its block in the one sync
    routed: jax.Array | None = None


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, *, batch_slots: int = 4,
                 max_len: int = 256,
                 version_sets: list | None = None,
                 quantum_buckets: tuple[int, ...] = QUANTUM_BUCKETS,
                 prefill_chunk_len: int = PREFILL_CHUNK_LEN,
                 page_size: int | None = None, n_pages: int | None = None,
                 page_reserve: str = "worst", prefix_sharing: bool = True,
                 ladder=None, speculative: bool = False,
                 spec_depth: int = 4, spec_ngram: int = 3,
                 spec_recurrent: bool = True):
        self.cfg = cfg
        self.model: Model = build_model(cfg)
        self.params = params
        self.slots = batch_slots
        self.max_len = max_len
        # paged KV cache: linear-attention cache leaves live in a global
        # page pool indexed through a per-slot page table; memory becomes
        # a scheduler-visible dimension (PagePool commitments gate
        # admission, free-page headroom clamps decode quanta) and common
        # prompt prefixes are deduplicated across requests (refcounted
        # shared pages + copy-on-write).  page_size=None keeps the dense
        # per-slot row layout.
        self.paged = page_size is not None
        self.page_size = int(page_size) if self.paged else 0
        self.page_reserve = page_reserve
        if self.paged:
            if self.page_size < 1 or max_len % self.page_size:
                raise ValueError(
                    f"page_size={page_size} must be >= 1 and divide "
                    f"max_len={max_len}")
            if page_reserve not in ("worst", "prompt"):
                raise ValueError(
                    f"page_reserve={page_reserve!r} not in ('worst', "
                    "'prompt')")
            self._paged_paths = self.model.paged_leaf_paths()
            if not self._paged_paths:
                raise ValueError(
                    f"{cfg.name}: no pageable (linear-KV) cache leaves — "
                    "recurrent-state models keep the dense layout")
            self.pages_per_slot = max_len // self.page_size
            if n_pages is None:
                n_pages = batch_slots * self.pages_per_slot
            self.pool: PagePool | None = PagePool(int(n_pages),
                                                 self.page_size)
            # prefix sharing splices pool pages under a partially-dense
            # row, so it needs every seq-axis leaf paged (pure-attention
            # families; hybrids would leak recurrent state)
            self.prefix_sharing = bool(prefix_sharing) \
                and self.model.all_cache_leaves_paged()
            self.cache = self.model.init_paged_cache(
                batch_slots, max_len, int(n_pages), self.page_size)
            # host mirror of the device page table + per-slot page maps
            self._page_table = np.zeros((batch_slots, self.pages_per_slot),
                                        np.int32)
            self._table_dirty = False
            self._slot_pages: list[dict[int, int]] = [
                {} for _ in range(batch_slots)]     # logical -> physical
            self._slot_shared: list[set[int]] = [
                set() for _ in range(batch_slots)]  # borrowed (COW-guarded)
            self._slot_commit = [0] * batch_slots   # reserved, unallocated
        else:
            self._paged_paths = frozenset()
            self.pages_per_slot = 0
            self.pool = None
            self.prefix_sharing = False
            self.cache = self.model.init_cache(batch_slots, max_len)
        self.slot_req: list[Request | None] = [None] * batch_slots
        self.slot_pos = np.zeros(batch_slots, np.int32)
        # chunked, length-bucketed admission: chunk sizes are powers of
        # two <= prefill_chunk_len, clamped so a padded tail can never
        # write past the cache's max_len rows
        self.prefill_chunk_len = min(_next_pow2(prefill_chunk_len),
                                     _next_pow2(max_len + 1) // 2 or 1)
        self.prefill_buckets = tuple(
            1 << i for i in range(self.prefill_chunk_len.bit_length()))
        self._prefill: dict[int, _PrefillState] = {}   # slot -> state (FIFO)
        self.prefill_chunks = 0        # chunk quanta executed
        self.prefill_tokens = 0        # real prompt tokens prefilled
        self.prefill_pad_tokens = 0    # bucket-padding tokens (waste)
        self.rejected_invalid = 0      # admissions refused for length
        # pristine single-slot cache row: admissions prefill from this so a
        # reused slot can never leak the previous tenant's KV / SSM state.
        # Paged engines prefill into a DENSE batch-1 row (chunk kernels are
        # layout-oblivious) and scatter it into the pools page-by-page at
        # finish, so the empty row is a dense row either way.
        self._empty_row = (self.model.init_cache(1, max_len) if self.paged
                           else self._slice_row(0))
        # adaptive-compilation state: tiles come from the dominant layer's
        # multi-version table when one is supplied; else from an autotuned
        # level ladder (the ``ladder`` argument — a LadderSpec or its raw
        # levels list — or, when neither is given, the process-global
        # ladder dispatch.load_ladder() installed); else the built-in
        # DEFAULT_LEVEL_TILES.  The ladder is snapshotted at build time so
        # later global installs never change a live engine's versions.
        self.version_sets = version_sets
        self._tile_source = (max(version_sets,
                                 key=lambda vs: vs.solo_version().flops)
                             if version_sets else None)
        lad = ladder if ladder is not None else dispatch.active_ladder()
        if lad is not None and hasattr(lad, "levels"):
            lad = lad.levels
        if lad is not None:
            if len(lad) != cm.NUM_LEVELS:
                raise ValueError(f"ladder has {len(lad)} levels, expected "
                                 f"{cm.NUM_LEVELS}")
            self._ladder = [{op: dict(kw) for op, kw in lvl.items()}
                            for lvl in lad]
        else:
            self._ladder = None
        # measured-counter loop: per-quantum wall times feed this bank;
        # the runtimes poll it through read_counters(source="measured").
        # co_runner_load is stamped by the cluster runtime before each
        # dispatch (observability on the recorded observations).
        self.counter_bank = CounterBank()
        self.co_runner_load = 0
        self.interference_level = 0.0
        self._active_tiles: dict | None = None
        self.level_switches = 0           # distinct-version switch count
        self.quantum_buckets = tuple(sorted(set(
            int(b) for b in quantum_buckets)))
        if not self.quantum_buckets or self.quantum_buckets[0] < 1:
            raise ValueError("quantum_buckets must be positive ints")
        # dispatch-granularity counters: the fused-quantum win is measured,
        # not asserted — tokens_per_sync is the tokens decoded per
        # device->host sync (1.0 on the per-step path, up to K fused)
        self.host_syncs = 0
        self.tokens_decoded = 0
        self.quantum_calls = 0
        # MoE routing over the real tokens of chunked prefill and fused
        # decode quanta, read back with the syncs those already make:
        # picks over all routed experts, and picks on experts held here
        self._routes = cfg.moe is not None
        self.moe_assignments = 0
        self.moe_held_assignments = 0
        # speculative decode quanta: a prompt-lookup drafter proposes up
        # to spec_depth tokens per row; one batched verify forward scores
        # them all (Model.verify_quantum) and the longest matching prefix
        # plus a corrected token is emitted.  Recurrent-state families
        # need the verify's restore pass; spec_recurrent=False turns
        # speculation off for them (plain-quantum fallback) instead.
        self.speculative = bool(speculative)
        self.spec_depth = int(spec_depth)
        if self.speculative and self.spec_depth < 1:
            raise ValueError("spec_depth must be >= 1")
        self._spec_enabled = self.speculative and (
            bool(spec_recurrent)
            or not self.model._has_nonseq_cache_leaves())
        self.drafter = (NgramDrafter(depth=self.spec_depth,
                                     max_ngram=int(spec_ngram))
                        if self.speculative else None)
        self.spec_quanta = 0       # speculative quanta dispatched
        self.spec_fallbacks = 0    # spec-eligible dispatches that fell back
        self.tokens_drafted = 0    # draft tokens submitted to verify
        self.tokens_accepted = 0   # draft tokens accepted (emitted past the
                                   # guaranteed corrected token)
        self.spec_rollbacks = 0    # row-quanta where a draft was rejected
        self._spec_accept_ewma = 1.0   # emitted tokens per spec dispatch
        self.version_cache = VersionCache(self.model)
        # per-engine row writer: O(row) in-place admission (donated cache +
        # dynamic_update_slice along the batch axis; slot is a traced
        # scalar, so one executable serves every slot)
        self._row_writer = self._make_row_writer()
        if self.paged:
            self._paged_row_writer = self._make_paged_row_writer()
            self._row_gather = self._make_row_gather()
            self._page_copier = self._make_page_copier()
        # occupancy telemetry (ServingMetrics.peak_cache_tokens /
        # cache_utilization sample these)
        self.peak_cache_tokens = 0
        self.peak_active_slots = 0
        self._use_version({})             # baseline: no overrides installed

    # ------------------------------------------------------------------
    def _use_version(self, tiles: dict) -> None:
        entry = self.version_cache.get(tiles)
        self._entry = entry
        self._prefill_chunk = entry.prefill_chunk
        self._decode = entry.decode

    @property
    def tokens_per_sync(self) -> float:
        return self.tokens_decoded / max(self.host_syncs, 1)

    @property
    def draft_hit_rate(self) -> float:
        """Accepted draft tokens / drafted tokens (0.0 before any spec
        quantum ran)."""
        return self.tokens_accepted / max(self.tokens_drafted, 1)

    @property
    def spec_stats(self) -> dict:
        """Speculative-decode counters for metrics / bench reports."""
        return {"spec_quanta": self.spec_quanta,
                "spec_fallbacks": self.spec_fallbacks,
                "tokens_drafted": self.tokens_drafted,
                "tokens_accepted": self.tokens_accepted,
                "draft_hit_rate": self.draft_hit_rate,
                "spec_rollbacks": self.spec_rollbacks}

    def expected_accept_per_step(self) -> float:
        """Expected tokens emitted per dispatched decode step (>= 1.0;
        1.0 exactly for non-speculative engines).  The SLO scheduler's
        EDF slack math multiplies its step budget by this, so a request
        whose remaining tokens would not fit the deadline at one
        token/step stays schedulable when speculation is landing
        multi-token quanta (an EWMA of recent acceptance)."""
        if not self._spec_enabled:
            return 1.0
        return max(1.0, float(self._spec_accept_ewma))

    def tiles_for_level(self, level: float) -> dict:
        """The tile table the compiled source selects at ``level``."""
        return self._tiles_for(cm.Interference.from_level(level))

    def _tiles_for(self, itf: cm.Interference) -> dict:
        if self._tile_source is not None:
            v = self._tile_source.select(itf)
            return {"matmul": {"bm": int(v.bm), "bk": int(v.bk),
                               "bn": int(v.bn)}}
        if self._ladder is not None:
            lvl = self._ladder[cm.level_to_idx(itf.level)]
            return {op: dict(kw) for op, kw in lvl.items()}
        return DEFAULT_LEVEL_TILES[cm.level_to_idx(itf.level)]

    def set_interference_level(self, level: float) -> dict:
        """Switch the active code version to the one compiled for
        ``level`` (0.0 = solo .. 1.0 = heavy co-location).

        Swaps in the version-cache entry for the matching tile
        configuration (already-compiled executables after ``warmup()`` or
        a prior visit — never a retrace) and atomically installs the same
        tiles in the process-global dispatch table for observability /
        out-of-engine callers: ops the new source does not override are
        cleared, so no stale per-op entry survives a source switch.
        Returns the installed override dict (observability / tests)."""
        itf = cm.Interference.from_level(level)
        tiles = self._tiles_for(itf)
        switched = tiles != self._active_tiles
        with span(ENGINE_SET_LEVEL, switched=int(switched)):
            if switched:
                dispatch.install_tile_overrides(tiles)
                self._use_version(tiles)
                self._active_tiles = tiles
                self.level_switches += 1
        self.interference_level = itf.level
        return {op: dict(kw) for op, kw in tiles.items()}

    def warmup(self, levels: list[float] | None = None,
               quantum_buckets: tuple[int, ...] | None = None) -> dict:
        """Ahead-of-time build AND execute the executables of every
        interference level (default: the full NUM_LEVELS grid), so later
        ``set_interference_level`` calls are dictionary swaps and the step
        that follows them never traces or compiles.

        Decode is shape-stable and always warmed, and so is every
        prefill-chunk bucket, so mixed-length traffic never retraces.
        Every fused K-bucket executable is AOT-compiled alongside
        (against abstract cache shapes — no decode steps run for them),
        so the first ``step_quantum`` after warmup never traces either;
        pass ``quantum_buckets`` to warm a subset.  Memory: one compiled
        decode + one fused executable per (distinct tile configuration,
        K-bucket), plus one chunked prefill per (configuration, chunk
        bucket).  Returns the version-cache stats snapshot."""
        if levels is None:
            levels = [cm.grid_point(i) for i in range(cm.NUM_LEVELS)]
        buckets = (self.quantum_buckets if quantum_buckets is None
                   else tuple(quantum_buckets))
        # the warm decode calls below donate self.cache and run at pos=0,
        # so snapshot any resident request rows and restore them after —
        # warming up mid-serving must not corrupt in-flight KV/SSM state
        live_rows = [(i, self._slice_row(i))
                     for i, r in enumerate(self.slot_req) if r is not None]
        if self.paged:
            # aim every slot at the trash page while warm decodes run:
            # their garbage writes land there, never in live pool pages
            self.cache["page_table"] = jnp.zeros_like(
                self.cache["page_table"])
            self._table_dirty = True
        toks = jnp.zeros((self.slots,), jnp.int32)
        pos = jnp.zeros((self.slots,), jnp.int32)
        # the currently-active version first (the no-override baseline an
        # engine serves with before its first level is set), then the table
        tile_tables = [self._active_tiles if self._active_tiles is not None
                       else {}]
        tile_tables += [self.tiles_for_level(lv) for lv in levels]
        for entry in self.version_cache.warmup(tile_tables):
            # decode donates its cache: adopt the returned one (numerics
            # are irrelevant here — live rows are always re-prefilled from
            # the pristine row at admission)
            logits, self.cache = entry.decode(self.params, {"tokens": toks},
                                              self.cache, pos)
            logits.block_until_ready()
            for k in buckets:
                self.version_cache.quantum(entry, k, self.params,
                                           self.cache, self.slots)
            if self._spec_enabled:
                # every reachable (bucket, depth) pair: the dispatch
                # bucket is the smallest one covering min(k, d+1), so
                # buckets above that are never requested
                cap = min(self.spec_depth + 1, self.quantum_buckets[-1])
                top = next(b for b in self.quantum_buckets if b >= cap)
                for k in buckets:
                    if k <= top:
                        self.version_cache.spec_quantum(
                            entry, k, self.spec_depth, self.params,
                            self.cache, self.slots)
            for cb in self.prefill_buckets:
                lg, _, _ = entry.prefill_chunk(
                    self.params, jnp.zeros((1, cb), jnp.int32),
                    self._empty_row, jnp.int32(0), jnp.int32(cb))
                lg.block_until_ready()
        if self.paged:
            # warm the engine-level paged helpers too (first admission /
            # COW must not compile mid-serving); all writes hit trash
            trash = jnp.zeros(self.pages_per_slot, jnp.int32)
            self._row_gather(self.cache, self._empty_row, trash)
            self.cache = self._page_copier(self.cache, jnp.int32(0),
                                           jnp.int32(0))
            for i, row in live_rows:
                self.cache = self._paged_row_writer(self.cache, row,
                                                    jnp.int32(i), trash)
            if not live_rows:
                self.cache = self._paged_row_writer(
                    self.cache, self._empty_row, jnp.int32(0), trash)
            self._sync_table()       # restore the real table from the mirror
        else:
            for i, row in live_rows:
                self.cache = self._row_writer(self.cache, row, jnp.int32(i))
        return dict(self.version_cache.stats)

    @property
    def active_slots(self) -> int:
        """Occupied request slots right now (the cluster runtime's live
        occupancy signal: co-runner demand is synthesized per occupied
        slot, so this is what the interference counters 'see')."""
        return sum(r is not None for r in self.slot_req)

    # ------------------------------------------------------------------
    def _free_slot(self) -> int | None:
        for i, r in enumerate(self.slot_req):
            if r is None:
                return i
        return None

    def _slice_row(self, slot: int):
        """Snapshot a slot as a dense batch-1 row cache.  On the paged
        engine only the dense (recurrent-state) leaves carry per-slot
        data worth saving — pool leaves are shared across slots and
        survive in place — so paged leaves come back as zero rows and the
        restoring write scatters them to the trash page."""
        if not self.paged:
            return jax.tree_util.tree_map_with_path(
                lambda p, c: jax.lax.slice_in_dim(c, slot, slot + 1,
                                                  axis=cache_batch_axis(p)),
                self.cache)
        paths = self._paged_paths

        def f(p, c, empty):
            if path_keys(p) in paths:
                return empty
            return jax.lax.slice_in_dim(c, slot, slot + 1,
                                        axis=cache_batch_axis(p))
        body = {k: v for k, v in self.cache.items() if k != "page_table"}
        return jax.tree_util.tree_map_with_path(f, body, self._empty_row)

    @staticmethod
    def _make_row_writer():
        """Jitted O(row) slot write: the batched cache is donated (updated
        in place) and the row lands via ``dynamic_update_slice_in_dim`` on
        its batch axis — admission cost scales with one row, not with the
        whole (slots, max_len) cache.  ``slot`` is a traced scalar, so a
        single executable serves every slot."""
        def write(cache, row_cache, slot):
            def put(p, c, r):
                return jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), slot, axis=cache_batch_axis(p))
            return jax.tree_util.tree_map_with_path(put, cache, row_cache)
        return jax.jit(write, donate_argnums=(0,))

    def _make_paged_row_writer(self):
        """Paged counterpart of the row writer: the dense batch-1 row is
        reshaped into pages and scattered to the physical destinations in
        ``wtab`` (pages_per_slot,) int32.  Entries mapped to the trash
        page absorb the content of shared / unallocated logical pages
        (borrowed prefixes must not be overwritten); dense leaves — the
        recurrent state of hybrid models — land on their batch axis as in
        the dense writer.  The device page table passes through
        untouched (it is host-owned, refreshed by ``_sync_table``)."""
        axes = self.model.paged_leaf_axes()
        n_slot, ps = self.pages_per_slot, self.page_size

        def write(cache, row_cache, slot, wtab):
            body = {k: v for k, v in cache.items() if k != "page_table"}

            def put(p, c, r):
                keys = path_keys(p)
                if keys in axes:
                    # dense row: batch (size 1) at ba, max_len at sa ->
                    # pages (n_slot) at ba, page positions (ps) at sa
                    ba, sa = axes[keys]
                    r = jnp.squeeze(r, ba)
                    r = r.reshape(*r.shape[:sa - 1], n_slot, ps,
                                  *r.shape[sa:])
                    rp = jnp.moveaxis(r, sa - 1, ba)
                    at = (slice(None),) * ba + (wtab,)
                    return c.at[at].set(rp.astype(c.dtype))
                return jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), slot, axis=cache_batch_axis(p))
            out = jax.tree_util.tree_map_with_path(put, body, row_cache)
            out["page_table"] = cache["page_table"]
            return out
        return jax.jit(write, donate_argnums=(0,))

    def _make_row_gather(self):
        """Materialize a slot's mapped pages into a dense batch-1 row —
        the shared-prefix admission path: borrowed pages land at their
        logical offsets so the unshared tail can prefill on top of them.
        ``trow`` entries still unmapped read the trash page; that garbage
        sits at positions the remaining chunks overwrite before any query
        attends to it.  Dense leaves keep the pristine empty row's
        state."""
        axes = self.model.paged_leaf_axes()

        def gather(cache, row_cache, trow):
            body = {k: v for k, v in cache.items() if k != "page_table"}

            def g(p, c, r):
                keys = path_keys(p)
                if keys not in axes:
                    return r
                # pool pages (n_slot) at ba, page positions at sa -> move
                # the pages next to the positions and merge into max_len
                ba, sa = axes[keys]
                pages = jnp.moveaxis(jnp.take(c, trow, axis=ba), ba, sa - 1)
                return pages.reshape(r.shape).astype(r.dtype)
            return jax.tree_util.tree_map_with_path(g, body, row_cache)
        return jax.jit(gather)

    def _make_page_copier(self):
        """Copy-on-write kernel: duplicate physical page ``src`` into
        ``dst`` across every pool leaf (one logical page occupies the
        same physical index in every layer's pool).  Traced scalars, so
        one executable serves every (src, dst) pair; the cache is donated
        (in-place update)."""
        axes = self.model.paged_leaf_axes()

        def copy(cache, src, dst):
            def cp(p, c):
                keys = path_keys(p)
                if keys not in axes:
                    return c
                ax = axes[keys][0]             # the pool's page axis
                page = jax.lax.dynamic_slice_in_dim(c, src, 1, axis=ax)
                return jax.lax.dynamic_update_slice_in_dim(c, page, dst,
                                                           axis=ax)
            return jax.tree_util.tree_map_with_path(cp, cache)
        return jax.jit(copy, donate_argnums=(0,))

    # ------------------------------------------------------------------
    # Page accounting (paged engines only)
    # ------------------------------------------------------------------
    def _sync_table(self) -> None:
        """Push the host page-table mirror to the device when stale.  The
        table rides inside the cache pytree, so every compiled executable
        already takes it — no signature change, no retrace."""
        if self.paged and self._table_dirty:
            self.cache["page_table"] = jnp.asarray(self._page_table)
            self._table_dirty = False

    def _alloc_page(self, slot: int) -> int | None:
        """One physical page for ``slot``, drawing down its admission
        commitment first (those draws cannot fail by construction);
        uncommitted draws may return None when the pool's free surplus is
        exhausted (counted as a stall by the pool)."""
        assert self.pool is not None
        if self._slot_commit[slot] > 0:
            self._slot_commit[slot] -= 1
            return self.pool.alloc(reserved=True)
        return self.pool.alloc(reserved=False)

    def _probe_prefix(self, prompt) -> tuple[list, tuple | None]:
        """Published pages covering a prefix of ``prompt``: the list of
        full-page hits [(logical, physical), ...] plus an optional
        partial-tail hit — a published page whose token span *covers* the
        entire remaining prompt (the borrower attends only to its own
        prefix of the page; positions beyond are causally masked until
        copy-on-write privatizes them)."""
        assert self.pool is not None
        ps = self.page_size
        toks = tuple(int(t) for t in prompt)
        n = len(toks)
        shared: list[tuple[int, int]] = []
        j = 0
        while (j + 1) * ps <= n:
            phys = self.pool.lookup(toks[:j * ps], toks[j * ps:(j + 1) * ps])
            if phys is None:
                break
            shared.append((j, phys))
            j += 1
        partial = None
        rem = toks[j * ps:]
        if rem and len(rem) < ps:
            phys = self.pool.lookup_covering(toks[:j * ps], rem)
            if phys is not None:
                partial = (j, phys)
        return shared, partial

    def admission_pages(self, prompt,
                        max_new_tokens: int) -> tuple[int, int | None]:
        """(pages_needed, pages_free) for the admission controller: the
        worst-case pages this request would commit (net of shareable
        prefix pages) and the pool's uncommitted free surplus.  Dense
        engines report (0, None) — memory is not a conflict dimension
        there."""
        if not self.paged:
            return 0, None
        assert self.pool is not None
        n = len(prompt)
        shared: list = []
        if self.prefix_sharing:
            shared, _ = self._probe_prefix(prompt)
        horizon = (n + max(int(max_new_tokens), 1)
                   if self.page_reserve == "worst" else n + 1)
        need = self.pool.pages_for(min(horizon, self.max_len)) - len(shared)
        return max(need, 0), self.pool.uncommitted_free

    def _paged_admit(self, req: Request, slot: int,
                     n: int) -> tuple[int, object] | None:
        """Page-pool side of admission: probe the prefix index, commit
        the worst-case page budget, map shared pages (refcounted) and
        allocate owned pages covering the unshared prompt region.
        Returns (start, row_cache) — the prefill start offset (shared
        tokens skip prefill; the final prompt token always prefills so
        the first-token logits exist) and the row to prefill into — or
        None when the pool cannot commit (counted as a page conflict)."""
        assert self.pool is not None
        pool, ps = self.pool, self.page_size
        shared: list[tuple[int, int]] = []
        partial: tuple | None = None
        if self.prefix_sharing:
            shared, partial = self._probe_prefix(req.prompt)
        horizon = (n + max(req.max_new_tokens, 1)
                   if self.page_reserve == "worst" else n + 1)
        commit = max(
            pool.pages_for(min(horizon, self.max_len)) - len(shared), 0)
        if not pool.commit(commit):
            return None
        self._slot_commit[slot] = commit
        pages = self._slot_pages[slot]
        borrowed = self._slot_shared[slot]
        pages.clear()
        borrowed.clear()
        trow = self._page_table[slot]
        trow[:] = TRASH_PAGE
        shared_len = len(shared) * ps
        if partial is not None:
            shared = shared + [partial]
            shared_len = n
        for j, phys in shared:
            pool.retain(phys)
            pool.shared_hits += 1
            pages[j] = phys
            borrowed.add(j)
            trow[j] = phys
        # owned pages covering the rest of the prompt (commitment covers
        # every one of them, so these allocations cannot fail)
        for j in range(len(shared), pool.pages_for(n)):
            phys = self._alloc_page(slot)
            assert phys is not None
            pages[j] = phys
            trow[j] = phys
        self._table_dirty = True
        # the final prompt token must prefill even when fully shared:
        # its forward pass produces the first-token logits
        start = min(shared_len, n - 1)
        if start > 0:
            row = self._row_gather(self.cache, self._empty_row,
                                   jnp.asarray(trow))
        else:
            row = self._empty_row
        return start, row

    def _write_table(self, slot: int) -> np.ndarray:
        """Scatter destinations for a finished prefill row: owned pages
        keep their physical index, borrowed and unmapped pages divert to
        the trash page (their content either already lives in the pool or
        was never real)."""
        wtab = np.full(self.pages_per_slot, TRASH_PAGE, np.int32)
        borrowed = self._slot_shared[slot]
        for j, phys in self._slot_pages[slot].items():
            if j not in borrowed:
                wtab[j] = phys
        return wtab

    def _publish_slot_pages(self, slot: int, req: Request) -> None:
        """Advertise the slot's owned FULL prompt pages in the pool's
        prefix index.  Partial tail pages are never published — decode
        writes into them, and unpublished pages need no COW for their
        owner (published spans end at or before the prompt, decode writes
        strictly after, so an owner never writes its own published
        page)."""
        if not (self.paged and self.prefix_sharing):
            return
        assert self.pool is not None
        ps = self.page_size
        toks = tuple(int(t) for t in req.prompt)
        n = len(toks)
        borrowed = self._slot_shared[slot]
        for j, phys in self._slot_pages[slot].items():
            if j not in borrowed and (j + 1) * ps <= n:
                self.pool.publish(toks[:j * ps], toks[j * ps:(j + 1) * ps],
                                  phys)

    def release_slot(self, slot: int) -> None:
        """Invalidate a freed slot's cache state before reuse — the
        completion-side half of the pristine-row guarantee (admission
        writes a pristine row; release must not leave the previous
        tenant's state reachable).  Dense: scatter the empty row over the
        slot.  Paged: drop the slot's page references (a page frees when
        its last holder leaves; published pages another request still
        shares survive), return unused commitment, and park the table row
        on the trash page."""
        self.slot_req[slot] = None
        self.slot_pos[slot] = 0
        if not self.paged:
            self.cache = self._row_writer(self.cache, self._empty_row,
                                          jnp.int32(slot))
            return
        assert self.pool is not None
        for phys in self._slot_pages[slot].values():
            self.pool.release(phys)
        self._slot_pages[slot].clear()
        self._slot_shared[slot].clear()
        self.pool.uncommit(self._slot_commit[slot])
        self._slot_commit[slot] = 0
        self._page_table[slot, :] = TRASH_PAGE
        self._table_dirty = True

    def _paged_preflight(self, active: list[int],
                         n_left: np.ndarray) -> np.ndarray:
        """Map / privatize every page the coming decode writes touch.

        For each row writing positions [pos, pos + n_left): allocate
        missing pages (commitment first), and privatize borrowed pages
        before the first write — copy-on-write when other holders remain,
        plain ownership takeover (unpublish) when this slot is the last.
        Rows that cannot get a page are clamped to the last mapped
        position (pool counts the stall); with page_reserve="worst"
        stalls are impossible by construction.  Ends by refreshing the
        device table."""
        assert self.pool is not None
        pool, ps = self.pool, self.page_size
        for i in active:
            steps = int(n_left[i])
            if steps <= 0:
                continue
            pos = int(self.slot_pos[i])
            pages = self._slot_pages[i]
            borrowed = self._slot_shared[i]
            for j in range(pos // ps, (pos + steps - 1) // ps + 1):
                phys = pages.get(j)
                if phys is None:
                    new = self._alloc_page(i)
                    if new is None:
                        n_left[i] = max(j * ps - pos, 0)
                        break
                    pages[j] = new
                    self._page_table[i, j] = new
                    self._table_dirty = True
                elif j in borrowed:
                    if pool.refcount(phys) > 1:
                        new = self._alloc_page(i)
                        if new is None:
                            n_left[i] = max(j * ps - pos, 0)
                            break
                        self.cache = self._page_copier(
                            self.cache, jnp.int32(phys), jnp.int32(new))
                        pool.release(phys)
                        pool.cow_copies += 1
                        pages[j] = new
                        self._page_table[i, j] = new
                    else:
                        # sole holder: take ownership; stop advertising
                        # the original tokens (content will diverge)
                        pool.unpublish(phys)
                    borrowed.discard(j)
                    self._table_dirty = True
        self._sync_table()
        return n_left

    def decode_k_headroom(self, k: int) -> int:
        """Clamp a decode quantum to free-page headroom: the largest
        k' <= k whose worst-case new-page demand across decodable rows
        the pool can satisfy right now.  Never below 1 — the per-row
        preflight clamps (and counts) rows a single step cannot map.
        Dense engines return k unchanged; the SLO scheduler calls this
        before sizing a quantum so memory pressure shrinks quanta instead
        of surfacing as mid-quantum stalls."""
        if not self.paged or k <= 1:
            return max(int(k), 1)
        assert self.pool is not None
        ps = self.page_size
        rows = []
        for i, req in enumerate(self.slot_req):
            if req is None or i in self._prefill:
                continue
            need = req.max_new_tokens + 1 - len(req.output)
            room = self.max_len - 1 - int(self.slot_pos[i])
            rows.append((int(self.slot_pos[i]),
                         max(1, min(need, room)),
                         self._slot_pages[i]))
        free = self.pool.free_pages
        best = 1
        for kk in range(1, int(k) + 1):
            demand = 0
            for pos, budget, pages in rows:
                steps = min(kk, budget)
                demand += sum(
                    1 for j in range(pos // ps, (pos + steps - 1) // ps + 1)
                    if j not in pages)
            if demand > free:
                break
            best = kk
        return best

    # ------------------------------------------------------------------
    # Occupancy telemetry
    # ------------------------------------------------------------------
    @property
    def cache_valid_tokens(self) -> int:
        """Tokens resident on behalf of live requests (prefilled plus
        decoded positions across occupied slots)."""
        total = 0
        for i, req in enumerate(self.slot_req):
            if req is None:
                continue
            st = self._prefill.get(i)
            total += st.done if st is not None else int(self.slot_pos[i])
        return total

    @property
    def cache_resident_tokens(self) -> int:
        """Token capacity the cache actually holds resident: dense rows
        pin slots * max_len regardless of occupancy; paged residency is
        allocated pages only, with shared pages counted once — the
        dedup win prefix sharing buys."""
        if self.paged:
            assert self.pool is not None
            return self.pool.used_pages * self.page_size
        return self.slots * self.max_len

    @property
    def cache_utilization(self) -> float:
        """Peak valid tokens / peak resident token capacity.  Dense
        engines divide by the pinned slots * max_len; paged engines by
        the page high-water mark — and because shared pages are resident
        once but valid for every holder, prefix sharing can push this
        past 1.0 (that IS the dedup win)."""
        cap = (self.pool.peak_used * self.page_size
               if self.paged and self.pool is not None
               else self.slots * self.max_len)
        return self.peak_cache_tokens / cap if cap else 0.0

    def _note_occupancy(self) -> None:
        self.peak_active_slots = max(self.peak_active_slots,
                                     self.active_slots)
        self.peak_cache_tokens = max(self.peak_cache_tokens,
                                     self.cache_valid_tokens)

    @property
    def page_stats(self) -> dict:
        """Pool counters for benches / cluster metrics ({} when dense)."""
        if not self.paged:
            return {}
        assert self.pool is not None
        p = self.pool
        return {"page_size": self.page_size, "total_pages": p.total,
                "used_pages": p.used_pages, "peak_used": p.peak_used,
                "committed": p.committed, "shared_hits": p.shared_hits,
                "cow_copies": p.cow_copies, "stalls": p.stalls,
                "conflicts": p.conflicts,
                "published": p.published_pages}

    def _prefill_schedule(self, n: int, start: int = 0) -> collections.deque:
        """Chunk sizes for an ``n``-token prompt: fixed-size full chunks
        plus a power-of-two tail bucket (padded up), split further if the
        padding would write past ``max_len``.  Every size is a power of
        two <= ``prefill_chunk_len``, so the compiled-prefill shape set
        is the bucket table, never the prompt-length distribution.
        ``start`` skips tokens already resident (shared prefix pages):
        the schedule covers [start, n) only."""
        out: collections.deque = collections.deque()
        done = start
        c = self.prefill_chunk_len
        while n - done >= c:
            out.append(c)
            done += c
        rem = n - done
        while rem:
            b = _next_pow2(rem)
            if done + b <= self.max_len:
                out.append(b)                  # padded tail bucket
                break
            out.append(b // 2)                 # largest pow2 < rem, all real
            done += b // 2
            rem -= b // 2
        return out

    def admit_request(self, req: Request, *, drain: bool = False) -> bool:
        """Reserve a slot for ``req`` and queue its prefill chunks WITHOUT
        executing them — callers meter prefill by pumping
        :meth:`prefill_step` (runtimes interleave it with decode quanta).
        ``drain=True`` additionally pumps queued chunks (oldest first)
        until this request's first token is out — the synchronous
        convenience path for tests/examples.

        Returns False when no slot is free (retry later).  Raises
        ``ValueError`` for prompts the cache row cannot hold — empty, or
        ``len(prompt) >= max_len`` (a clamped row write would silently
        corrupt the cache); such a request must be dropped, not retried."""
        with span(ENGINE_ADMIT, model=self.cfg.name, rid=req.rid):
            n = len(req.prompt)
            if n < 1 or n >= self.max_len:
                self.rejected_invalid += 1
                raise ValueError(
                    f"prompt length {n} outside [1, {self.max_len - 1}]: the "
                    f"cache row holds max_len={self.max_len} positions and "
                    "needs at least one free for decode")
            slot = self._free_slot()
            if slot is None:
                return False
            start, row = 0, self._empty_row
            if self.paged:
                admitted = self._paged_admit(req, slot, n)
                if admitted is None:
                    return False     # pool cannot commit (counted as conflict)
                start, row = admitted
            self.slot_req[slot] = req
            self.slot_pos[slot] = n
            self._prefill[slot] = _PrefillState(
                req=req, row_cache=row,
                schedule=self._prefill_schedule(n, start), done=start)
            self._note_occupancy()
            if drain:
                while not req.output:
                    self.prefill_step()
            return True

    @property
    def prefill_pending(self) -> int:
        """Slots whose prompts are not fully prefilled yet."""
        return len(self._prefill)

    def prefill_queue(self) -> list[tuple[int, int, int]]:
        """Slots mid-prefill, FIFO order: (slot, rid, chunks_left).  The
        SLO scheduler's view of the prefill backlog — it picks the slot
        whose TTFT deadline is tightest instead of the oldest one."""
        return [(slot, st.req.rid, len(st.schedule))
                for slot, st in self._prefill.items()]

    def decode_backlog(self) -> list[tuple[int, int, int]]:
        """Decodable slots: (slot, rid, tokens_left).  ``tokens_left`` is
        the remaining decode budget (the SRPT/slack estimate the SLO
        scheduler sizes decode quanta from)."""
        out = []
        for i, req in enumerate(self.slot_req):
            if req is None or i in self._prefill:
                continue
            need = req.max_new_tokens + 1 - len(req.output)
            room = self.max_len - 1 - int(self.slot_pos[i])
            out.append((i, req.rid, max(1, min(need, room))))
        return out

    def prefill_step(self, slot: int | None = None) -> PrefillQuantum | None:
        """Run ONE prefill chunk — the prefill-side dispatch quantum —
        for ``slot``, or for the oldest slot still prefilling when
        ``slot`` is None.  The runtimes pass the slot whose TTFT deadline
        is tightest; ``drain`` and ``run_to_completion`` pass nothing.

        The chunk prefills into the slot's accumulating batch-1 row cache
        at its start-position offset; only the final chunk pays a
        device->host sync (the first-token argmax) and writes the row
        into the batched cache, making the slot decodable.  Returns what
        ran, or None when nothing is prefilling."""
        if not self._prefill:
            return None
        with span(ENGINE_PREFILL_STEP, model=self.cfg.name):
            if slot is None:
                slot, st = next(iter(self._prefill.items()))
            else:
                st = self._prefill[slot]
            c = st.schedule.popleft()
            n = len(st.req.prompt)
            valid = min(c, n - st.done)
            toks = np.zeros(c, np.int32)
            toks[:valid] = st.req.prompt[st.done:st.done + valid]
            finished = not st.schedule
            traces0 = self.version_cache.traces
            t0 = time.perf_counter()
            with span(PREFILL_DISPATCH, model=self.cfg.name, rid=st.req.rid,
                      start=st.done, chunk=c, tokens=valid,
                      finished=int(finished)):
                logits, st.row_cache, routed = self._prefill_chunk(
                    self.params, jnp.asarray(toks)[None], st.row_cache,
                    jnp.int32(st.done), jnp.int32(valid))
            if self._routes:
                st.routed.append(routed)
            st.done += valid
            self.prefill_chunks += 1
            self.prefill_tokens += valid
            self.prefill_pad_tokens += c - valid
            if finished:
                with span(PREFILL_ROW_WRITE, rid=st.req.rid):
                    if self.paged:
                        self.cache = self._paged_row_writer(
                            self.cache, st.row_cache, jnp.int32(slot),
                            jnp.asarray(self._write_table(slot)))
                        self._publish_slot_pages(slot, st.req)
                    else:
                        self.cache = self._row_writer(self.cache, st.row_cache,
                                                      jnp.int32(slot))
                with span(PREFILL_SYNC, rid=st.req.rid) as sync:
                    if self._routes:
                        # veltair: ignore[host-sync-in-hot-path] the ONE sanctioned sync per admission (finishing chunk only), the prompt's routing counts riding along
                        first, routed = jax.device_get(
                            (jnp.argmax(logits[0]), st.routed))
                        self._count_routing(sync, sum(routed))
                        first = int(first)
                    else:
                        # veltair: ignore[host-sync-in-hot-path] the ONE sanctioned sync per admission (finishing chunk only)
                        first = int(jnp.argmax(logits[0]))
                # only the finishing chunk syncs, so only it yields a usable
                # wall time (intermediate chunks are async dispatches whose
                # device work this sync may still be draining — keying the
                # observation by the full prompt's pow2 bucket keeps walls
                # comparable); the trace guard drops first-visit compiles
                # like the decode path
                if traces0 == self.version_cache.traces:
                    self.counter_bank.observe(
                        "prefill", _next_pow2(max(st.done, 1)),
                        self._entry.key, time.perf_counter() - t0,
                        tokens=valid, co_runners=self.co_runner_load)
                self.host_syncs += 1
                self.tokens_decoded += 1
                st.req.output.append(first)
                del self._prefill[slot]
            self._note_occupancy()
            return PrefillQuantum(slot=slot, rid=st.req.rid, chunk=c,
                                  tokens=valid, finished=finished)

    def step(self) -> list[Request]:
        """One decode step for every active slot; returns finished reqs.
        Slots still mid-prefill are not decodable and are skipped.

        Thin wrapper over the unified quantum path: a per-step dispatch
        is a 1-step non-fused quantum (one sync, one token per row)."""
        return self.finish_quantum(self.begin_quantum(1, fused=False))

    # ------------------------------------------------------------------
    # Fused dispatch quanta
    # ------------------------------------------------------------------
    def begin_quantum(self, k: int, *,
                      fused: bool = True) -> QuantumHandle | None:
        """Dispatch up to ``k`` decode steps for every active slot,
        without syncing.  This is THE decode entry point: :meth:`step`
        and :meth:`step_quantum` are thin wrappers over it.

        With ``fused=True`` the quantum runs as ONE fused on-device
        executable.  Per-row budgets (``n_left``) clamp each slot to its
        remaining token/length allowance and to ``k``; rows past their
        budget freeze on device (token, position and cache), so the
        result is token-for-token identical to ``k`` sequential
        :meth:`step` calls.  The executed quantum is capped at the
        largest K-bucket — callers dispatching bigger quanta issue
        further calls with the leftover (one sync each).

        With ``fused=False`` one plain decode step is dispatched (``k``
        is ignored beyond being positive).  No runtime takes this path:
        it is the fused quantum's test oracle, kept on the same handle
        protocol so both modes do identical bookkeeping in
        :meth:`finish_quantum`.  Returns ``None`` when no slot is active
        (slots still mid-prefill are not decodable)."""
        active = [i for i, r in enumerate(self.slot_req)
                  if r is not None and i not in self._prefill]
        if not active or k <= 0:
            return None
        k = int(k)
        with span(ENGINE_BEGIN_QUANTUM, model=self.cfg.name):
            n_left = np.zeros(self.slots, np.int32)
            toks = np.zeros(self.slots, np.int32)
            for i in active:
                req = self.slot_req[i]
                need = req.max_new_tokens + 1 - len(req.output)
                room = self.max_len - 1 - int(self.slot_pos[i])
                # a live row always decodes at least one step — exactly what
                # sequential step() does before its finish check, and it keeps
                # degenerate admissions (max_new_tokens=0, prompt at the length
                # limit) finishing instead of spinning with a zero budget
                n_left[i] = max(1, min(need, room))
                toks[i] = req.output[-1]
            if fused and self._spec_enabled:
                handle = self._try_spec_quantum(k, active, n_left.copy(), toks)
                if handle is not None:
                    return handle
                # no usable draft / no room for the d+1 write span: the plain
                # fused quantum below is the per-row fallback
            if self.paged:
                cap = (1 if not fused else min(k, self.quantum_buckets[-1]))
                n_left = self._paged_preflight(active,
                                               np.minimum(n_left, cap))
                if not any(n_left[i] > 0 for i in active):
                    return None      # every decodable row waits on a free page
            if not fused:
                # per-slot positions: each row decodes at its own absolute
                # position and attends under its own kv-valid horizon, so
                # mixed-length / staggered prompts stay exact (free slots
                # compute garbage rows that the next admission's pristine-row
                # prefill replaces)
                n_left = np.minimum(n_left, 1)
                traces0 = self.version_cache.traces
                t0 = time.perf_counter()
                with self._dispatch_span(active, k, 1, 1, n_left, "decode"):
                    logits, self.cache = self._decode(
                        self.params, {"tokens": jnp.asarray(toks)}, self.cache,
                        jnp.asarray(self.slot_pos))
                return QuantumHandle(block=jnp.argmax(logits, axis=-1)[None],
                                     n_left=n_left, steps=1, active=active,
                                     t0=t0, traces0=traces0, bucket=1,
                                     tiles=self._entry.key)
            steps = int(min(k, int(n_left.max()), self.quantum_buckets[-1]))
            bucket = next(b for b in self.quantum_buckets if b >= steps)
            n_left = np.minimum(n_left, steps)
            qfn = self.version_cache.quantum(self._entry, bucket, self.params,
                                             self.cache, self.slots)
            # timestamp AFTER the executable lookup: a cold K-bucket's AOT
            # compile is host-side cost the runtimes charge, not device work
            # the measured counters may attribute to interference
            traces0 = self.version_cache.traces
            t0 = time.perf_counter()
            with self._dispatch_span(active, k, steps, bucket, n_left,
                                     "decode"):
                block, self.cache, (_, routed) = qfn(
                    self.params, jnp.asarray(toks), self.cache,
                    jnp.asarray(self.slot_pos), jnp.asarray(n_left))
            self.quantum_calls += 1
            return QuantumHandle(block=block, n_left=n_left, steps=steps,
                                 active=active, t0=t0, traces0=traces0,
                                 bucket=bucket, tiles=self._entry.key,
                                 routed=routed)

    def _dispatch_span(self, active: list[int], asked: int, steps: int,
                       bucket: int, n_left: np.ndarray, kind: str):
        """The ``engine.quantum.dispatch`` span of one decode call."""
        return span(QUANTUM_DISPATCH, model=self.cfg.name, slots=self.slots,
                    rows=len(active), prefilling=len(self._prefill),
                    asked=asked, steps=steps, bucket=bucket,
                    tokens=int(n_left.sum()), kind=kind)

    def _try_spec_quantum(self, k: int, active: list[int],
                          n_left: np.ndarray,
                          toks: np.ndarray) -> QuantumHandle | None:
        """Dispatch one speculative verify quantum, or return None to
        fall back to the plain fused quantum (no usable draft anywhere,
        a row too close to the cache end for the static d+1 write span,
        or — on paged engines — not enough free-page headroom for the
        worst-case d+1 writes per row).  The fallback never retraces:
        both paths run warmed executables."""
        d = self.spec_depth
        # the verify writes positions [pos, pos + d] for every active row
        # regardless of acceptance, so every row needs d steps of room
        if any(self.max_len - 1 - int(self.slot_pos[i]) < d
               for i in active):
            self.spec_fallbacks += 1
            return None
        if self.paged and self.decode_k_headroom(d + 1) < d + 1:
            # free-page headroom clamps the draft depth; with a static
            # depth that clamp IS the fallback to plain quanta
            self.spec_fallbacks += 1
            return None
        drafts = np.zeros((self.slots, d), np.int32)
        n_drafted = 0
        for i in active:
            req = self.slot_req[i]
            dr = self.drafter.draft(
                np.concatenate([np.asarray(req.prompt, np.int32),
                                np.asarray(req.output, np.int32)]), d)
            if dr is not None:
                drafts[i] = dr
                n_drafted += 1
        if n_drafted == 0:
            # adversarial (low-hit-rate) traffic: a verify forward would
            # emit one token per row for d+1 positions of compute — the
            # plain quantum is strictly better, so take it
            self.spec_fallbacks += 1
            return None
        cap = min(max(int(k), 1), d + 1, self.quantum_buckets[-1])
        n_left = np.minimum(n_left, cap)
        if self.paged:
            span = np.zeros(self.slots, np.int32)
            for i in active:
                span[i] = d + 1
            span = self._paged_preflight(active, span)
            # writes past a row's mapped span land on the trash page;
            # tokens whose KV lives there must never be emitted
            n_left = np.minimum(n_left, span)
            if not any(n_left[i] > 0 for i in active):
                self.spec_fallbacks += 1
                return None
        bucket = next(b for b in self.quantum_buckets if b >= cap)
        sfn = self.version_cache.spec_quantum(
            self._entry, bucket, d, self.params, self.cache, self.slots)
        traces0 = self.version_cache.traces
        t0 = time.perf_counter()
        with self._dispatch_span(active, k, cap, bucket, n_left, "spec"):
            block, n_emit, accepted, self.cache, _ = sfn(
                self.params, jnp.asarray(toks), jnp.asarray(drafts),
                self.cache, jnp.asarray(self.slot_pos), jnp.asarray(n_left))
        self.quantum_calls += 1
        self.spec_quanta += 1
        self.tokens_drafted += d * len(active)
        # steps=1: a verify quantum is ONE sequence-parallel forward —
        # that is the whole speedup — so virtual clocks charge it like a
        # single decode step while it emits up to min(k, d+1) tokens/row
        return QuantumHandle(block=block, n_left=n_left, steps=1,
                             active=active, t0=t0, traces0=traces0,
                             bucket=bucket, tiles=self._entry.key,
                             kind="spec", emitted=n_emit,
                             accepted=accepted, drafted=d)

    def finish_quantum(self, handle: QuantumHandle | None) -> list[Request]:
        """Block on a dispatched quantum — the single device->host sync at
        the quantum boundary — and do the request bookkeeping: append each
        row's tokens, advance positions, free finished slots.  Returns
        finished requests (like :meth:`step`); per-request executed steps
        land in ``handle.row_steps``."""
        if handle is None:
            return []
        with span(ENGINE_FINISH_QUANTUM, model=self.cfg.name):
            with span(QUANTUM_SYNC) as sync:
                if handle.routed is not None:
                    # veltair: ignore[host-sync-in-hot-path] THE sanctioned per-quantum sync (block and routing counts in one transfer)
                    block, routed = jax.device_get((handle.block,
                                                    handle.routed))
                    self._count_routing(sync, routed)
                    emitted = accepted = None
                elif handle.kind == "spec":
                    # ONE fused sync for the whole spec quantum: token
                    # block plus per-row emission/acceptance come back in
                    # a single device->host transfer instead of three
                    # veltair: ignore[host-sync-in-hot-path] THE sanctioned per-quantum sync (spec path: fused triple)
                    block, emitted, accepted = jax.device_get(
                        (handle.block, handle.emitted, handle.accepted))
                else:
                    # veltair: ignore[host-sync-in-hot-path] THE sanctioned per-quantum sync (one block transfer per quantum)
                    block = np.asarray(handle.block)
                    emitted = accepted = None
            with span(QUANTUM_BOOKKEEP):
                return self._bookkeep_quantum(handle, block, emitted,
                                              accepted)

    def _count_routing(self, sync, routed) -> None:
        """Add one sync's routing counts (assigned, held, peak) to the
        counters and to its span's arguments."""
        assigned, held, peak = (int(v) for v in routed)
        self.moe_assignments += assigned
        self.moe_held_assignments += held
        sync.set_metadata(moe_assigned=assigned, moe_held=held,
                          moe_peak=peak)

    def _bookkeep_quantum(self, handle: QuantumHandle, block, emitted,
                          accepted) -> list[Request]:
        """Everything ``finish_quantum`` does after its sync, on host
        values: counters, the measured-counter observation, each row's
        tokens and positions, and freeing finished slots."""
        self.host_syncs += 1
        if handle.kind == "spec":
            block = np.asarray(block)
            emitted = np.asarray(emitted).astype(np.int32)
            accepted = np.asarray(accepted)
            # fold the actual per-row emission into n_left so every
            # consumer below (and in the runtimes) sees real token counts
            handle.n_left = emitted
            d = handle.drafted
            for i in handle.active:
                self.tokens_accepted += max(int(emitted[i]) - 1, 0)
                if int(accepted[i]) < d:
                    self.spec_rollbacks += 1
            if handle.active:
                mean = float(emitted[handle.active].sum()) \
                    / len(handle.active)
                self._spec_accept_ewma = (0.8 * self._spec_accept_ewma
                                          + 0.2 * mean)
        # measured counters: the sync above closed the quantum's device
        # span; observe it unless it was untimed or traced mid-span (a
        # first-visit compile inside the timed region must not read as
        # interference slowdown — the trace guard drops it).  Speculative
        # quanta observe under their own kind: their wall/token ratio
        # varies with acceptance, and folding them into "decode" floors
        # would read as phantom interference slowdown
        if handle.t0 > 0.0 and \
                handle.traces0 == self.version_cache.traces:
            self.counter_bank.observe(
                handle.kind, handle.bucket, handle.tiles,
                time.perf_counter() - handle.t0,
                tokens=int(handle.n_left.sum()),
                co_runners=self.co_runner_load)
        finished = []
        for i in handle.active:
            req = self.slot_req[i]
            took = int(handle.n_left[i])
            req.output.extend(int(t) for t in block[:took, i])
            self.slot_pos[i] += took
            self.tokens_decoded += took
            handle.row_steps[req.rid] = took
        self._note_occupancy()               # peak before finished rows free
        for i in handle.active:
            req = self.slot_req[i]
            if len(req.output) >= req.max_new_tokens + 1 or \
                    self.slot_pos[i] >= self.max_len - 1:
                req.done = True
                finished.append(req)
                self.release_slot(i)
        return finished

    def step_quantum(self, k: int) -> list[Request]:
        """Fused ``k``-step decode with exactly one host sync: dispatch +
        collect in one call (use :meth:`begin_quantum` /
        :meth:`finish_quantum` to overlap several engines)."""
        return self.finish_quantum(self.begin_quantum(k))

    def run_to_completion(self, reqs: list[Request],
                          max_steps: int = 10_000, *,
                          fused: bool = True) -> list[Request]:
        """Serve ``reqs`` to completion.  Decode runs on the fused
        quantum path by default (largest warmed K-bucket per dispatch,
        one sync each); ``fused=False`` keeps the per-token reference
        loop.  Both produce identical token streams."""
        pending = collections.deque(reqs)
        done: list[Request] = []
        k = self.quantum_buckets[-1] if fused else 1
        steps = 0
        while (pending or any(r is not None for r in self.slot_req)) \
                and steps < max_steps:
            while pending and self.admit_request(pending[0]):
                pending.popleft()
            while self._prefill:        # drain queued chunks before decode
                self.prefill_step()
            done.extend(self.finish_quantum(self.begin_quantum(
                k, fused=fused)))
            steps += 1
        return done
