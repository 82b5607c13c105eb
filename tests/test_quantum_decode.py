"""Fused dispatch quanta: ``step_quantum(k)`` must be token-for-token
identical to ``k`` sequential ``step()`` calls — under staggered
admissions, mixed prompt lengths, mid-quantum completions (per-request
``max_new_tokens`` so rows freeze at different steps inside one quantum)
and level switches at quantum boundaries — in both the XLA reference
path and Pallas interpret mode.  The quantum boundary is also the host
boundary: exactly ONE device->host sync per fused call, and a full level
sweep after ``warmup()`` leaves the version-cache trace counter flat
with the fused quantum entries already present."""
import dataclasses
import functools
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import cost_model as cm
from repro.kernels import dispatch
from repro.models import build_model
from repro.models.model import cache_batch_axis, path_keys
from repro.models.params import abstract_params
from repro.serving.engine import Request, ServingEngine

MAX_LEN = 32


def _sequential_reference(model, params, prompt, n_new):
    """One request alone through the raw model — the ground truth."""
    cache = model.init_cache(1, MAX_LEN)
    logits, cache = model.prefill(
        params, {"tokens": jnp.asarray(prompt, jnp.int32)[None]}, cache)
    out = [int(jnp.argmax(logits[0]))]
    t = len(prompt)
    for _ in range(n_new):
        logits, cache = model.decode_step(
            params, {"tokens": jnp.asarray([out[-1]], jnp.int32)}, cache,
            jnp.int32(t))
        out.append(int(jnp.argmax(logits[0])))
        t += 1
    return out
PROMPT_LENS = (3, 7, 2)          # deliberately misaligned
MAX_NEW = (6, 3, 5)              # rows complete at different quantum steps


def _reduced(name):
    """The reduced config of ``name``; recurrentgemma's local-attention
    window is cut below MAX_LEN so its ring cache wraps (overwrites
    entries the row still reads) inside a test's sequences."""
    cfg = get_reduced_config(name)
    if cfg.rglru is not None:
        cfg = dataclasses.replace(
            cfg, rglru=dataclasses.replace(cfg.rglru, window_size=8))
    return cfg


@functools.cache
def _family(name):
    """A reduced model of ``name`` with its params and the shared
    prompts."""
    cfg = _reduced(name)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(13)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    return cfg, model, params, prompts


@pytest.fixture(scope="module")
def setup():
    return _family("gemma-2b")


@pytest.fixture(autouse=True)
def _clean_dispatch():
    yield
    dispatch.set_mode("xla")
    dispatch.clear_tile_overrides()


def _make_reqs(prompts):
    return [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, MAX_NEW))]


# The shared schedule: admissions and level switches happen only at
# quantum boundaries, so the fused and per-step runs see byte-identical
# request state at every boundary.  (quantum, level, admit_next) tuples.
SCHEDULE = [(2, 0.0, True), (3, 1.0, True), (4, 0.3, False),
            (2, 1.0, True), (4, 0.0, False), (8, 0.6, False),
            (8, 0.6, False), (8, 0.0, False)]


def _run_schedule(cfg, params, prompts, *, fused):
    """-> (engine, requests, frozen): ``frozen`` counts the fused quanta
    in which some active row's budget fell short of the executed bucket,
    so that row froze on device."""
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                           quantum_buckets=(2, 4))
    reqs = _make_reqs(prompts)
    pending = list(reqs)
    frozen = 0
    for k, level, admit in SCHEDULE:
        if admit and pending:
            if engine.admit_request(pending[0], drain=True):
                pending.pop(0)
        engine.set_interference_level(level)
        if fused:
            h = engine.begin_quantum(k)
            if h is not None:
                frozen += any(h.n_left[i] < h.bucket for i in h.active)
            engine.finish_quantum(h)
        else:
            for _ in range(k):
                engine.step()
        if not pending and all(r.done for r in reqs):
            break
    assert all(r.done for r in reqs), "schedule must drain every request"
    return engine, reqs, frozen


# One case per cache kind: gemma-2b (MQA linear KV, both dispatch modes),
# starcoder2-3b (GQA linear KV), mamba2-780m (SSM conv/state),
# recurrentgemma-2b (RG-LRU state + wrapping local-window ring).
@pytest.mark.parametrize("arch,mode", [
    pytest.param("gemma-2b", "xla", id="xla"),
    pytest.param("gemma-2b", "interpret", id="interpret"),
    pytest.param("starcoder2-3b", "xla", id="starcoder2-3b-xla"),
    pytest.param("mamba2-780m", "xla", id="mamba2-780m-xla"),
    pytest.param("recurrentgemma-2b", "xla", id="recurrentgemma-2b-xla"),
])
def test_quantum_identical_to_sequential_steps(arch, mode):
    cfg, _, params, prompts = _family(arch)
    dispatch.set_mode(mode)
    _, want, _ = _run_schedule(cfg, params, prompts, fused=False)
    eng, got, frozen = _run_schedule(cfg, params, prompts, fused=True)
    for w, g in zip(want, got):
        assert g.output == w.output, (arch, mode, g.rid, g.output, w.output)
    # rows froze inside a quantum, and the fused run really coarsened the
    # dispatch unit
    assert frozen >= 1
    assert eng.quantum_calls >= 3
    assert eng.tokens_per_sync > 1.0


def test_exactly_one_host_sync_per_quantum(setup):
    """Acceptance: the host blocks once per fused quantum — the sync
    counter advances by exactly 1 per step_quantum regardless of how many
    tokens the quantum decoded."""
    cfg, _, params, prompts = setup
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    reqs = _make_reqs(prompts)
    engine.admit_request(reqs[0], drain=True)
    engine.admit_request(reqs[1], drain=True)
    while any(r is not None for r in engine.slot_req):
        syncs0, toks0 = engine.host_syncs, engine.tokens_decoded
        engine.step_quantum(4)
        assert engine.host_syncs == syncs0 + 1
        assert engine.tokens_decoded > toks0
    # per-step baseline: one sync per token
    engine2 = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    engine2.admit_request(_make_reqs(prompts)[0], drain=True)
    s0 = engine2.host_syncs
    engine2.step()
    engine2.step()
    assert engine2.host_syncs == s0 + 2


def test_quanta_beyond_max_bucket_split_and_stay_exact(setup):
    """A quantum larger than the top K-bucket is executed in bucket-sized
    fused chunks (one sync each) and stays token-identical."""
    cfg, model, params, prompts = setup
    want = _sequential_reference(model, params, prompts[0], 9)
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN,
                           quantum_buckets=(1, 2))
    req = Request(rid=0, prompt=prompts[0], max_new_tokens=9)
    engine.admit_request(req, drain=True)
    calls = 0
    while not req.done:
        h = engine.begin_quantum(16)
        assert h.steps <= 2, "capped at the largest warmed bucket"
        engine.finish_quantum(h)
        calls += 1
    assert calls >= 5                      # 9 tokens in <=2-step chunks
    assert req.output[:10] == want[:10]


def test_row_budget_freezes_exactly_at_bucket_edge(setup):
    """K-bucket boundary: a row whose remaining budget equals the
    executed bucket exactly must emit precisely that many tokens and
    freeze — no off-by-one at the pow2 edge, and the next quantum picks
    it up at the right position."""
    cfg, model, params, prompts = setup
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                           quantum_buckets=(2, 4))
    # rid 0 needs exactly 4 more tokens (== the top bucket); rid 1 has
    # plenty — one fused call must retire rid 0 at the edge exactly
    r0 = Request(rid=0, prompt=prompts[0], max_new_tokens=4)
    r1 = Request(rid=1, prompt=prompts[1], max_new_tokens=9)
    engine.admit_request(r0, drain=True)
    engine.admit_request(r1, drain=True)
    h = engine.begin_quantum(4)
    assert h.steps == 4 and h.bucket == 4
    fin = engine.finish_quantum(h)
    assert [r.rid for r in fin] == [0], "rid 0 retires at the edge"
    assert len(r0.output) == 5            # prefill token + exactly 4
    assert h.row_steps[0] == 4 and h.row_steps[1] == 4
    want = _sequential_reference(model, params, prompts[0], 4)
    assert r0.output == want[:5]
    while not r1.done:
        engine.step_quantum(4)
    assert r1.output == _sequential_reference(model, params,
                                              prompts[1], 9)[:10]


def test_k_beyond_largest_warmed_bucket_selects_top_bucket(setup):
    """K-bucket boundary: requesting k past the largest warmed bucket
    dispatches the top bucket's executable (no new compile, no phantom
    bucket key) and leaves the remainder for further calls."""
    cfg, _, params, prompts = setup
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN,
                           quantum_buckets=(2, 4))
    engine.warmup()
    vc = engine.version_cache
    misses0 = vc.misses
    engine.admit_request(Request(rid=0, prompt=prompts[0],
                                 max_new_tokens=20), drain=True)
    h = engine.begin_quantum(16)
    assert h.steps == 4 and h.bucket == 4, "capped at the top bucket"
    engine.finish_quantum(h)
    assert vc.misses == misses0, "no executable built past the ladder"
    for entry in vc._entries.values():
        assert set(entry.quanta) <= {2, 4}, "no bucket key beyond warmed"


def test_mid_quantum_completion_frees_slot_for_next_admission(setup):
    """A row finishing mid-quantum frees its slot at the boundary, and
    the next admission into that slot is pristine (no leaked state from
    the frozen tail of the previous tenant)."""
    cfg, model, params, prompts = setup
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    short = Request(rid=0, prompt=prompts[0], max_new_tokens=2)
    engine.admit_request(short, drain=True)
    engine.step_quantum(8)                 # freezes after 2 steps
    assert short.done
    assert engine._free_slot() == 0
    want = _sequential_reference(model, params, prompts[2], 4)
    nxt = Request(rid=1, prompt=prompts[2], max_new_tokens=4)
    engine.admit_request(nxt, drain=True)
    while not nxt.done:
        engine.step_quantum(4)
    assert nxt.output[:5] == want[:5]


def test_level_sweep_after_warmup_traces_flat_with_quanta(setup):
    """Acceptance: warmup pre-builds the fused K-buckets alongside the
    level table, so a full level sweep dispatching fused quanta performs
    zero traces and zero version-cache misses."""
    cfg, _, params, prompts = setup
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                           quantum_buckets=(2, 4))
    engine.warmup()
    vc = engine.version_cache
    for entry in vc._entries.values():
        assert set(entry.quanta) == {2, 4}, "buckets prebuilt at warmup"
    traces0, misses0 = vc.traces, vc.misses
    engine.admit_request(Request(rid=0, prompt=prompts[0],
                               max_new_tokens=64), drain=True)
    for i in range(cm.NUM_LEVELS):
        engine.set_interference_level(cm.grid_point(i))
        engine.step_quantum(3)
    assert vc.traces == traces0, "no trace after warmup"
    assert vc.misses == misses0, "every fused dispatch is a cache hit"
    assert engine.quantum_calls == cm.NUM_LEVELS


def test_zero_budget_request_finishes_under_fused_dispatch(setup):
    """Degenerate admissions (max_new_tokens=0) must complete in fused
    mode exactly like the per-step loop (one decode then the finish
    check), not spin forever with a zero quantum budget."""
    cfg, _, params, prompts = setup

    def run(fused):
        engine = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
        req = Request(rid=0, prompt=prompts[0], max_new_tokens=0)
        engine.admit_request(req, drain=True)
        for _ in range(4):
            if req.done:
                break
            engine.step_quantum(4) if fused else engine.step()
        return req

    want, got = run(False), run(True)
    assert want.done and got.done, "zero-budget request must finish"
    assert got.output == want.output


def test_warmup_mid_serving_preserves_inflight_state(setup):
    """warmup() donates and rewrites the batched cache for its warm decode
    calls — resident request rows must be snapshotted and restored, so a
    mid-serving warmup never changes the tokens an in-flight request
    produces."""
    cfg, model, params, prompts = setup
    want = _sequential_reference(model, params, prompts[0], 6)
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    req = Request(rid=0, prompt=prompts[0], max_new_tokens=6)
    engine.admit_request(req, drain=True)
    engine.step()
    engine.step()
    engine.warmup()   # mid-serving warmup
    while not req.done:
        engine.step_quantum(4)
    assert req.output[:7] == want[:7]


def test_admission_write_is_jitted_and_row_local(setup):
    """The O(row) admission path: repeated admissions reuse one compiled
    row-writer executable (slot index is traced, so slot 0 and slot 1
    share it) and never corrupt resident rows."""
    cfg, model, params, prompts = setup
    want = [_sequential_reference(model, params, p, 3) for p in prompts]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=3)
            for i, p in enumerate(prompts)]
    done = engine.run_to_completion(list(reqs))
    assert len(done) == len(reqs)
    for i, r in enumerate(reqs):
        assert r.output[:4] == want[i][:4]


def _rows(cache, row):
    """{key path: leaf restricted to batch row ``row``} of a dense cache."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    return {path_keys(p): np.asarray(jnp.take(leaf, row,
                                              axis=cache_batch_axis(p)))
            for p, leaf in flat}


@pytest.mark.parametrize("arch", ["starcoder2-3b", "mamba2-780m",
                                  "recurrentgemma-2b",
                                  "deepseek-v2-lite-16b"])
def test_frozen_row_cache_contract(arch):
    """One fused quantum of K=4 steps over three rows: row 0 is frozen
    for the whole quantum, row 1 freezes after 2 steps, row 2 runs all 4.
    Frozen rows keep recurrent and ring leaves bit-exact whole, and linear
    KV leaves bit-exact on every visible position (< pos); the live work
    equals per-step decode of each row's own budget."""
    cfg = _reduced(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    b, plen, k = 3, 6, 4
    prompts = jax.random.randint(jax.random.PRNGKey(2), (b, plen), 0,
                                 cfg.vocab_size, jnp.int32)
    logits, cache0 = jax.jit(model.prefill)(
        params, {"tokens": prompts}, model.init_cache(b, MAX_LEN))
    toks0 = jnp.argmax(logits, -1).astype(jnp.int32)
    pos0 = jnp.full((b,), plen, jnp.int32)
    n_left = np.array([0, 2, k], np.int32)

    qfn = jax.jit(model.decode_quantum, static_argnums=(5,))
    block, cache_q, pos_q, _ = qfn(params, toks0, cache0, pos0,
                                jnp.asarray(n_left), k)
    np.testing.assert_array_equal(np.asarray(pos_q), plen + n_left)

    # per-step decode: after[s] is the whole batch stepped s times
    step = jax.jit(model.decode_step)
    after, toks, pos = [cache0], toks0, pos0
    for i in range(k):
        lg, c = step(params, {"tokens": toks}, after[-1], pos)
        toks = jnp.argmax(lg, -1).astype(jnp.int32)
        pos = pos + 1
        after.append(c)
        assert int(block[i, 2]) == int(toks[2])

    axes = model.paged_leaf_axes()
    for row, n in enumerate(n_left.tolist()):
        got, want = _rows(cache_q, row), _rows(after[n], row)
        vis = plen + n                         # visible positions < vis
        replay = _rows(after[n + 1], row) if n < k else None
        for key, g in got.items():
            if key not in axes:                # recurrent / ring: whole
                np.testing.assert_array_equal(
                    g, want[key], err_msg=f"{arch} row {row} {key}")
                continue
            seq = axes[key][1] - 1             # the row axis is dropped
            np.testing.assert_array_equal(
                g.take(range(vis), seq), want[key].take(range(vis), seq),
                err_msg=f"{arch} row {row} {key} visible positions")
            if replay is not None:     # frozen: the replayed write is
                np.testing.assert_array_equal(   # the next live step's
                    g.take(vis, seq), replay[key].take(vis, seq),
                    err_msg=f"{arch} row {row} {key} replayed write")


def _compiled_quantum(model, cache, b, **jit_kw):
    """``decode_quantum`` (4 steps over ``b`` rows) compiled for abstract
    params and ``cache``."""
    rows = jax.ShapeDtypeStruct((b,), jnp.int32)
    return jax.jit(model.decode_quantum, static_argnums=(5,),
                   **jit_kw).lower(abstract_params(model.param_specs()),
                                   rows, cache, rows, rows, 4).compile()


@pytest.mark.parametrize("arch", ["starcoder2-3b", "mamba2-780m",
                                  "recurrentgemma-2b",
                                  "deepseek-v2-lite-16b"])
def test_quantum_selects_only_state_leaves(arch):
    """Structural guard on the compiled decode quantum: no ``select``
    produces a whole stacked linear KV leaf (a per-step pass over the
    entire KV cache), while every recurrent / ring leaf keeps its
    frozen-row select.  Batch and length are picked so no leaf's shape
    coincides with another's."""
    model = build_model(_reduced(arch))
    b = 5
    cache = abstract_params(model.cache_specs(b, 48))
    hlo = _compiled_quantum(model, cache, b).as_text()
    selected = {tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"= \w+\[([\d,]*)\]\S* select\(", hlo)}
    linear = model.paged_leaf_paths()
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    for path, leaf in flat:
        if path_keys(path) in linear:
            assert leaf.shape not in selected, (arch, path_keys(path))
        else:
            assert leaf.shape in selected, (arch, path_keys(path))


@pytest.mark.parametrize("arch", ["starcoder2-3b", "gemma-2b",
                                  "deepseek-v2-lite-16b"])
def test_quantum_writes_stacked_cache_in_place(arch):
    """Structural guard on the layer scan's cache contract: stacked
    linear KV leaves ride in the scan's carry and each layer writes its
    token into the stack in place.  So the compiled decode quantum, cache
    donated, holds no copy or dynamic-update-slice of a whole stacked
    leaf and no copy of one layer, and its temporaries stay under half
    the stacks' bytes (taking the stack in as ``xs`` and out as ``ys``
    held a restacked copy and copied it into the step carry: 1.25-1.34x
    here).  GQA, MQA and MLA (whose unstacked first layer stays out).

    Eight layers and 2048 positions, so the cache outweighs the
    temporaries of weights and activations.  The cache is float32: the
    CPU has no bfloat16 scatter and converts the whole operand to float32
    and back, a pass the TPU does not make; ``test_tpu_compile.py``
    checks the served bfloat16 quantum as the chip compiles it."""
    model = build_model(dataclasses.replace(_reduced(arch), num_layers=8))
    cache = jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.float32),
        abstract_params(model.cache_specs(8, 2048)))
    compiled = _compiled_quantum(model, cache, 8, donate_argnums=(2,))
    linear = model.paged_leaf_paths()
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)
    stacks = [leaf for path, leaf in flat
              if path_keys(path)[0] == "blocks" and path_keys(path) in linear]
    assert stacks, arch
    whole = {leaf.shape for leaf in stacks}
    layer = {leaf.shape[1:] for leaf in stacks}
    hlo = compiled.as_text()
    for dims, op in re.findall(
            r"= \w+\[([\d,]*)\]\S* (copy|dynamic-update-slice)\(", hlo):
        shape = tuple(int(d) for d in dims.split(",") if d)
        assert shape not in whole, (arch, op, shape)
        assert op != "copy" or shape not in layer, (arch, op, shape)
    stack_bytes = sum(leaf.size * leaf.dtype.itemsize for leaf in stacks)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < stack_bytes / 2, (arch, temp, stack_bytes)
