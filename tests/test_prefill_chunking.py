"""Chunked, length-bucketed prefill quanta (ISSUE-5 acceptance).

(1) Chunked+padded admission is token-for-token identical to a plain
per-request ``Model.prefill`` + ``Model.decode_step`` greedy loop under
staggered admissions and mixed prompt lengths — across every cache
family the engines serve (linear KV, SSM state, RG-LRU recurrence +
window ring buffer); the ring-buffer wrap (prompt longer than the
attention window) is exact too.
(2) A ``prompt_len_spread > 0`` workload served after ``warmup()``
performs ZERO jax retraces (the compiled prefill shapes are the bucket
table, not the prompt-length distribution) — xla and interpret modes.
(3) Admission validates prompt length: ``len >= max_len`` raises (the
old path silently corrupted the cache row via a clamped
``dynamic_update_slice``) and the runtimes count it as a conflict.
(4) Prefill is metered: a long-prompt admission advances the virtual
clock and TTFT, and prefill chunks interleave with co-resident decode
quanta instead of stalling them.
(5) Co-located tenants get per-tenant prompt streams (seed offset).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import cost_model as cm
from repro.core.scheduler import FixedBlockPolicy, VeltairPolicy
from repro.kernels import dispatch
from repro.models import build_model
from repro.serving import OnlineRuntime, Workload, build_paper_plans
from repro.serving.engine import Request, ServingEngine

from greedy_ref import manual_greedy

HW = cm.CPU_3990X
TENANTS = ["resnet50", "googlenet"]
MAX_LEN = 32
# deliberately mixed: multi-chunk, padded tail, sub-chunk, non-pow2
PROMPT_LENS = (13, 7, 19, 5)
N_NEW = 4


@pytest.fixture(scope="module")
def plans():
    return build_paper_plans(TENANTS, HW)


@pytest.fixture(scope="module")
def models():
    built = {}
    for i, arch in enumerate(("gemma-2b", "mamba2-780m",
                              "recurrentgemma-2b")):
        cfg = get_reduced_config(arch)
        model = build_model(cfg)
        built[arch] = (cfg, model, model.init(jax.random.PRNGKey(i)))
    return built


@pytest.fixture(autouse=True)
def _clean_dispatch():
    yield
    dispatch.set_mode("xla")
    dispatch.clear_tile_overrides()


def _prompts(cfg, lens, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
            for n in lens]


def _staggered(engine, prompts):
    """Admissions at different steps into 2 slots (slot reuse included)."""
    reqs = [Request(rid=i, prompt=p, max_new_tokens=N_NEW)
            for i, p in enumerate(prompts)]
    pending = list(reqs)
    assert engine.admit_request(pending.pop(0), drain=True)
    engine.step()
    assert engine.admit_request(pending.pop(0), drain=True)
    engine.step()
    engine.step()
    engine.run_to_completion(pending)
    assert all(r.done for r in reqs)
    return reqs


# ---------------------------------------------------------------------------
# (1) token identity: chunked+bucketed == the plain per-request reference
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("arch", ["gemma-2b", "mamba2-780m",
                                  "recurrentgemma-2b"])
def test_chunked_prefill_token_identity(models, arch):
    cfg, model, params = models[arch]
    prompts = _prompts(cfg, PROMPT_LENS)
    want = [manual_greedy(model, params, p, N_NEW, MAX_LEN) for p in prompts]
    chunk = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                          prefill_chunk_len=8)
    got = _staggered(chunk, prompts)
    for w, g in zip(want, got):
        assert g.output == w, (arch, g.rid, g.output, w)
    # the chunked engine really went through the bucketed path
    assert chunk.prefill_chunks > len(prompts)       # 13 and 19 split
    assert chunk.prefill_pad_tokens > 0              # 13, 19, 5 padded
    assert chunk.prefill_tokens == sum(PROMPT_LENS)


def test_chunked_prefill_token_identity_interpret(models):
    cfg, model, params = models["gemma-2b"]
    dispatch.set_mode("interpret")
    prompts = _prompts(cfg, PROMPT_LENS[:2])
    want = [manual_greedy(model, params, p, N_NEW, MAX_LEN) for p in prompts]
    chunk = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                          prefill_chunk_len=8)
    got = _staggered(chunk, prompts)
    for w, g in zip(want, got):
        assert g.output == w, (g.rid, g.output, w)


def test_window_ring_wrap_chunked_matches_monolithic(models):
    """Prompt longer than the hybrid's attention window: chunked prefill
    must reproduce the ring-buffer eviction pattern bit-exactly."""
    cfg, model, params = models["recurrentgemma-2b"]
    window = cfg.rglru.window_size
    n, max_len = window + 13, 2 * window
    prompt = _prompts(cfg, (n,), seed=11)[0]

    def decode_tail(cache, logits, steps=4):
        out = [int(jnp.argmax(logits[0]))]
        t = n
        for _ in range(steps):
            logits, cache = model.decode_step(
                params, {"tokens": jnp.asarray([out[-1]], jnp.int32)},
                cache, jnp.int32(t))
            out.append(int(jnp.argmax(logits[0])))
            t += 1
        return out

    cache = model.init_cache(1, max_len)
    lg, cache = model.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                              cache)
    want = decode_tail(cache, lg)

    cache = model.init_cache(1, max_len)
    done, c = 0, 16
    while done < n:
        valid = min(c, n - done)
        toks = np.zeros(c, np.int32)
        toks[:valid] = prompt[done:done + valid]
        lg, cache, _ = model.prefill_chunk(
            params, {"tokens": jnp.asarray(toks)[None]}, cache,
            jnp.int32(done), jnp.int32(valid))
        done += valid
    assert decode_tail(cache, lg) == want


# ---------------------------------------------------------------------------
# (2) mixed-length serving with zero post-warmup retraces
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_mixed_length_serve_zero_retraces_after_warmup(models, plans, mode):
    cfg, _, params = models["gemma-2b"]
    dispatch.set_mode(mode)
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                           prefill_chunk_len=8)
    engine.warmup()                      # buckets only, no prompt lengths
    vc = engine.version_cache
    traces0, misses0 = vc.traces, vc.misses
    runtime = OnlineRuntime(engine, VeltairPolicy(HW), plans, HW)
    wl = Workload.poisson(TENANTS, 60, 6, prompt_len=12, max_new_tokens=3,
                          seed=2, prompt_len_spread=9)
    assert len(set(wl.prompt_lengths())) > 1, "spread must mix lengths"
    m = runtime.serve(wl)
    assert m.n_queries == wl.n_queries
    assert vc.traces == traces0, "mixed lengths must not retrace"
    assert vc.misses == misses0, "every dispatch is a version-cache hit"
    assert runtime.prefill_quanta > 0
    assert engine.prefill_pad_tokens > 0, "bucket padding exercised"


# ---------------------------------------------------------------------------
# (3) admission-time length validation
# ---------------------------------------------------------------------------
def test_admission_boundary_lengths(models):
    cfg, _, params = models["gemma-2b"]
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=16,
                           prefill_chunk_len=8)
    # longest admissible prompt: max_len - 1 (one row left for decode)
    ok = Request(rid=0, prompt=_prompts(cfg, (15,))[0], max_new_tokens=4)
    done = engine.run_to_completion([ok])
    assert done and ok.done and len(ok.output) >= 2
    # inadmissible: empty, exactly max_len, beyond max_len
    for n in (0, 16, 17):
        bad = Request(rid=1, prompt=_prompts(cfg, (n or 1,))[0][:n],
                      max_new_tokens=1)
        with pytest.raises(ValueError):
            engine.admit_request(bad)
    assert engine.rejected_invalid == 3
    # a rejected admission must not leak its slot
    assert engine._free_slot() is not None


def test_runtime_counts_oversized_prompts_as_conflicts(models, plans):
    cfg, _, params = models["gemma-2b"]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    runtime = OnlineRuntime(engine, VeltairPolicy(HW), plans, HW)
    wl = Workload.poisson(TENANTS, 60, 4, prompt_len=MAX_LEN,
                          max_new_tokens=2, seed=1)
    m = runtime.serve(wl)
    assert runtime.conflicts == wl.n_queries
    assert m.conflict_rate == 1.0
    assert not runtime.records, "oversized prompts must be dropped"


# ---------------------------------------------------------------------------
# (4) prefill is metered: clock, TTFT, interleaving
# ---------------------------------------------------------------------------
def test_long_prompt_admission_advances_clock(models, plans):
    """Regression: admission used to be free in virtual time — now a
    17-token prompt at chunk 4 is five metered quanta before TTFT."""
    cfg, _, params = models["gemma-2b"]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                           prefill_chunk_len=4)
    runtime = OnlineRuntime(engine, VeltairPolicy(HW), plans, HW)
    wl = Workload([(0.0, "resnet50")], prompt_len=17, max_new_tokens=2)
    m = runtime.serve(wl)
    assert runtime.prefill_quanta == 5          # [4, 4, 4, 4, 1]
    rec = runtime.records[0]
    assert rec.ttft_s == pytest.approx(5 * runtime.step_dt)
    assert m.avg_ttft_s == pytest.approx(rec.ttft_s)
    # latency includes the metered prefill plus the decode steps
    assert rec.latency >= 7 * runtime.step_dt - 1e-12


def test_prefill_chunks_interleave_with_decode(models, plans):
    """Prefill chunks are quanta the earliest-deadline pick orders
    against decode quanta.  Two same-length untiered prompts back to
    back have equal deadlines: the pick drains prefill first, oldest
    prompt first, and both rows then decode in one batch.  Give the
    first prompt the interactive tier and the second the batch tier, and
    the interactive request's decode completes before the batch prompt's
    prefill does — a co-resident admission no longer stalls it."""
    cfg, _, params = models["gemma-2b"]

    def serve(tenants, tiers=None):
        engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                               prefill_chunk_len=4)
        runtime = OnlineRuntime(engine, FixedBlockPolicy(HW, 1), plans, HW)
        runtime.serve(Workload([(0.0, t) for t in tenants], prompt_len=12,
                               max_new_tokens=2, tiers=tiers))
        assert len(runtime.records) == 2
        assert runtime.prefill_quanta == 6          # 3 chunks per prompt
        first, second = sorted(runtime.records,
                               key=lambda r: (r.finish, r.ttft_s))
        return runtime, first, second

    runtime, first, second = serve(["resnet50", "resnet50"])
    dt = runtime.step_dt
    assert [ev[0] for ev in runtime.sched_trace] == ["prefill"] * 6 + \
        ["decode"] * 2
    assert first.ttft_s == pytest.approx(3 * dt)
    assert second.ttft_s == pytest.approx(6 * dt)
    assert first.finish == second.finish == pytest.approx(8 * dt)

    _, first, second = serve(["resnet50", "googlenet"],
                             {"resnet50": "interactive",
                              "googlenet": "batch"})
    assert first.tier == "interactive"
    assert first.ttft_s < second.ttft_s
    # the first request finished before the second's prefill completed
    assert first.finish < second.arrival + second.ttft_s


# ---------------------------------------------------------------------------
# (5) per-tenant prompt streams in the cluster
# ---------------------------------------------------------------------------
def test_cluster_tenant_prompts_differ_but_stay_deterministic():
    from repro.serving import ClusterRuntime, build_cluster
    archs = ["gemma-2b", "mamba2-780m"]
    tenants = build_cluster(archs, HW, batch_slots=2, max_len=MAX_LEN)
    runtime = ClusterRuntime(tenants, VeltairPolicy(HW), HW)
    wl = Workload.poisson(archs, 100, 6, prompt_len=6, max_new_tokens=2,
                          seed=4)
    tables = runtime.tenant_prompts(wl)
    assert not np.array_equal(tables[archs[0]], tables[archs[1]]), \
        "co-located tenants must not replay byte-identical prompts"
    again = runtime.tenant_prompts(wl)
    for a in archs:
        assert np.array_equal(tables[a], again[a]), "must stay deterministic"
    # and the cluster serves chunked admissions end to end
    m = runtime.serve(wl)
    assert m.aggregate.n_queries == wl.n_queries
    assert sum(m.prefill_quanta.values()) >= wl.n_queries
    assert m.aggregate.avg_ttft_s > 0.0
