"""Speculative multi-token decode quanta: draft -> batched verify ->
rollback must be token-for-token identical to non-speculative greedy
decode — the correctness bar is exact token identity, not "close".

Covered here, per cache family (attention / MLA / SSM / hybrid
window+RG-LRU) and in both the XLA reference path and Pallas interpret
mode:

* identity under staggered admissions, mixed prompt lengths,
  mid-quantum completions and level switches at quantum boundaries;
* the rollback path specifically (drafts that verify rejects must leave
  the cache exactly where sequential decode would);
* paged engines: the worst-case d+1 write span is preflighted, partial
  acceptance never leaks trash-page state into emitted tokens;
* zero post-warmup retraces: ``warmup()`` pre-builds the spec verify
  executables alongside the K-buckets, so a serving loop with level
  switches never traces;
* ``spec_recurrent=False`` downgrades recurrent-state models to the
  plain fused quantum (still exact, zero spec quanta).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.core import cost_model as cm
from repro.kernels import dispatch
from repro.serving.engine import Request, ServingEngine
from repro.serving.speculative import NgramDrafter

MAX_LEN = 64
ARCHS = ("gemma-2b", "deepseek-v2-lite-16b", "mamba2-780m",
         "recurrentgemma-2b")


@pytest.fixture(scope="module", params=ARCHS)
def setup(request):
    from repro.models import build_model
    cfg = get_reduced_config(request.param)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (10, 7, 4)]
    return cfg, model, params, prompts


def _mla_only(cfg):
    """MoE-free clone of an MLA config: first_dense_layers == num_layers
    turns every block into ds_dense0 (MLA attention + dense MLP), so the
    MLA cache family is tested without the MoE router's near-tie expert
    selection amplifying ulp-level drift between the chunked verify
    forward and the sequential decode step."""
    import dataclasses
    from repro.models import build_model
    cfg = dataclasses.replace(cfg, name=cfg.name + "-mla-only",
                              first_dense_layers=cfg.num_layers)
    params = build_model(cfg).init(jax.random.PRNGKey(0))
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_dispatch():
    yield
    dispatch.set_mode("xla")
    dispatch.clear_tile_overrides()


def _serve(cfg, params, prompts, *, speculative, n_new=(40, 36, 20),
           k=4, levels=(), stagger=True, **engine_kw):
    """Drive a schedule with staggered admissions, mixed lengths and
    mid-quantum completions; level switches at quantum boundaries."""
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                        speculative=speculative, **engine_kw)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=n)
            for i, (p, n) in enumerate(zip(prompts, n_new))]
    pending = list(reqs)
    if not stagger:
        while pending and eng.admit_request(pending[0], drain=True):
            pending.pop(0)
    for i in range(400):
        if all(r.done for r in reqs):
            break
        if stagger and pending and i % 3 == 0:
            if eng.admit_request(pending[0], drain=True):
                pending.pop(0)
        if levels:
            eng.set_interference_level(levels[i % len(levels)])
        eng.step_quantum(k)
    assert all(r.done for r in reqs), "schedule must drain every request"
    return eng, [list(r.output) for r in reqs]


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_spec_identical_to_plain_greedy(setup, mode):
    """The tentpole bar: speculation changes the schedule, never the
    tokens — under staggered admissions, mixed lengths, mid-quantum
    completions and level switches, per-request streams match the
    non-speculative engine exactly."""
    cfg, _, params, prompts = setup
    dispatch.set_mode(mode)
    _, want = _serve(cfg, params, prompts, speculative=False,
                     levels=(0.0, 1.0, 0.3))
    eng, got = _serve(cfg, params, prompts, speculative=True,
                      levels=(0.0, 1.0, 0.3))
    assert got == want
    # the speculative path actually ran (and the rollback path with it:
    # tiny random models never accept every draft of every quantum)
    assert eng.spec_quanta > 0
    assert eng.tokens_drafted > 0


def test_spec_rollback_only_stream_is_exact(setup):
    """All-rejected drafts are the hardest rollback case (emit exactly
    one corrected token, rewind everything else): force it by drafting
    against histories the model never follows."""
    cfg, _, params, prompts = setup
    if cfg.moe is not None:
        # Unigram drafts drive repeated-token plateaus where the MoE
        # router's top-k sits on ~ulp-wide logit ties; the chunked
        # verify forward and the sequential step then pick different
        # experts and the argmax flips.  Not a rollback bug — the MLA
        # rollback machinery is exercised here on a MoE-free clone
        # (every layer MLA + dense MLP), and deepseek proper is held to
        # full identity in test_spec_identical_to_plain_greedy.
        cfg, params = _mla_only(cfg)
    _, want = _serve(cfg, params, prompts, speculative=False)
    eng, got = _serve(cfg, params, prompts, speculative=True,
                      spec_ngram=1, spec_depth=3)
    assert got == want
    assert eng.spec_quanta > 0


def test_spec_paged_preflight_and_identity(setup):
    """Paged engines preflight the worst-case d+1 writes per row and
    clamp emission to the mapped span — a small pool must degrade to
    fallbacks/stalls, never to wrong tokens.  Models with no pageable
    (linear-KV) cache leaf refuse the paged layout outright."""
    cfg, model, params, prompts = setup
    if not model.paged_leaf_paths():
        with pytest.raises(ValueError, match="no pageable"):
            _serve(cfg, params, prompts, speculative=True,
                   page_size=8, n_pages=24)
        return
    _, want = _serve(cfg, params, prompts, speculative=False)
    eng, got = _serve(cfg, params, prompts, speculative=True,
                      page_size=8, n_pages=24)
    assert got == want
    assert eng.spec_quanta > 0


def test_spec_zero_retraces_after_warmup(setup):
    """warmup() pre-builds every reachable (K-bucket, draft-depth) spec
    executable: a level-sweeping speculative serve afterwards performs
    zero traces and zero version-cache misses."""
    cfg, _, params, prompts = setup
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                        speculative=True)
    eng.warmup()
    if eng._spec_enabled:
        for entry in eng.version_cache._entries.values():
            assert entry.spec, "verify executables prebuilt at warmup"
    vc = eng.version_cache
    traces0, misses0 = vc.traces, vc.misses
    reqs = [Request(rid=i, prompt=p, max_new_tokens=30)
            for i, p in enumerate(prompts[:2])]
    for r in reqs:
        eng.admit_request(r, drain=True)
    i = 0
    while not all(r.done for r in reqs):
        eng.set_interference_level(cm.grid_point(i % cm.NUM_LEVELS))
        eng.step_quantum(4)
        i += 1
        assert i < 400
    assert vc.traces == traces0, "no trace after warmup"
    assert vc.misses == misses0, "every spec dispatch is a cache hit"


def test_spec_recurrent_opt_out_falls_back_to_plain_quanta(setup):
    """spec_recurrent=False: engines whose cache holds non-sequence
    (recurrent-state) leaves serve through the plain fused quantum —
    still exact, zero speculative dispatches."""
    cfg, model, params, prompts = setup
    eng, got = _serve(cfg, params, prompts, speculative=True,
                      spec_recurrent=False)
    _, want = _serve(cfg, params, prompts, speculative=False)
    assert got == want
    if model._has_nonseq_cache_leaves():
        assert not eng._spec_enabled
        assert eng.spec_quanta == 0
    else:
        assert eng._spec_enabled     # pure-attention models keep spec on


def test_spec_counters_and_hit_rate_consistency(setup):
    """The surfaced counters stay internally consistent: accepted <=
    drafted, hit rate is their ratio, every spec-eligible dispatch is
    either a spec quantum or a counted fallback."""
    cfg, _, params, prompts = setup
    eng, _ = _serve(cfg, params, prompts, speculative=True)
    s = eng.spec_stats
    assert 0 <= s["tokens_accepted"] <= s["tokens_drafted"]
    assert s["draft_hit_rate"] == pytest.approx(
        s["tokens_accepted"] / max(s["tokens_drafted"], 1))
    assert s["spec_quanta"] + s["spec_fallbacks"] > 0
    assert eng.expected_accept_per_step() >= 1.0


def test_drafter_prompt_lookup():
    """NgramDrafter finds the latest n-gram recurrence, proposes its
    continuation, right-pads near the end, and returns None when
    nothing recurs."""
    d = NgramDrafter(depth=3, max_ngram=2)
    got = d.draft([1, 2, 9, 9, 1, 2])
    assert got is not None and got.tolist() == [9, 9, 1]
    # latest occurrence wins over earlier ones
    got = d.draft([1, 2, 3, 1, 2, 4, 1, 2])
    assert got.tolist() == [4, 1, 2]
    # hit near the end: pad by repeating the last candidate
    got = d.draft([7, 5, 6, 7, 5])
    assert got.tolist() == [6, 7, 5]
    got = d.draft([3, 8, 3])
    assert got.tolist() == [8, 3, 3]
    assert d.draft([1, 2, 3, 4, 5]) is None
    assert d.draft([4]) is None
