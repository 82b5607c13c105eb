"""Per-slot continuous batching: staggered mixed-length prompts must be
token-for-token identical to a sequential one-request-at-a-time reference
(in both the XLA reference path and Pallas interpret mode), and level
flips after warmup() must be dictionary swaps — zero new traces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced_config
from repro.kernels import dispatch
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine

PROMPT_LENS = (3, 7, 5)          # deliberately misaligned
N_NEW = 4
MAX_LEN = 32


@pytest.fixture(scope="module")
def setup():
    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPT_LENS]
    return cfg, model, params, prompts


@pytest.fixture(autouse=True)
def _clean_dispatch():
    yield
    dispatch.set_mode("xla")
    dispatch.clear_tile_overrides()


def _sequential_reference(model, params, prompt, n_new):
    """One request alone through the raw model: the ground truth any
    batched/staggered schedule must reproduce exactly."""
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


def _staggered_run(cfg, params, prompts):
    """Admit requests at different steps into a 2-slot engine (so slot
    reuse happens too) and run to completion."""
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    reqs = [Request(rid=i, prompt=p, max_new_tokens=N_NEW)
            for i, p in enumerate(prompts)]
    assert engine.admit_request(reqs[0], drain=True)
    engine.step()                          # slot 0 is one token ahead
    assert engine.admit_request(reqs[1], drain=True)     # different length, later join
    engine.step()
    engine.step()
    engine.run_to_completion([reqs[2]])    # admitted after a slot frees
    assert all(r.done for r in reqs)
    return engine, reqs


@pytest.mark.parametrize("mode", ["xla", "interpret"])
def test_misaligned_prompts_match_sequential_reference(setup, mode):
    cfg, model, params, prompts = setup
    dispatch.set_mode(mode)
    want = [_sequential_reference(model, params, p, N_NEW) for p in prompts]
    _, reqs = _staggered_run(cfg, params, prompts)
    for i, req in enumerate(reqs):
        assert req.output[:N_NEW + 1] == want[i][:N_NEW + 1], \
            (mode, i, req.output, want[i])


def test_slot_reuse_cannot_leak_previous_request(setup):
    """A short prompt admitted into a slot previously used by a longer
    request must match its solo output (pristine-row admission)."""
    cfg, model, params, _ = setup
    rng = np.random.default_rng(11)
    long_p = rng.integers(0, cfg.vocab_size, 9).astype(np.int32)
    short_p = rng.integers(0, cfg.vocab_size, 2).astype(np.int32)
    want = _sequential_reference(model, params, short_p, N_NEW)
    engine = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    engine.run_to_completion([Request(rid=0, prompt=long_p,
                                      max_new_tokens=N_NEW)])
    req = Request(rid=1, prompt=short_p, max_new_tokens=N_NEW)
    engine.run_to_completion([req])
    assert req.output[:N_NEW + 1] == want[:N_NEW + 1]


def test_full_level_sweep_after_warmup_zero_retraces(setup):
    """Acceptance: after warmup(), sweeping every interference level and
    stepping performs zero retraces — each switch is a cache hit."""
    cfg, _, params, prompts = setup
    from repro.core import cost_model as cm
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    engine.warmup()
    vc = engine.version_cache
    traces0, misses0 = vc.traces, vc.misses
    switches0 = engine.level_switches
    engine.admit_request(Request(rid=0, prompt=prompts[0],
                               max_new_tokens=64), drain=True)
    for i in range(cm.NUM_LEVELS):
        engine.set_interference_level(cm.grid_point(i))
        engine.step()
    for i in range(4):                      # and repeated flips
        engine.set_interference_level(float(i % 2))
        engine.step()
    assert engine.level_switches > switches0, "flips must register"
    assert vc.misses == misses0, "every switch must be a cache hit"
    assert vc.traces == traces0, "no new traces after warmup"


def test_interpret_mode_flips_hit_distinct_version_entries(setup):
    """Under a Pallas dispatch mode each tile table gets its own compiled
    entry (xla mode collapses them — tiles don't affect the reference
    path), and flips after warming those entries never retrace."""
    cfg, _, params, prompts = setup
    dispatch.set_mode("interpret")
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    engine.warmup(levels=[0.0, 1.0])
    vc = engine.version_cache
    assert len(vc) == 3                 # baseline {} + two tile tables
    traces0, misses0 = vc.traces, vc.misses
    engine.admit_request(Request(rid=0, prompt=prompts[0],
                               max_new_tokens=64), drain=True)
    for i in range(4):
        engine.set_interference_level(float(i % 2))
        engine.step()
    assert vc.misses == misses0 and vc.traces == traces0
    assert vc.hits >= 4


def test_version_cache_shared_per_tiles_not_per_switch(setup):
    cfg, _, params, _ = setup
    dispatch.set_mode("interpret")      # xla mode collapses keys
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN)
    engine.set_interference_level(0.0)
    engine.set_interference_level(1.0)
    n_entries = len(engine.version_cache)
    assert n_entries == 3               # baseline {} + two tile tables
    for lv in (0.0, 1.0, 0.0, 1.0):
        engine.set_interference_level(lv)
    assert len(engine.version_cache) == n_entries
    assert engine.version_cache.hits >= 4


def test_two_engines_do_not_invalidate_each_other(setup):
    """Per-engine override contexts: engine B switching levels must not
    change what engine A's compiled executables produce."""
    cfg, model, params, prompts = setup
    dispatch.set_mode("interpret")
    want = _sequential_reference(model, params, prompts[0], N_NEW)
    eng_a = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    eng_b = ServingEngine(cfg, params, batch_slots=1, max_len=MAX_LEN)
    req = Request(rid=0, prompt=prompts[0], max_new_tokens=N_NEW)
    eng_a.admit_request(req, drain=True)
    eng_b.set_interference_level(1.0)      # B stomps the global table
    while not req.done:
        eng_a.step()
    assert req.output[:N_NEW + 1] == want[:N_NEW + 1]


def test_atomic_override_install_clears_stale_ops():
    """Switching from the default source ({matmul, attention}) to a
    matmul-only table must clear the attention entry."""
    dispatch.install_tile_overrides(
        {"matmul": {"bm": 64}, "attention": {"bq": 64}})
    assert dispatch.tile_overrides("attention")
    dispatch.install_tile_overrides({"matmul": {"bm": 32}})
    assert dispatch.tile_overrides("attention") == {}
    assert set(dispatch.all_tile_overrides()) == {"matmul"}


def test_tile_context_is_atomic_and_scoped():
    dispatch.install_tile_overrides({"attention": {"bq": 64}})
    with dispatch.tile_context({"matmul": {"bm": 16}}):
        # inside a context, ops it does not name have NO override
        assert dispatch.tile_overrides("matmul") == {"bm": 16}
        assert dispatch.tile_overrides("attention") == {}
        assert set(dispatch.all_tile_overrides()) == {"matmul"}
    assert dispatch.tile_overrides("attention") == {"bq": 64}
