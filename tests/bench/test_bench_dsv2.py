"""DeepSeek-V2-Lite in the benchmark: its configuration file, adapter,
plain reference, counts and routing readers, and the cell served whole
at a tiny size on the CPU."""
from __future__ import annotations

import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import benchtools  # noqa: F401  (puts the checkout on the path)
import bench.run as bench_run
import repro.launch.compile_cache as compile_cache
from bench.lib import routing, serve, spans, traffic, weights
from bench.lib.context import Context
from bench.lib.spec import family_module, load_cell, metric_reader
from dsv2tools import CELL, CONFIG, NAME, SPEC, TINY_SPEC, write_tiny_root
from repro.configs import base
from repro.models import build_model
from repro.models import layers as L
from repro.models import moe as moe_mod
from repro.models.mla import softmax_scale, yarn_inv_freq
from repro.serving.engine import ServingEngine

adapter = family_module("adapters", "deepseek_v2")
ref = family_module("reference", "deepseek_v2")
counts = family_module("counts", "deepseek_v2")
PEAKS = json.loads((benchtools.ROOT / "bench/peaks.json").read_text())[
    "TPU v5 lite"]
METRICS = ("moe.held_share.dsv2l", "moe.expert_load.dsv2l",
           "model.mfu.dsv2l")

# The program computes activations, residual and the latent cache in
# bfloat16 (unit roundoff 2**-8): three layers and the head leave its
# logits about 1e-2 (relative L2) from the float32 reference, and a
# router pick that flips on a near tie moves single positions further.
# A wrong rotation, scale, gate or held-expert offset is an O(1) error.
REL_TOL = 3e-2


# -- the configuration file ---------------------------------------------
def test_file_states_the_published_keys_and_the_share():
    top = {k: v for k, v in CONFIG.items()
           if k not in ("name", "source", "deployment", "reduced", "changes",
                        "assumed", "memory", "models", "engine", "runtime")}
    assert all(SPEC[k] == v for k, v in top.items()), \
        [k for k, v in top.items() if SPEC[k] != v]
    assert set(CONFIG["reduced"]) == set(CONFIG["changes"]) == \
        {"n_routed_experts", "max_position_embeddings"}
    assert SPEC["router_experts"] == 64 and SPEC["n_routed_experts"] == 8
    assert SPEC["first_held_expert"] == 0
    bench = json.loads((benchtools.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "dsv2-lite-ep8")
    assert entry["reduced"] == CONFIG["reduced"]
    cfg = adapter.model_config(NAME, SPEC)
    assert cfg.moe.num_experts == 64 and cfg.moe.n_held == 8
    assert cfg.moe.top_k == 6 and not cfg.moe.norm_topk_prob
    assert cfg.mla.yarn.factor == 40 and cfg.first_dense_d_ff == 10944


def test_adapter_refuses_a_program_without_the_layer(monkeypatch):
    monkeypatch.delattr(base, "YarnConfig")
    with pytest.raises(ValueError, match="YaRN"):
        adapter.model_config(NAME, SPEC)


def test_adapter_refuses_a_program_without_held_experts(monkeypatch):
    @dataclasses.dataclass(frozen=True)
    class AllExperts:
        num_experts: int
        top_k: int
        expert_d_ff: int
    monkeypatch.setattr(base, "MoEConfig", AllExperts)
    with pytest.raises(ValueError, match="share of the routed experts"):
        adapter.model_config(NAME, SPEC)


@pytest.mark.parametrize("key,value", [("topk_method", "noaux_tc"),
                                       ("routed_scaling_factor", 16),
                                       ("scoring_func", "sigmoid")])
def test_adapter_refuses_a_router_it_does_not_run(key, value):
    with pytest.raises(ValueError, match=key):
        adapter.model_config(NAME, dict(SPEC, **{key: value}))


# -- counts ----------------------------------------------------------------
def test_parameter_totals_match_the_published_size_and_the_share():
    uncut = counts.param_count(SPEC, routed_experts=64)
    assert abs(uncut - 15.7e9) <= 0.02 * 15.7e9
    assert counts.param_count(SPEC) == pytest.approx(3.11e9, rel=0.005)
    model = build_model(adapter.model_config(NAME, SPEC))
    from repro.models import param_count
    assert param_count(model.param_specs()) == counts.param_count(SPEC)


def test_decode_step_and_prefill_chunk_are_bandwidth_bound_on_v5e():
    """Even counting every pick of the call on a held expert."""
    rows = [(512, 1)] * 96
    held = 96 * 6 * 26
    f, b = counts.decode(SPEC, rows, steps=1, held=held)
    assert b / PEAKS["hbm_bytes_per_s"] > f / PEAKS["flops_per_s"]
    f, b = counts.prefill(SPEC, 512, 16, held=16 * 6 * 26)
    assert b / PEAKS["hbm_bytes_per_s"] > f / PEAKS["flops_per_s"]


def test_prefill_chunk_before_the_prompt_end_needs_no_logits():
    f_mid, b_mid = counts.prefill(SPEC, 0, 16, last=False)
    f_end, b_end = counts.prefill(SPEC, 0, 16, last=True)
    d, v = SPEC["hidden_size"], SPEC["vocab_size"]
    assert f_end - f_mid == 2 * d * v and b_end - b_mid == 2 * d * v


def test_routed_flops_count_only_held_picks():
    f0, b0 = counts.decode(SPEC, [(100, 2)], steps=2)
    f1, b1 = counts.decode(SPEC, [(100, 2)], steps=2, held=3)
    assert f1 - f0 == 3 * 2 * 3 * 2048 * 1408 and b1 == b0


# -- reference against the program ----------------------------------------
def _tiny(seed: int):
    cfg = adapter.model_config(NAME, TINY_SPEC)
    model = build_model(cfg)
    params = weights.draw(model, seed, 0)
    return cfg, model, params, adapter.reference_weights(params)


def served_logits(cfg, params, seqs: np.ndarray, prompt: tuple[int, ...]):
    """Logits of each row of ``seqs`` at positions prompt-1 .. end-2,
    served as the engine serves them: the prompt in the engine's prefill
    chunks (its chunk executable, its chunk schedule) into a row written
    into the batched cache, then decode steps of every row at its own
    position through the engine's decode executable, fed the row's own
    next tokens."""
    b, n = seqs.shape
    eng = ServingEngine(cfg, params, batch_slots=b, max_len=64)
    out = [[] for _ in range(b)]
    for i, p in enumerate(prompt):
        row, done = eng._empty_row, 0
        for c in eng._prefill_schedule(p):
            valid = min(c, p - done)
            toks = np.zeros(c, np.int32)
            toks[:valid] = seqs[i, done:done + valid]
            lg, row, _ = eng._prefill_chunk(
                params, jnp.asarray(toks)[None], row, jnp.int32(done),
                jnp.int32(valid))
            done += valid
        eng.cache = eng._row_writer(eng.cache, row, jnp.int32(i))
        out[i].append(np.asarray(lg[0], np.float32))
    pos = np.asarray(prompt, np.int32)
    while (pos < n - 1).any():
        toks = seqs[np.arange(b), np.minimum(pos, n - 1)]
        lg, eng.cache = eng._decode(params, {"tokens": jnp.asarray(toks)},
                                    eng.cache, jnp.asarray(pos))
        for i in range(b):
            if pos[i] < n - 1:
                out[i].append(np.asarray(lg[i], np.float32))
        pos = pos + 1
    return [np.stack(o) for o in out]


def rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_served_logits_agree_with_the_reference(seed):
    cfg, _, params, w = _tiny(seed)
    prompt = (21, 7)                     # a 16-token chunk and a tail
    seqs = np.random.default_rng(seed).integers(
        0, TINY_SPEC["vocab_size"], (2, 48)).astype(np.int32)
    full = np.asarray(ref.logits(TINY_SPEC, w, jnp.asarray(seqs)),
                      np.float32)
    for i, got in enumerate(served_logits(cfg, params, seqs, prompt)):
        want = full[i, prompt[i] - 1:seqs.shape[1] - 1]
        assert got.shape == want.shape
        assert rel(got, want) < REL_TOL, (seed, i)
        assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9


def widest_gap(logits, chosen) -> float:
    top = logits.max(-1)
    got = np.take_along_axis(logits, chosen[..., None], -1)[..., 0]
    return float((top - got).max())


def test_float8_control_reads_wider_gaps_than_the_program():
    """On three seeds, the tokens the float8 reference puts first lie
    further below the float32 reference's best than the program's do."""
    for seed in (1, 2, 3):
        _, model, params, w = _tiny(seed)
        toks = jnp.asarray(np.random.default_rng(seed).integers(
            0, TINY_SPEC["vocab_size"], (2, 96)), jnp.int32)
        prog = np.asarray(model.forward(params, {"tokens": toks})[0])
        full = np.asarray(ref.logits(TINY_SPEC, w, toks))
        low = np.asarray(ref.logits(TINY_SPEC, w, toks, quant=True))
        g_prog = widest_gap(full, prog.argmax(-1))
        g_ctl = widest_gap(full, low.argmax(-1))
        assert g_ctl > 0 and g_ctl >= 3 * g_prog, (seed, g_prog, g_ctl)


def test_expert_shares_add_up_to_the_uncut_layer():
    """16 routed experts held 4 to a share: the four shares' routed parts
    (the program's layer, each told which experts it holds) plus the
    shared experts, counted once, equal the reference's uncut layer.  In
    float32 throughout, so only summation order separates them."""
    spec = dict(TINY_SPEC, n_routed_experts=16, first_held_expert=0)
    cfg = adapter.model_config(NAME, spec)
    w = adapter.reference_weights(weights.draw(build_model(cfg), 5, 0))["moe"]
    w = {k: v[0].astype(jnp.float32) for k, v in w.items()}
    h = jax.random.normal(jax.random.PRNGKey(3), (2, 24, 64), jnp.float32)
    uncut = ref.moe_ffn(h, w, (6, 0, False), False)
    parts = jnp.zeros_like(h)
    for first in range(0, 16, 4):
        share = dataclasses.replace(cfg.moe, held_experts=4,
                                    first_held_expert=first)
        held = {"router": w["router"],
                "w_gate": w["e_gate"][first:first + 4],
                "w_up": w["e_up"][first:first + 4],
                "w_down": w["e_down"][first:first + 4]}
        out, _, _ = moe_mod.apply_moe(held, h, cfg, share)
        parts = parts + out
    shared = L.apply_mlp({"w_gate": w["s_gate"], "w_up": w["s_up"],
                          "w_down": w["s_down"]}, h, "swiglu")
    np.testing.assert_allclose(np.asarray(parts + shared), np.asarray(uncut),
                               rtol=1e-4, atol=1e-5)


def test_reference_yarn_is_the_programs():
    """The reference writes YaRN out on its own; both give the published
    frequencies and softmax scale."""
    inv, factor, scale = ref.yarn(SPEC)
    cfg = adapter.model_config(NAME, SPEC)
    np.testing.assert_allclose(np.asarray(inv),
                               yarn_inv_freq(64, 10000.0, cfg.mla.yarn),
                               rtol=1e-6)
    assert factor == 1.0
    assert scale == pytest.approx(softmax_scale(cfg.mla), rel=1e-12)
    assert scale == pytest.approx(192 ** -0.5 * 1.58962, rel=1e-4)


# -- the cell at a tiny size ------------------------------------------------
@pytest.fixture(scope="module")
def tiny_root(tmp_path_factory):
    return write_tiny_root(tmp_path_factory.mktemp("tiny_dsv2"))


def run_cell(root, seed: int, monkeypatch, control: bool = False) -> dict:
    monkeypatch.setattr(bench_run, "peaks_for", lambda kind: PEAKS)
    # tests keep JAX's persistent compilation cache off
    monkeypatch.setattr(compile_cache, "enable_compile_cache",
                        lambda: "off")
    return bench_run.run(load_cell(CELL, root), seed, 3.0, False,
                         jax.devices(), control=control)


def test_sound_program_is_correct(tiny_root, monkeypatch):
    res = run_cell(tiny_root, 2**32 + 11, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == [f"gap.{NAME}"]


@pytest.mark.parametrize("seed", [1, 2])
def test_float8_control_in_the_programs_place_is_not_correct(
        tiny_root, seed, monkeypatch):
    res = run_cell(tiny_root, seed, monkeypatch, control=True)
    assert res["correct"] is False, res["checks"]


@pytest.fixture(scope="module")
def traced(tiny_root, tmp_path_factory):
    """The tiny cell served through ``ClusterRuntime`` with the profiler
    open over the whole serve: (engine, counter deltas, trace dir)."""
    cell = load_cell(CELL, tiny_root)
    tenants = serve.build_tenants(cell.config, 2**31 + 3, lambda msg: None)
    engines = serve.build_engines(cell.config, tenants,
                                  serve.plans_for(cell.config, tenants))
    requests = traffic.generate(cell.traffic, [t.name for t in tenants],
                                1.0, 2**31 + 3)[:8]
    runtime = serve.make_runtime(cell.config, engines, 2**31 + 3)
    eng = engines[0].engine
    keys = ("moe_assignments", "moe_held_assignments", "prefill_tokens",
            "tokens_decoded")
    before = {k: getattr(eng, k) for k in keys}
    trace_dir = tmp_path_factory.mktemp("dsv2_trace")
    jax.profiler.start_trace(str(trace_dir))
    try:
        runtime.serve(serve.workload(requests, 2**31 + 3))
    finally:
        jax.profiler.stop_trace()
    delta = {k: getattr(eng, k) - before[k] for k in keys}
    return eng, delta, trace_dir, cell


def _ctx(cell, window_s=None):
    return Context(stamps=None, counters0={}, counters1={}, end=0.0,
                   specs={NAME: cell.config["models"][NAME]}, peaks=PEAKS,
                   reduction=object(),
                   traced=None if window_s is None else (0.0, window_s))


def test_routing_readers_give_the_counter_sums(traced, monkeypatch):
    eng, delta, trace_dir, cell = traced
    monkeypatch.setattr(spans, "TRACE_DIR", trace_dir)
    assert delta["moe_assignments"] == 6 * 2 * (
        delta["prefill_tokens"] + delta["tokens_decoded"] - 8)
    rs = routing.traced_syncs(_ctx(cell))
    peak = sum(int(r["moe_peak"]) for r in rs)
    ctx = _ctx(cell)
    assert metric_reader("moe.held_share.dsv2l")(ctx) == pytest.approx(
        100.0 * delta["moe_held_assignments"] / delta["moe_assignments"])
    assert metric_reader("moe.expert_load.dsv2l")(ctx) == pytest.approx(
        peak / (delta["moe_held_assignments"] / 4))
    assert metric_reader("moe.expert_load.dsv2l")(ctx) >= 1.0
    assert metric_reader("model.mfu.dsv2l")(ctx) is None   # no window

    class NoCalls:
        prefill_calls, decode_calls = [], []
    ctx = dataclasses.replace(_ctx(cell, window_s=2.0), stamps=NoCalls())
    want = 100.0 * counts.routed_flops(
        cell.config["models"][NAME], delta["moe_held_assignments"]) \
        / (2.0 * PEAKS["flops_per_s"])
    assert metric_reader("model.mfu.dsv2l")(ctx) == pytest.approx(want)


def test_routing_readers_are_silent_without_counts(tmp_path, monkeypatch,
                                                   traced):
    cell = traced[3]
    jax.profiler.start_trace(str(tmp_path))
    try:
        jnp.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    for name in METRICS:
        assert metric_reader(name)(_ctx(cell, 1.0)) is None, name
    untraced = dataclasses.replace(_ctx(cell, 1.0), reduction=None)
    for name in METRICS:
        assert metric_reader(name)(untraced) is None, name

