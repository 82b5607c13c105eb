"""Per-architecture smoke tests (reduced configs) + cache-path invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import (ARCH_NAMES, get_config, get_reduced_config,
                           SHAPES, shape_applicable)
from repro.configs.base import ShapeConfig
from repro.models import build_model, make_sample_inputs, param_count

SMOKE = ShapeConfig("smoke", seq_len=16, global_batch=2, mode="train")


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_forward_and_loss(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_sample_inputs(cfg, SMOKE)
    logits, aux = model.forward(params, batch)
    b, s = 2, 16
    assert logits.shape == (b, s, cfg.vocab_size)
    assert bool(jnp.all(jnp.isfinite(logits)))
    loss, metrics = model.loss(params, batch)
    assert bool(jnp.isfinite(loss)) and float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_arch_smoke_train_step(arch):
    from repro.training import TrainConfig, OptimizerConfig
    from repro.training.train_step import init_train_state, make_train_step
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    tc = TrainConfig(optimizer=OptimizerConfig(lr=1e-3, warmup_steps=1,
                                               total_steps=4))
    state = init_train_state(model, jax.random.PRNGKey(0), tc)
    step = jax.jit(make_train_step(model, tc))
    batch = make_sample_inputs(cfg, SMOKE)
    state, m1 = step(state, batch)
    state, m2 = step(state, batch)
    assert np.isfinite(float(m1["loss"])) and np.isfinite(float(m2["loss"]))
    assert float(m2["loss"]) < float(m1["loss"])  # same batch: must improve
    assert int(state["step"]) == 2


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_prefill_decode_matches_forward(arch):
    cfg = get_reduced_config(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    B, S = 2, 12
    full = make_sample_inputs(
        cfg, ShapeConfig("s", seq_len=S + 1, global_batch=B, mode="prefill"))
    logits_full, _ = model.forward(params, full)
    if "tokens" in full:
        pre = {"tokens": full["tokens"][:, :S]}
        step = {"tokens": full["tokens"][:, S]}
    else:
        pre = {"embeds": full["embeds"][:, :S]}
        if "positions" in full:
            pre["positions"] = full["positions"][..., :S]
        step = {"embeds": full["embeds"][:, S]}
    cache = model.init_cache(B, S + 1)
    lg_pre, cache = model.prefill(params, pre, cache)
    lg_dec, _ = model.decode_step(params, step, cache, jnp.int32(S))
    np.testing.assert_allclose(np.asarray(lg_pre),
                               np.asarray(logits_full[:, S - 1]),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(lg_dec),
                               np.asarray(logits_full[:, -1]),
                               rtol=2e-2, atol=2e-2)


def test_chunked_attention_equals_unchunked():
    import repro.models.layers as L
    cfg = get_reduced_config("llama3-405b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_sample_inputs(cfg, SMOKE)
    logits_a, _ = model.forward(params, batch)
    old = L.SCORE_CHUNK_ELEMS
    try:
        L.SCORE_CHUNK_ELEMS = 32          # force chunking
        logits_b, _ = model.forward(params, batch)
    finally:
        L.SCORE_CHUNK_ELEMS = old
    np.testing.assert_allclose(np.asarray(logits_a), np.asarray(logits_b),
                               rtol=1e-4, atol=1e-4)


def test_moe_capacity_drops_tokens_but_stays_finite():
    """Routing is dropless (no expert capacity); the loss stays finite and
    the load-balance term is active."""
    cfg = get_reduced_config("arctic-480b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_sample_inputs(cfg, SMOKE)
    loss, metrics = model.loss(params, batch)
    assert bool(jnp.isfinite(loss))
    assert float(metrics["aux"]) > 0            # load-balance loss active


def test_full_config_param_counts():
    """Full (non-reduced) configs match the published parameter scale."""
    expected = {
        "llama3-405b": (390e9, 420e9),
        "arctic-480b": (450e9, 500e9),
        "deepseek-v2-lite-16b": (14e9, 18e9),
        "gemma-2b": (2.0e9, 3.0e9),
        "minicpm-2b": (2.2e9, 3.3e9),
        "starcoder2-3b": (2.8e9, 3.5e9),
        "mamba2-780m": (0.6e9, 0.9e9),
        "recurrentgemma-2b": (2.2e9, 3.4e9),
        "musicgen-large": (1.8e9, 2.6e9),
        "qwen2-vl-2b": (1.3e9, 2.4e9),
    }
    for arch, (lo, hi) in expected.items():
        n = param_count(build_model(get_config(arch)).param_specs())
        assert lo <= n <= hi, f"{arch}: {n/1e9:.2f}B not in [{lo/1e9}-{hi/1e9}]"


def test_long_500k_applicability():
    runnable = [a for a, s, ok, _ in
                __import__("repro.configs", fromlist=["all_cells"]).all_cells(
                    include_skipped=True)
                if s == "long_500k" and ok]
    assert sorted(runnable) == ["mamba2-780m", "recurrentgemma-2b"]
