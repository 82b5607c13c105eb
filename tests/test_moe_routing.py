"""The dropless held-expert MoE layer and latent attention's rope: a row's
result does not depend on the rows beside it, the routing counts the
serving programs return match a host recount of the router's picks and
the sync spans carry them, the gates follow the config, and YaRN gives
the published frequencies and softmax scale."""
import dataclasses
import glob
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_reduced_config
from repro.models import build_model
from repro.models import moe as moe_mod
from repro.models.mla import softmax_scale, yarn_inv_freq
from repro.serving import spans as program_spans
from repro.serving.engine import Request, ServingEngine

MAX_LEN = 64


def _held_share():
    """Reduced deepseek with 8 routed experts, top-2, of which experts
    3-5 are held here."""
    cfg = get_reduced_config("deepseek-v2-lite-16b")
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, num_experts=8, held_experts=3, first_held_expert=3))


@pytest.fixture(scope="module")
def share():
    cfg = _held_share()
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(4))


def test_a_row_is_the_same_alone_and_beside_other_rows(share):
    """Dropless: no expert capacity is shared between rows, so a row's
    prefill chunk and decode step give the same logits in any batch."""
    cfg, model, params = share
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (4, 16)), jnp.int32)
    chunk = jax.jit(model.prefill_chunk)
    step = jax.jit(model.decode_step)
    alone, row, _ = chunk(params, {"tokens": toks[:1]},
                          model.init_cache(1, MAX_LEN), 0, 16)
    many, rows, _ = chunk(params, {"tokens": toks},
                          model.init_cache(4, MAX_LEN), 0, 16)
    np.testing.assert_allclose(np.asarray(alone[0]), np.asarray(many[0]),
                               rtol=1e-6, atol=1e-6)
    nxt = jnp.asarray(rng.integers(0, cfg.vocab_size, (4,)), jnp.int32)
    pos = jnp.full((4,), 16, jnp.int32)
    lg1, _ = step(params, {"tokens": nxt[:1]}, row, pos[:1])
    lg4, _ = step(params, {"tokens": nxt}, rows, pos)
    np.testing.assert_allclose(np.asarray(lg1[0]), np.asarray(lg4[0]),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("norm", [False, True], ids=["as-is", "renormalised"])
def test_gates_follow_the_config(norm):
    """deepseek-v2 uses the top-k softmax probabilities as they are;
    arctic divides them by their sum."""
    moe = dataclasses.replace(get_config("deepseek-v2-lite-16b").moe,
                              norm_topk_prob=norm)
    router = jax.random.normal(jax.random.PRNGKey(0), (32, 64))
    x = jax.random.normal(jax.random.PRNGKey(1), (5, 32))
    probs, picks, gates = moe_mod.route(router, x, moe)
    want = np.take_along_axis(np.asarray(probs), np.asarray(picks), -1)
    if norm:
        want = want / want.sum(-1, keepdims=True)
    np.testing.assert_allclose(np.asarray(gates), want, rtol=1e-6)
    total = np.asarray(gates).sum(-1)
    if norm:
        np.testing.assert_allclose(total, 1.0, rtol=1e-6)
    else:
        assert (total < 1.0).all()
    assert (np.diff(want, axis=-1) <= 0).all()           # greedy, sorted


class Recount:
    """Host recount of the router's picks: ``apply_moe`` wrapped so that
    every call sends its picks and real-token mask to the host."""

    def __init__(self, moe):
        self.moe, self.held, self.assigned, self.calls = moe, 0, 0, 0

    def record(self, picks, real):
        picks, real = np.asarray(picks), np.asarray(real).astype(bool)
        lo = self.moe.first_held_expert
        on = (picks >= lo) & (picks < lo + self.moe.n_held)
        self.held += int(on[real].sum())
        self.assigned += int(real.sum()) * picks.shape[-1]
        self.calls += 1

    def wrap(self, apply_moe):
        def wrapped(params, x, cfg, moe, mask=None):
            b, s, m = x.shape
            _, picks, _ = moe_mod.route(params["router"],
                                        x.reshape(b * s, m), moe)
            real = jnp.ones((b * s,), bool) if mask is None \
                else mask.reshape(b * s)
            jax.debug.callback(self.record, picks, real)
            return apply_moe(params, x, cfg, moe, mask)
        return wrapped


def _serve(cfg, params, n_new=(9, 3, 14)):
    eng = ServingEngine(cfg, params, batch_slots=2, max_len=MAX_LEN,
                        quantum_buckets=(2, 4))
    rng = np.random.default_rng(7)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab_size, p)
                    .astype(np.int32), max_new_tokens=n)
            for i, (p, n) in enumerate(zip((21, 5, 11), n_new))]
    eng.run_to_completion(reqs)
    assert all(r.done for r in reqs)
    return eng, reqs


def test_routing_counters_match_a_host_recount(share, monkeypatch):
    """Chunked prefill with padded tails and fused quanta with rows
    frozen mid-quantum: the counters cover exactly the real tokens, every
    one routed to top_k experts in each MoE layer."""
    cfg, model, params = share
    recount = Recount(cfg.moe)
    monkeypatch.setattr(moe_mod, "apply_moe", recount.wrap(moe_mod.apply_moe))
    eng, reqs = _serve(cfg, params)
    jax.effects_barrier()
    n_moe = cfg.num_layers - cfg.first_dense_layers
    real = eng.prefill_tokens + eng.tokens_decoded - len(reqs)
    assert eng.prefill_pad_tokens > 0 and recount.calls > 0
    assert eng.moe_assignments == cfg.moe.top_k * real * n_moe
    assert eng.moe_assignments == recount.assigned
    assert eng.moe_held_assignments == recount.held
    assert 0 < eng.moe_held_assignments < eng.moe_assignments


def sync_args(path: str, name: str) -> list[dict]:
    """Arguments of the host spans ``name`` in a trace file."""
    data = jax.profiler.ProfileData.from_file(path)
    return [dict(ev.stats) for plane in data.planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events if ev.name == name]


def test_sync_spans_carry_the_counters(share, tmp_path):
    cfg, _, params = share
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng, reqs = _serve(cfg, params)
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    quanta = sync_args(path, program_spans.QUANTUM_SYNC)
    prefills = sync_args(path, program_spans.PREFILL_SYNC)
    assert len(prefills) == len(reqs) and quanta
    rs = quanta + prefills
    assert sum(r["moe_assigned"] for r in rs) == eng.moe_assignments
    assert sum(r["moe_held"] for r in rs) == eng.moe_held_assignments
    for r in rs:             # busiest held expert: between mean and total
        assert r["moe_held"] <= cfg.moe.n_held * r["moe_peak"]
        assert r["moe_peak"] <= r["moe_held"]


def test_models_without_experts_count_nothing():
    cfg = get_reduced_config("gemma-2b")
    eng, _ = _serve(cfg, build_model(cfg).init(jax.random.PRNGKey(0)))
    assert eng.moe_assignments == eng.moe_held_assignments == 0


@pytest.mark.parametrize("name", ["starcoder2-3b", "deepseek-v2-lite-16b"])
def test_routing_counts_are_a_program_output_only_with_experts(name):
    """The quantum and the prefill chunk of a model without routed
    experts return None for the counts, so their programs carry and
    output nothing for them; with experts, N_ROUTING_COUNTS int32."""
    cfg = get_reduced_config(name)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    vec = jnp.zeros((2,), jnp.int32)
    toks = jnp.zeros((1, 8), jnp.int32)
    *_, quantum = jax.eval_shape(
        lambda p, c: model.decode_quantum(p, vec, c, vec, vec + 2, 4),
        params, model.init_cache(2, MAX_LEN))
    *_, chunk = jax.eval_shape(
        lambda p, c: model.prefill_chunk(p, {"tokens": toks}, c, 0, 8),
        params, model.init_cache(1, MAX_LEN))
    for routed in (quantum, chunk):
        if cfg.moe is None:
            assert routed is None
        else:
            assert routed.shape == (moe_mod.N_ROUTING_COUNTS,)
            assert routed.dtype == jnp.int32


def test_yarn_at_the_published_numbers():
    """DeepSeek-V2-Lite's rope: 64 dims, theta 1e4, factor 40 over 4096
    positions, betas 32 and 1.  corr(r) = 64 ln(4096 / (2 pi r)) /
    (2 ln 1e4) puts the ramp between floor(corr(32)) = 10 and
    ceil(corr(1)) = 23: pairs 0-10 keep their frequency, pairs 23-31 are
    divided by 40 and those between are blended linearly.  The softmax
    scale is 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2."""
    mla = get_config("deepseek-v2-lite-16b").mla
    corr = [64 * math.log(4096 / (2 * math.pi * r)) / (2 * math.log(1e4))
            for r in (32, 1)]
    assert (math.floor(corr[0]), math.ceil(corr[1])) == (10, 23)
    freq = 1e4 ** (-np.arange(0, 64, 2) / 64)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = freq / 40 * ramp + freq * (1 - ramp)
    got = yarn_inv_freq(64, 1e4, mla.yarn)
    np.testing.assert_allclose(got, want, rtol=1e-12)
    np.testing.assert_array_equal(got[:11], freq[:11])
    np.testing.assert_allclose(got[23:], freq[23:] / 40, rtol=1e-12)
    mscale = 0.1 * 0.707 * math.log(40) + 1
    assert softmax_scale(mla) == pytest.approx(192 ** -0.5 * mscale ** 2,
                                               rel=1e-12)
    assert mscale ** 2 == pytest.approx(1.5896, abs=1e-4)
