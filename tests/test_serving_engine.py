"""Serving engine: batched continuous decode == manual decode loop."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_reduced_config
from repro.models import build_model
from repro.serving.engine import Request, ServingEngine

from greedy_ref import manual_greedy


def test_engine_matches_manual_decode():
    cfg = get_reduced_config("starcoder2-3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(rng.integers(0, cfg.vocab_size, 10), jnp.int32)
    n_new = 5
    want = manual_greedy(model, params, prompt, n_new, 32)

    engine = ServingEngine(cfg, params, batch_slots=2, max_len=32)
    req = Request(rid=0, prompt=np.asarray(prompt), max_new_tokens=n_new)
    done = engine.run_to_completion([req])
    assert len(done) == 1
    got = done[0].output[:n_new + 1]
    assert got == want[:len(got)], (got, want)


def test_engine_serves_multiple_requests():
    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab_size, 8).astype(np.int32),
                    max_new_tokens=4)
            for i in range(5)]
    engine = ServingEngine(cfg, params, batch_slots=2, max_len=24)
    done = engine.run_to_completion(reqs)
    assert len(done) == 5
    assert all(len(r.output) >= 5 for r in done)
