"""Per-kernel correctness: interpret-mode Pallas vs pure-jnp oracles,
swept over shapes/dtypes with hypothesis."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref


def _rand(rng, shape, dtype):
    x = rng.standard_normal(shape).astype(np.float32)
    return jnp.asarray(x, dtype)


@given(
    m=st.integers(1, 70), k=st.integers(1, 70), n=st.integers(1, 70),
    bm=st.sampled_from([8, 16, 32]), bk=st.sampled_from([16, 32]),
    bn=st.sampled_from([16, 32]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
@settings(max_examples=20, deadline=None)
def test_block_matmul_matches_ref(m, k, n, bm, bk, bn, dtype):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    x = _rand(rng, (m, k), dtype)
    w = _rand(rng, (k, n), dtype)
    got = ops.block_matmul(x, w, bm=bm, bk=bk, bn=bn, interpret=True)
    want = ref.matmul_ref(x, w)
    tol = 1e-4 if dtype == jnp.float32 else 8e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol * 8)


def test_block_matmul_batched_dims():
    rng = np.random.default_rng(0)
    x = _rand(rng, (2, 3, 24), jnp.float32)
    w = _rand(rng, (24, 16), jnp.float32)
    got = ops.block_matmul(x, w, bm=8, bk=8, bn=8, interpret=True)
    want = ref.matmul_ref(x, w)
    assert got.shape == (2, 3, 16)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@given(
    s=st.sampled_from([8, 17, 24]), t_extra=st.integers(0, 9),
    h=st.sampled_from([2, 4]), kv=st.sampled_from([1, 2]),
    d=st.sampled_from([8, 16]),
    window=st.sampled_from([None, 5, 16]),
    bq=st.sampled_from([4, 8]), bkv=st.sampled_from([4, 8]),
)
@settings(max_examples=20, deadline=None)
def test_flash_attention_matches_ref(s, t_extra, h, kv, d, window, bq, bkv):
    if h % kv:
        kv = 1
    t = s + t_extra
    rng = np.random.default_rng(s * 100 + t)
    q = _rand(rng, (2, s, h, d), jnp.float32)
    k = _rand(rng, (2, kv, t, d), jnp.float32)     # heads-major KV
    v = _rand(rng, (2, kv, t, d), jnp.float32)
    qpos = jnp.broadcast_to(jnp.arange(s), (2, s))
    got = ops.flash_attention(q, k, v, q_positions=qpos, kv_valid_len=s,
                              window=window, bq=bq, bkv=bkv, interpret=True)
    want = ref.attention_ref(q, k, v, offset=0, kv_valid_len=s,
                             window=window)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_flash_attention_decode_offset():
    rng = np.random.default_rng(3)
    q = _rand(rng, (2, 1, 4, 16), jnp.float32)
    k = _rand(rng, (2, 2, 32, 16), jnp.float32)     # heads-major KV
    v = _rand(rng, (2, 2, 32, 16), jnp.float32)
    got = ops.flash_attention(q, k, v, q_positions=jnp.full((2, 1), 20),
                              kv_valid_len=21, window=8, bq=8, bkv=8,
                              interpret=True)
    want = ref.attention_ref(q, k, v, offset=20, kv_valid_len=21, window=8)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


@given(
    l=st.sampled_from([8, 24, 40]), h=st.sampled_from([1, 3]),
    p=st.sampled_from([4, 8]), n=st.sampled_from([4, 8]),
    chunk=st.sampled_from([4, 8, 16]), with_init=st.booleans(),
)
@settings(max_examples=20, deadline=None)
def test_ssd_scan_matches_ref(l, h, p, n, chunk, with_init):
    rng = np.random.default_rng(l * 7 + h)
    x = _rand(rng, (2, l, h, p), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (2, l, h)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (h,)), jnp.float32)
    b = _rand(rng, (2, l, h, n), jnp.float32)
    c = _rand(rng, (2, l, h, n), jnp.float32)
    h0 = _rand(rng, (2, h, p, n), jnp.float32) if with_init else None
    y1, s1 = ops.ssd_scan(x, dt, a, b, c, chunk_size=chunk,
                          initial_state=h0, interpret=True)
    y2, s2 = ref.ssd_ref(x, dt, a, b, c, chunk_size=5,  # different chunking
                         initial_state=h0)
    np.testing.assert_allclose(y1, y2, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(s1, s2, rtol=2e-4, atol=2e-4)


def test_dispatch_interpret_mode_through_model():
    """The dispatch layer routes model math through the Pallas kernels in
    interpret mode and must agree with the pure-XLA path."""
    from repro.kernels import dispatch
    from repro.configs import get_reduced_config
    from repro.configs.base import ShapeConfig
    from repro.models import build_model, make_sample_inputs

    cfg = get_reduced_config("gemma-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_sample_inputs(
        cfg, ShapeConfig("s", seq_len=16, global_batch=2, mode="train"))
    logits_xla, _ = model.forward(params, batch)
    dispatch.set_mode("interpret")
    try:
        logits_k, _ = model.forward(params, batch)
    finally:
        dispatch.set_mode("xla")
    np.testing.assert_allclose(np.asarray(logits_k), np.asarray(logits_xla),
                               rtol=5e-2, atol=5e-2)
