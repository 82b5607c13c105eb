"""Sharding rules, pspec derivation, HLO collective parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType, PartitionSpec as P

from repro.dist import sharding as shd
from repro.launch import hlo_stats
from repro.models.params import ParamSpec


@pytest.fixture(scope="module")
def mesh():
    # single-device mesh with named axes of size 1 keeps tests runnable
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)


def _mesh_16_16():
    """Fake mesh-shape lookup for divisibility tests (no devices needed)."""
    class FakeMesh:
        shape = {"data": 16, "model": 16}
    return FakeMesh()


def test_pspec_divisibility_drop():
    mesh = _mesh_16_16()
    rules = shd.make_rules("train").params
    # kv_heads=8 cannot shard over model=16 -> dropped (GQA TP fallback)
    spec = shd.pspec_for((1024, 8, 128), ("embed", "kv_heads", "head_dim"),
                         rules, mesh)
    assert spec == P("data")
    # heads=128 shards fine
    spec2 = shd.pspec_for((1024, 128, 128), ("embed", "heads", "head_dim"),
                          rules, mesh)
    assert spec2 == P(("data",), "model")


def test_pspec_no_duplicate_mesh_axes():
    mesh = _mesh_16_16()
    rules = {"a": "model", "b": "model"}
    spec = shd.pspec_for((64, 64), ("a", "b"), rules, mesh)
    # 'model' used once only
    used = [e for e in spec if e is not None]
    assert used in ([("model",)], ["model"]) or len(used) == 1


def test_multi_axis_product_sharding():
    mesh = type("M", (), {"shape": {"pod": 2, "data": 16, "model": 16}})()
    rules = {"batch": ("pod", "data")}
    spec = shd.pspec_for((256, 128), ("batch", None), rules, mesh)
    assert spec == P(("pod", "data"))
    # non-divisible by the product: drops trailing axis
    spec2 = shd.pspec_for((2, 128), ("batch", None), rules, mesh)
    assert spec2 == P(("pod",))


def test_hint_noop_outside_context():
    x = jnp.ones((4, 4))
    assert shd.hint(x, ("batch", None)) is x


def test_hint_constrains_inside_context(mesh):
    rules = shd.make_rules("train")

    @jax.jit
    def f(x):
        with shd.use_rules(mesh, rules):
            return shd.hint(x, ("batch", "embed")) * 2
    out = f(jnp.ones((4, 8)))
    assert out.shape == (4, 8)


def test_device_bytes():
    mesh = _mesh_16_16()
    specs = {"w": ParamSpec((1024, 256), jnp.bfloat16, ("embed", "mlp"))}
    rules = shd.make_rules("train")
    pspecs = shd.param_pspecs(specs, rules, mesh)
    total = shd.device_bytes(pspecs, specs, mesh)
    assert total == 1024 * 256 * 2 // (16 * 16)


# --------------------------------------------------------------------------
# HLO collective parsing
# --------------------------------------------------------------------------
HLO_SAMPLE = """
HloModule test
  %ar = bf16[16,1024]{1,0} all-reduce(bf16[16,1024] %x), replica_groups={{0,1,2,3}}, to_apply=%add
  %ag = bf16[64,512]{1,0} all-gather(bf16[16,512] %y), replica_groups=[8,4]<=[32], dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(f32[32,128] %z), replica_groups={{0,1,2,3}}, to_apply=%add
  %cp = bf16[4,4]{1,0} collective-permute(bf16[4,4] %w), source_target_pairs={{0,1}}
  %dot = bf16[4,4]{1,0} dot(bf16[4,4] %a, bf16[4,4] %b)
"""


def test_parse_collectives_counts_and_bytes():
    st = hlo_stats.parse_collectives(HLO_SAMPLE)
    assert st.counts["all-reduce"] == 1
    assert st.counts["all-gather"] == 1
    assert st.counts["reduce-scatter"] == 1
    assert st.counts["collective-permute"] == 1
    assert st.counts["all-to-all"] == 0
    ar_bytes = 16 * 1024 * 2
    ag_bytes = 64 * 512 * 2
    rs_bytes = 8 * 128 * 4
    assert st.payload_bytes["all-reduce"] == ar_bytes
    assert st.payload_bytes["all-gather"] == ag_bytes
    expected_link = (2 * 3 / 4 * ar_bytes + 3 / 4 * ag_bytes
                     + 3 * rs_bytes + 4 * 4 * 2)
    assert np.isclose(st.link_bytes, expected_link, rtol=1e-6)


def test_parse_collectives_start_variant_halved():
    text = ("%ags = (bf16[8,8]{1,0}, bf16[32,8]{1,0}) "
            "all-gather-start(bf16[8,8] %p), replica_groups=[1,4]<=[4], "
            "dimensions={0}\n")
    st = hlo_stats.parse_collectives(text)
    assert st.counts["all-gather"] == 1
    # tuple bytes halved: (64+256)*2/2 = 320
    assert st.payload_bytes["all-gather"] == (8 * 8 + 32 * 8) * 2 // 2
