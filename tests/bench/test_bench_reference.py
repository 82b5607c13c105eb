"""The plain references against the repo's models at reduced widths on
the CPU, and the float8 control against the program at the same size."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchtools import MODELS, TINY_MB2, TINY_SC2
from bench.lib import weights
from bench.lib.spec import family_module
from repro.models import build_model

# The program computes activations, residual and KV in bfloat16 (unit
# roundoff 2**-8): two layers and the head leave its logits about 1e-2
# (relative L2) from the float32 reference.  A wrong mask, rotation,
# head mapping, conv tap or state update is an O(1) error.
REL_TOL = 3e-2


def tiny(name: str) -> dict:
    spec = dict(MODELS[name])
    spec.update(TINY_SC2 if spec["family"] == "starcoder2" else TINY_MB2)
    return spec


def both_logits(name: str, seed: int, length: int = 48):
    spec = tiny(name)
    adapter = family_module("adapters", spec["family"])
    ref = family_module("reference", spec["family"])
    model = build_model(adapter.model_config(name, spec))
    params = weights.draw(model, seed, 0)
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, spec["vocab_size"], (2, length)), jnp.int32)
    prog, _ = model.forward(params, {"tokens": toks})
    w = adapter.reference_weights(params)
    return (np.asarray(prog, np.float32),
            np.asarray(ref.logits(spec, w, toks), np.float32),
            np.asarray(ref.logits(spec, w, toks, quant=True), np.float32))


def rel(a, b) -> float:
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


@pytest.mark.parametrize("name", ["starcoder2-3b", "mamba2-780m"])
@pytest.mark.parametrize("seed", [0, 2**33 + 5])
def test_reference_agrees_with_the_program(name, seed):
    prog, ref, _ = both_logits(name, seed)
    assert rel(prog, ref) < REL_TOL
    assert (prog.argmax(-1) == ref.argmax(-1)).mean() > 0.9


def widest_gap(ref, chosen) -> float:
    top = ref.max(-1)
    got = np.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return float((top - got).max())


@pytest.mark.parametrize("name", ["starcoder2-3b", "mamba2-780m"])
def test_float8_control_reads_wider_gaps_than_the_program(name):
    """The control of the correctness check at a size a test can hold:
    on three seeds, the tokens the float8 reference puts first lie
    further below the float32 reference's best than the program's do."""
    for seed in (1, 2, 3):
        prog, ref, low = both_logits(name, seed, length=96)
        g_prog = widest_gap(ref, prog.argmax(-1))
        g_ctl = widest_gap(ref, low.argmax(-1))
        assert g_ctl > 0 and g_ctl >= 3 * g_prog, (seed, g_prog, g_ctl)


def test_weights_follow_the_declared_initializers():
    model = build_model(family_module("adapters", "starcoder2").model_config(
        "starcoder2-3b", tiny("starcoder2-3b")))
    p = weights.draw(model, 7, 0)
    q = weights.draw(model, 7, 0)
    r = weights.draw(model, 8, 0)
    leaves = jax.tree_util.tree_leaves
    assert all(bool((a == b).all()) for a, b in zip(leaves(p), leaves(q)))
    assert not bool((p["embed"]["embedding"] == r["embed"]["embedding"]).all())
    emb = np.asarray(p["embed"]["embedding"], np.float32)
    assert p["embed"]["embedding"].dtype == jnp.bfloat16
    assert abs(emb.std() - 0.02) < 0.002
    blocks = p["blocks"]["dense"]
    assert float(jnp.abs(blocks["ln1"]["scale"] - 1).max()) == 0.0
    assert float(jnp.abs(blocks["mlp"]["b_up"]).max()) == 0.0
