"""Checkpointing: roundtrip, retention, atomicity, async, elastic."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import Checkpointer, elastic_restore, reshard_plan


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.standard_normal((8, 16)), jnp.bfloat16),
        "scale": jnp.asarray(rng.standard_normal(16), jnp.float32),
        "nested": {"step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    tree = _tree()
    ckpt.save(5, tree)
    restored, step = ckpt.restore(None, tree)
    assert step == 5
    for a, b in zip(jax.tree_util.tree_leaves(tree),
                    jax.tree_util.tree_leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_latest_k_retention(tmp_path):
    ckpt = Checkpointer(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        ckpt.save(s, _tree(s))
    assert ckpt.steps() == [3, 4]


def test_atomicity_tmp_dirs_ignored(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(1, _tree())
    # simulate a crashed mid-save
    os.makedirs(tmp_path / "step_00000009.tmp")
    # and an uncommitted dir (no COMMITTED marker)
    os.makedirs(tmp_path / "step_00000007")
    assert ckpt.latest_step() == 1


def test_async_save(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save_async(3, _tree())
    ckpt.wait()
    assert ckpt.latest_step() == 3


def test_elastic_restore_between_meshes(tmp_path):
    """A checkpoint written under one topology restores under another —
    here 1-device meshes with different PartitionSpecs stand in for the
    256 -> 512 chip reshard (the code path is identical)."""
    from jax.sharding import AxisType, PartitionSpec as P
    mesh_a = jax.make_mesh((1, 1), ("data", "model"),
                           axis_types=(AxisType.Auto,) * 2)
    mesh_b = jax.make_mesh((1,), ("data",), axis_types=(AxisType.Auto,))
    tree = _tree()
    pspecs = {"w": P(None, None), "scale": P(None),
              "nested": {"step": P()}}
    ckpt = Checkpointer(str(tmp_path))
    ckpt.save(2, tree)
    restored, step = elastic_restore(ckpt, tree, pspecs, mesh_b)
    assert step == 2
    np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                  np.asarray(tree["w"], np.float32))
    rep = reshard_plan(pspecs, mesh_a, mesh_b,
                       {"w": (8, 16), "scale": (16,),
                        "nested": {"step": ()}})
    assert rep.n_leaves == 3 and not rep.incompatible


def test_restore_missing_raises(tmp_path):
    ckpt = Checkpointer(str(tmp_path))
    with pytest.raises(FileNotFoundError):
        ckpt.restore(None, _tree())


def test_fault_tolerance_heartbeat_and_straggler():
    from repro.dist.fault_tolerance import HeartbeatMonitor, StragglerPolicy
    hb = HeartbeatMonitor(deadline_s=5.0)
    hb.beat(1, now=0.0)
    hb.beat(2, now=0.0)
    hb.beat(1, now=4.0)
    assert hb.sweep(now=6.0) == [2]
    assert hb.alive() == [1]
    sp = StragglerPolicy(factor=4.0)
    assert not sp.is_straggler(1.0, 3.9)
    assert sp.is_straggler(1.0, 4.1)
    assert sp.redo_cost(1.0) == 5.0
