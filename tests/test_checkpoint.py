"""Checkpointing: atomic commit, restore, resharding, async, crash tail."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"a": jax.random.normal(k, (4, 8)),
            "b": {"c": jnp.arange(6, dtype=jnp.int32),
                  "d": jnp.float32(3.5)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 7, t, metadata={"data_step": 7})
    restored, manifest = ckpt.restore(str(tmp_path), 7, t)
    assert manifest["step"] == 7
    assert manifest["metadata"]["data_step"] == 7
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_and_gc(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ckpt.save(str(tmp_path), s, t, keep_last=3)
    assert ckpt.latest_step(str(tmp_path)) == 5
    assert ckpt.all_steps(str(tmp_path)) == [3, 4, 5]


def test_restore_shape_mismatch_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.zeros((2, 2))})
    with pytest.raises(ValueError):
        ckpt.restore(str(tmp_path), 1, {"a": jnp.zeros((3, 3))})


def test_restore_missing_leaf_raises(tmp_path):
    ckpt.save(str(tmp_path), 1, {"a": jnp.zeros(2)})
    with pytest.raises(KeyError):
        ckpt.restore(str(tmp_path), 1, {"a": jnp.zeros(2), "b": jnp.zeros(2)})


def test_restore_into_new_sharding(tmp_path):
    """elastic rescale: restore device_puts onto target shardings."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    t = {"w": jnp.arange(16.0).reshape(4, 4)}
    ckpt.save(str(tmp_path), 2, t)
    sh = {"w": NamedSharding(mesh, P("data", None))}
    restored, _ = ckpt.restore(str(tmp_path), 2, t, shardings=sh)
    assert restored["w"].sharding == sh["w"]
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.asarray(t["w"]))


def test_interrupted_save_leaves_no_partial_checkpoint(tmp_path):
    """a .tmp dir (crash before rename) is never listed as a checkpoint."""
    os.makedirs(tmp_path / "step_9.tmp")
    assert ckpt.all_steps(str(tmp_path)) == []


def test_async_checkpointer(tmp_path):
    t = _tree()
    ac = ckpt.AsyncCheckpointer(str(tmp_path), keep_last=2)
    ac.save(1, t)
    ac.save(2, t, metadata={"x": 1})
    ac.close()
    assert ckpt.all_steps(str(tmp_path)) == [1, 2]
    restored, man = ckpt.restore(str(tmp_path), 2, t)
    assert man["metadata"]["x"] == 1
