"""Checkpoint protocol: atomicity, completeness flag, GC, restore."""
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt import checkpoint as ckpt


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "a": jnp.asarray(rng.normal(size=(4, 8)), jnp.float32),
        "b": {"c": jnp.asarray(rng.integers(0, 9, (3,)), jnp.int32)},
    }


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 7, t)
    assert ckpt.latest_step(str(tmp_path)) == 7
    got = ckpt.restore(str(tmp_path), 7, t)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


import jax  # noqa: E402  (used above)


def test_incomplete_checkpoint_ignored(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 5, t)
    # simulate a crash mid-write: manifest exists but incomplete
    d = tmp_path / "step_00000009"
    d.mkdir()
    with open(d / "manifest.json", "w") as f:
        json.dump({"step": 9, "complete": False, "n_leaves": 0, "leaves": []}, f)
    assert ckpt.latest_step(str(tmp_path)) == 5


def test_gc_keeps_latest(tmp_path):
    t = _tree()
    for s in [1, 2, 3, 4, 5]:
        ckpt.save(str(tmp_path), s, t, keep=2)
    steps = sorted(os.listdir(tmp_path))
    assert steps == ["step_00000004", "step_00000005"]


def test_restore_validates_shapes(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 1, t)
    wrong = {"a": jnp.zeros((2, 2)), "b": {"c": jnp.zeros((3,), jnp.int32)}}
    with pytest.raises(AssertionError):
        ckpt.restore(str(tmp_path), 1, wrong)


def test_restore_with_shardings(tmp_path):
    t = _tree()
    ckpt.save(str(tmp_path), 1, t)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    from jax.sharding import NamedSharding, PartitionSpec as P
    sh = {"a": NamedSharding(mesh, P()), "b": {"c": NamedSharding(mesh, P())}}
    got = ckpt.restore(str(tmp_path), 1, t, shardings=sh)
    np.testing.assert_array_equal(np.asarray(got["a"]), np.asarray(t["a"]))
