"""Checkpoint manager: roundtrip, retention, elastic re-shard restore."""
import os
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.manager import CheckpointManager, restore, save
from repro.sharding.compat import make_mesh

TMP = "results/_test_ckpt"


@pytest.fixture(autouse=True)
def _clean():
    shutil.rmtree(TMP, ignore_errors=True)
    os.makedirs(TMP, exist_ok=True)
    yield
    shutil.rmtree(TMP, ignore_errors=True)


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "a": jax.random.normal(k, (16, 8)),
        "nested": {"b": jnp.arange(10, dtype=jnp.int32), "c": jnp.ones((3,), jnp.bfloat16)},
    }


def test_roundtrip_exact():
    tree = _tree()
    save(os.path.join(TMP, "x"), tree, extra={"step": 7})
    out, extra = restore(os.path.join(TMP, "x"), tree)
    assert extra["step"] == 7
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(out)):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
        assert a.dtype == b.dtype


def test_manager_retention_and_latest():
    mgr = CheckpointManager(TMP, keep=2)
    for step in (10, 20, 30):
        mgr.save(step, _tree(step))
    assert mgr.latest_step() == 30
    dirs = sorted(d for d in os.listdir(TMP) if d.startswith("step_"))
    assert dirs == ["step_20", "step_30"]  # step_10 evicted


def test_restore_latest_roundtrip():
    mgr = CheckpointManager(TMP, keep=3)
    t = _tree(1)
    mgr.save(5, t)
    out, extra = mgr.restore_latest(t)
    assert extra["step"] == 5
    np.testing.assert_allclose(np.asarray(out["a"]), np.asarray(t["a"]))


def test_elastic_reshard_restore():
    """Restore with explicit target shardings (different 'mesh')."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    tree = _tree(2)
    save(os.path.join(TMP, "y"), tree)
    mesh = make_mesh((1, 1), ("data", "model"))
    sh = jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), tree)
    out, _ = restore(os.path.join(TMP, "y"), tree, shardings=sh)
    for leaf in jax.tree_util.tree_leaves(out):
        assert leaf.sharding.mesh.shape == {"data": 1, "model": 1}


def test_corrupt_save_does_not_clobber(monkeypatch):
    """A failed save must leave the previous checkpoint intact (atomicity)."""
    path = os.path.join(TMP, "z")
    tree = _tree(3)
    save(path, tree, extra={"v": 1})

    import repro.checkpoint.manager as mgr

    class Boom(Exception):
        pass

    def bad_packb(*a, **k):
        raise Boom()

    # fail inside the tmp-dir write, regardless of which codec is in use
    monkeypatch.setattr(mgr.msgpack, "packb", bad_packb)
    with pytest.raises(Boom):
        save(path, _tree(4), extra={"v": 2})
    out, extra = restore(path, tree)
    assert extra["v"] == 1
