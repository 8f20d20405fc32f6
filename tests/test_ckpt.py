"""Checkpoint/restart: roundtrip, atomicity, async, elastic resharding."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.ckpt.checkpoint import (AsyncCheckpointer, latest_step, prune,
                                   restore, save)


def _tree(seed=0):
    rng = np.random.default_rng(seed)
    return {"w": jnp.asarray(rng.standard_normal((16, 8)), jnp.float32),
            "inner": {"b": jnp.asarray(rng.standard_normal(8), jnp.float32),
                      "step": jnp.asarray(7, jnp.int32)}}


def test_save_restore_roundtrip(tmp_path):
    t = _tree()
    save(str(tmp_path), 5, t, extra={"note": "x"})
    assert latest_step(str(tmp_path)) == 5
    out, manifest = restore(str(tmp_path), t)
    assert manifest["step"] == 5 and manifest["extra"]["note"] == "x"
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_restore_structure_mismatch_raises(tmp_path):
    save(str(tmp_path), 1, _tree())
    bad = {"w": jnp.zeros((16, 8)), "other": jnp.zeros(3)}
    with pytest.raises(ValueError, match="structure mismatch"):
        restore(str(tmp_path), bad)


def test_restore_shape_mismatch_names_leaf(tmp_path):
    save(str(tmp_path), 1, _tree())
    bad = _tree()
    bad["w"] = jnp.zeros((4, 8), jnp.float32)
    with pytest.raises(ValueError, match="'w'"):
        restore(str(tmp_path), bad)


def test_crash_mid_write_recovery(tmp_path):
    """A writer killed mid-write leaves a .tmp-step_* dir: readers ignore
    it, the next save sweeps it, and restore serves the last committed
    step."""
    t = _tree()
    save(str(tmp_path), 1, t)
    # a killed writer's half-finished step-2 attempt
    junk = tmp_path / ".tmp-step_00000002"
    os.makedirs(junk)
    (junk / "arrays.npz").write_bytes(b"partial garbage")
    assert latest_step(str(tmp_path)) == 1          # never visible
    out, manifest = restore(str(tmp_path), t)
    assert manifest["step"] == 1
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(out)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    save(str(tmp_path), 3, _tree(3))                # sweeps the leftovers
    assert not junk.exists()
    assert latest_step(str(tmp_path)) == 3


def test_listdir_noise_tolerated(tmp_path):
    """Foreign files/dirs that merely resemble checkpoints don't crash
    step parsing."""
    save(str(tmp_path), 4, _tree())
    (tmp_path / "step_notanumber").mkdir()
    (tmp_path / "stepfile.txt").write_text("x")
    assert latest_step(str(tmp_path)) == 4
    prune(str(tmp_path), keep=1)
    assert latest_step(str(tmp_path)) == 4


def test_latest_and_prune(tmp_path):
    for s in (1, 3, 7, 9):
        save(str(tmp_path), s, _tree(s))
    assert latest_step(str(tmp_path)) == 9
    prune(str(tmp_path), keep=2)
    steps = sorted(int(d.split("_")[1]) for d in os.listdir(tmp_path))
    assert steps == [7, 9]


def test_no_partial_checkpoint_visible(tmp_path):
    """Tmp dirs never count as checkpoints (atomic-rename commit)."""
    os.makedirs(tmp_path / ".tmp-step_00000042")
    assert latest_step(str(tmp_path)) is None


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(str(tmp_path), keep=2)
    for s in range(4):
        ck.save(s, _tree(s))
    ck.wait()
    assert latest_step(str(tmp_path)) == 3
    out, _ = restore(str(tmp_path), _tree())
    np.testing.assert_array_equal(np.asarray(out["w"]),
                                  np.asarray(_tree(3)["w"]))


def test_elastic_restore_onto_sharding(tmp_path):
    """Restore places leaves with a target sharding (mesh-shape agnostic)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    t = _tree()
    save(str(tmp_path), 2, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P("data")),
          "inner": {"b": NamedSharding(mesh, P()),
                    "step": NamedSharding(mesh, P())}}
    out, _ = restore(str(tmp_path), t, shardings=sh)
    assert out["w"].sharding.is_equivalent_to(sh["w"], 2)
    np.testing.assert_array_equal(np.asarray(out["w"]), np.asarray(t["w"]))
