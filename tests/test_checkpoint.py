"""Checkpoint store: atomic save/load, half-write recovery, retention and
the async writer."""
import os

import jax.numpy as jnp
import numpy as np

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    load_checkpoint,
    save_checkpoint,
)


def test_checkpoint_roundtrip(tmp_path):
    tree = {"w": jnp.arange(10.0), "n": {"b": jnp.ones((2, 3))}}
    save_checkpoint(str(tmp_path), 7, tree, extra={"loss": 1.5})
    restored, step, extra = load_checkpoint(str(tmp_path), tree)
    assert step == 7 and extra["loss"] == 1.5
    np.testing.assert_array_equal(np.asarray(restored["w"]),
                                  np.arange(10.0))


def test_checkpoint_atomicity_ignores_tmp(tmp_path):
    tree = {"w": jnp.ones(3)}
    save_checkpoint(str(tmp_path), 5, tree)
    # simulate a crashed half-write
    os.makedirs(tmp_path / "step_000000009.tmp")
    assert latest_step(str(tmp_path)) == 5


def test_checkpoint_retention(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2, async_write=False)
    tree = {"w": jnp.ones(3)}
    for s in (1, 2, 3, 4):
        mgr.save(s, tree)
    steps = sorted(
        int(n.split("_")[1]) for n in os.listdir(tmp_path)
        if n.startswith("step_"))
    assert steps == [3, 4]


def test_checkpoint_async(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=3, async_write=True)
    tree = {"w": jnp.arange(5.0)}
    mgr.save(11, tree)
    mgr.wait()
    assert latest_step(str(tmp_path)) == 11
    mgr.close()
