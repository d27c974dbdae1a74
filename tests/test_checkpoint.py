"""Checkpoint: roundtrip fidelity, atomicity, large-array sharding,
latest-step resolution, InTune-state extras."""
import os

import jax.numpy as jnp
import numpy as np

from repro.train import checkpoint as ckpt


def _tree():
    rng = np.random.RandomState(0)
    return {
        "params": {"w": rng.randn(16, 8).astype(np.float32),
                   "layers": ({"a": rng.randn(3)}, {"a": rng.randn(3)})},
        "opt": {"m": rng.randn(16, 8).astype(np.float32)},
        "step": np.asarray(7),
    }


def _assert_tree_equal(a, b):
    import jax
    la = jax.tree_util.tree_leaves(a)
    lb = jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_roundtrip(tmp_path):
    tree = _tree()
    ckpt.save(str(tmp_path), 7, tree, extras={"note": "hi"})
    restored, manifest = ckpt.restore(str(tmp_path))
    _assert_tree_equal(tree, restored)
    assert manifest["step"] == 7
    assert manifest["extras"]["note"] == "hi"


def test_latest_step_skips_incomplete(tmp_path):
    ckpt.save(str(tmp_path), 1, _tree())
    ckpt.save(str(tmp_path), 5, _tree())
    # a crashed write: tmp dir without manifest
    os.makedirs(tmp_path / "step_00000009.tmp")
    assert ckpt.latest_step(str(tmp_path)) == 5
    restored, m = ckpt.restore(str(tmp_path))
    assert m["step"] == 5


def test_large_array_sharded(tmp_path):
    big = {"emb": np.arange(4000, dtype=np.float32).reshape(200, 20)}
    ckpt.save(str(tmp_path), 0, big, max_shard_bytes=4096)
    d = tmp_path / "step_00000000"
    shards = [f for f in os.listdir(d) if f.startswith("shard_")]
    assert len(shards) > 1                      # actually split
    restored, _ = ckpt.restore(str(tmp_path), 0)
    np.testing.assert_array_equal(restored["emb"], big["emb"])


def test_intune_state_rides_in_extras(tmp_path):
    from repro.core.controller import InTune
    from repro.data.pipeline import criteo_pipeline
    from repro.data.simulator import MachineSpec
    tuner = InTune(criteo_pipeline(), MachineSpec(), seed=0,
                   finetune_ticks=10)
    tuner.run(12)
    state = tuner.state_dict()
    ckpt.save(str(tmp_path), 3, {"agent_qnet": state["agent"]["qnet"]},
              extras={"workers": state["workers"],
                      "prefetch_mb": state["prefetch_mb"],
                      "agent_steps": state["agent"]["steps"]})
    restored, manifest = ckpt.restore(str(tmp_path))
    tuner2 = InTune(criteo_pipeline(), MachineSpec(), seed=1,
                    finetune_ticks=10)
    tuner2.load_state_dict({
        "agent": {"qnet": restored["agent_qnet"],
                  "steps": manifest["extras"]["agent_steps"]},
        "workers": manifest["extras"]["workers"],
        "prefetch_mb": manifest["extras"]["prefetch_mb"]})
    assert tuner2.allocation.workers.tolist() == state["workers"]
