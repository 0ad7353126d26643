"""Checkpointing: roundtrip, atomicity, retention, async, data-loader resume."""

import json
import threading
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint.checkpoint import (CheckpointManager, restore_pytree,
                                         save_pytree)
from repro.data.tokens import TokenDataset


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    return {"w": jax.random.normal(k, (8, 16)),
            "inner": {"b": jnp.arange(5, dtype=jnp.int32)},
            "scalar": jnp.float32(3.25)}


def test_roundtrip(tmp_path):
    t = _tree()
    save_pytree(t, tmp_path / "ck", extra={"step": 7})
    restored = restore_pytree(jax.tree.map(jnp.zeros_like, t), tmp_path / "ck")
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_atomic_publish_no_tmp_left(tmp_path):
    save_pytree(_tree(), tmp_path / "ck")
    assert (tmp_path / "ck" / "manifest.json").exists()
    assert not (tmp_path / "ck.tmp").exists()


def test_manifest_validates_structure(tmp_path):
    save_pytree(_tree(), tmp_path / "ck")
    bad_template = {"w": jnp.zeros((8, 16)), "inner": {"b": jnp.zeros(5, jnp.int32)},
                    "scalar": jnp.zeros(()), "EXTRA": jnp.zeros(3)}
    with pytest.raises(KeyError):
        restore_pytree(bad_template, tmp_path / "ck")


def test_manager_retention_and_latest(tmp_path):
    m = CheckpointManager(tmp_path, keep=2)
    for s in (10, 20, 30):
        m.save(s, _tree(s))
    assert m.steps() == [20, 30]
    assert m.latest_step() == 30
    restored, extra, step = m.restore(jax.tree.map(jnp.zeros_like, _tree()))
    assert step == 30


def test_async_save_equivalent(tmp_path):
    m = CheckpointManager(tmp_path, keep=3)
    t = _tree(5)
    m.save_async(1, t, extra={"x": 1})
    m.wait()
    restored, extra, _ = m.restore(jax.tree.map(jnp.zeros_like, t))
    assert extra == {"x": 1}
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_async_save_snapshots_host_leaves(tmp_path):
    """An async save writes the tree as it was at the call, though the
    caller writes into its host arrays while the save runs (a serving
    fleet's ``h_seq`` between steps)."""
    m = CheckpointManager(tmp_path, keep=3)
    buf = np.arange(1 << 20, dtype=np.int32)
    m.save_async(1, {"buf": buf})
    buf[:] = -1
    m.wait()
    restored, _, _ = m.restore({"buf": np.zeros_like(buf)}, step=1)
    np.testing.assert_array_equal(np.asarray(restored["buf"]),
                                  np.arange(1 << 20, dtype=np.int32))


def test_torn_write_recovery(tmp_path):
    """A crash mid-save leaves ``step_<N>.tmp/`` with payload but no
    manifest.  ``steps()`` must not list it, ``restore()`` must fall back to
    the last published step, and the restore-time sweep must remove the
    debris so retries of step N start clean."""
    from repro.serving.faults import torn_save

    m = CheckpointManager(tmp_path, keep=3)
    m.save(1, _tree(1))
    m.save(2, _tree(2))
    orphan = torn_save(m, 3, _tree(3))
    assert orphan.exists() and not (orphan / "manifest.json").exists()

    assert m.steps() == [1, 2]
    assert m.latest_step() == 2
    restored, _, step = m.restore(jax.tree.map(jnp.zeros_like, _tree()))
    assert step == 2
    assert not orphan.exists(), "restore must sweep the torn tmp dir"
    for a, b in zip(jax.tree.leaves(_tree(2)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_corrupt_published_step_skipped(tmp_path):
    """Post-publish disk rot (unparseable manifest) must drop the step from
    validity filtering instead of crashing restore."""
    from repro.serving.faults import corrupt_published

    m = CheckpointManager(tmp_path, keep=3)
    m.save(1, _tree(1))
    m.save(2, _tree(2))
    corrupt_published(m, 2)

    assert m.steps() == [1]
    restored, _, step = m.restore(jax.tree.map(jnp.zeros_like, _tree()))
    assert step == 1
    for a, b in zip(jax.tree.leaves(_tree(1)), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_payload_checksum_mismatch_detected(tmp_path):
    """Flipping payload bytes after publish must fail the manifest's
    prefix-checksum validation loudly, not return wrong integers."""
    save_pytree(_tree(), tmp_path / "ck", extra={})
    npz = tmp_path / "ck" / "arrays.npz"
    raw = bytearray(npz.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    npz.write_bytes(bytes(raw))
    # either the npz layer (CRC) or the manifest checksum must object
    with pytest.raises(Exception):
        restore_pytree(jax.tree.map(jnp.zeros_like, _tree()), tmp_path / "ck")


def test_dataloader_exact_resume():
    """Index-based loader: a restarted run consumes identical batches."""
    ds = TokenDataset(vocab_size=100, seq_len=16, global_batch=4, seed=3)
    run1 = [ds.batch_at(s)["tokens"] for s in range(6)]
    state = ds.state_dict(3)
    ds2 = TokenDataset(vocab_size=100, seq_len=16, global_batch=4, seed=3)
    resume = TokenDataset.resume_step(state)
    run2 = [ds2.batch_at(s)["tokens"] for s in range(resume, 6)]
    for a, b in zip(run1[3:], run2):
        np.testing.assert_array_equal(a, b)


def test_dataloader_host_sharding_covers_global_batch():
    """Union of host slices == the single-host global batch (elasticity)."""
    full = TokenDataset(vocab_size=50, seq_len=8, global_batch=8, seed=1)
    hosts = [TokenDataset(vocab_size=50, seq_len=8, global_batch=8, seed=1,
                          host_id=h, num_hosts=4) for h in range(4)]
    got = np.concatenate([h.batch_at(2)["tokens"] for h in hosts])
    np.testing.assert_array_equal(got, full.batch_at(2)["tokens"])
