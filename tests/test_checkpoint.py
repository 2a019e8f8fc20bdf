"""Fault-tolerance tests: atomic save/restore roundtrip, CRC corruption
fallback, keep-k pruning, async writer and its error surfacing."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


def _state(seed=0):
    k = jax.random.key(seed)
    return {
        "params": {"w": jax.random.normal(k, (8, 16), jnp.float32),
                   "b": jnp.zeros((16,), jnp.bfloat16)},
        "step": jnp.asarray(seed, jnp.int32),
    }


def _like(state):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state)


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = _state(3)
    mgr.save(3, state, {"data": {"step": 3}})
    restored, extra, step = mgr.restore_latest(_like(state))
    assert step == 3 and extra["data"]["step"] == 3
    jax.tree.map(lambda a, b: np.testing.assert_array_equal(
        np.asarray(a, np.float32), np.asarray(b, np.float32)), state, restored)


def test_bf16_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    state = {"w": jnp.full((4,), 1.5, jnp.bfloat16)}
    mgr.save(1, state)
    restored, _, _ = mgr.restore_latest(_like(state))
    assert restored["w"].dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(restored["w"], np.float32),
                                  np.full((4,), 1.5, np.float32))


def test_corruption_falls_back_to_older(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False, keep=5)
    mgr.save(1, _state(1))
    mgr.save(2, _state(2))
    # corrupt the newest checkpoint's first leaf
    d = os.path.join(str(tmp_path), "step_0000000002")
    leaf = [f for f in os.listdir(d) if f.endswith(".npy")][0]
    with open(os.path.join(d, leaf), "r+b") as f:
        f.seek(-4, 2)
        f.write(b"\xde\xad\xbe\xef")
    restored, _, step = mgr.restore_latest(_like(_state(0)))
    assert step == 1  # fell back
    assert int(restored["step"]) == 1


def test_keep_k_pruning(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False, keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, _state(s))
    assert mgr.all_steps() == [3, 4]


def test_async_save(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=True)
    mgr.save(7, _state(7))
    mgr.wait()
    assert mgr.all_steps() == [7]


def test_atomicity_no_tmp_left(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _state(1))
    assert not [f for f in os.listdir(str(tmp_path)) if f.endswith(".tmp")]
    assert open(os.path.join(str(tmp_path), "LATEST")).read() == "step_0000000001"


def _break_directory(path):
    """Replace the checkpoint directory with a regular file so every write
    inside it fails (works under root, unlike permission bits)."""
    import shutil
    shutil.rmtree(path)
    with open(path, "w") as f:
        f.write("not a directory")


def test_async_writer_error_surfaces_on_next_save(tmp_path):
    """A failed background write must NOT vanish with the thread: the next
    save() re-raises it as CheckpointWriteError (chained to the original)."""
    from repro.checkpoint import CheckpointWriteError
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, async_save=True)
    mgr.save(1, _state(1))
    mgr.wait()                       # clean write goes through
    assert mgr.all_steps() == [1]
    _break_directory(d)
    mgr.save(2, _state(2))           # writer thread dies silently...
    with pytest.raises(CheckpointWriteError) as exc:
        mgr.save(3, _state(3))       # ...and THIS surfaces it
    assert exc.value.__cause__ is not None


def test_async_writer_error_surfaces_on_close(tmp_path):
    from repro.checkpoint import CheckpointWriteError
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, async_save=True)
    _break_directory(d)
    mgr.save(1, _state(1))
    with pytest.raises(CheckpointWriteError):
        mgr.close()
    mgr.close()                      # error is consumed; close is idempotent


def test_sync_save_raises_immediately(tmp_path):
    from repro.checkpoint import CheckpointWriteError
    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, async_save=False)
    _break_directory(d)
    with pytest.raises(CheckpointWriteError):
        mgr.save(1, _state(1))


def test_structure_mismatch_rejected(tmp_path):
    mgr = CheckpointManager(str(tmp_path), async_save=False)
    mgr.save(1, _state(1))
    bad_like = {"other": jax.ShapeDtypeStruct((3,), jnp.float32)}
    assert mgr.restore_latest(bad_like) is None
