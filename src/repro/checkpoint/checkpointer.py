"""Fault-tolerant checkpointing.

Guarantees (all tested in tests/test_checkpoint.py):
  * **Atomicity** -- a checkpoint directory appears only after a completed
    write (write to ``<step>.tmp`` then os.rename); the LATEST pointer is
    updated with write-temp + rename as well, so a crash mid-save can never
    corrupt the restore path.
  * **Integrity** -- per-leaf CRC32 in the manifest; restore verifies and
    falls back to the next-older checkpoint if any leaf fails (bit-rot /
    truncated write after a node failure).
  * **Exact resume** -- user extras (the supervisor's completed iteration
    count and current s) ride in the manifest, so a restart resumes at the
    exact iteration it snapshotted.
  * **Elastic restarts** -- arrays are stored with *logical* (unsharded)
    shapes, so the same checkpoint restarts on a different device count
    (the supervisor re-plans its mesh with
    ``faults.supervisor.plan_solver_mesh``).
  * **Async** -- saves run on a writer thread off the solve's critical path
    (state is device_get'd synchronously -- cheap relative to a segment --
    and serialized in the background).  keep=N pruning runs after each
    commit.
  * **No silent writer death** -- an exception on the background writer is
    captured and re-raised as :class:`CheckpointWriteError` on the next
    ``save()`` / ``wait()`` / ``close()``, so a failed async snapshot (disk
    full, permissions) can never silently break the restore chain the
    supervisor leans on.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
import zlib

import jax
import numpy as np


def _flatten(state):
    leaves, treedef = jax.tree.flatten(state)
    paths = [str(i) for i in range(len(leaves))]
    return leaves, paths, treedef


class CheckpointWriteError(RuntimeError):
    """A checkpoint write failed.  For async saves this surfaces on the NEXT
    ``save()`` / ``wait()`` / ``close()`` call -- the background thread's
    original exception is chained as ``__cause__``."""


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_save = async_save
        self._writer: threading.Thread | None = None
        self._writer_step: int | None = None
        self._pending_error: BaseException | None = None
        os.makedirs(directory, exist_ok=True)

    # ------------------------------------------------------------- save --
    def save(self, step: int, state, extra: dict | None = None,
             block: bool = False) -> None:
        self.wait()  # one in-flight save at a time; raises a captured failure
        host_state = jax.tree.map(np.asarray, jax.device_get(state))
        if self.async_save and not block:
            self._writer_step = step
            self._writer = threading.Thread(
                target=self._write_guarded, args=(step, host_state, extra or {}),
                daemon=True)
            self._writer.start()
        else:
            try:
                self._write(step, host_state, extra or {})
            except Exception as e:
                raise CheckpointWriteError(
                    f"checkpoint write for step {step} failed") from e

    def _write_guarded(self, step: int, host_state, extra: dict) -> None:
        # Runs on the writer thread: an uncaught exception here would die with
        # the thread, leaving callers believing the snapshot landed.  Capture
        # it; wait() re-raises on the caller's thread.
        try:
            self._write(step, host_state, extra)
        except BaseException as e:
            self._pending_error = e

    def _raise_pending(self) -> None:
        if self._pending_error is not None:
            e, self._pending_error = self._pending_error, None
            step = self._writer_step
            raise CheckpointWriteError(
                f"async checkpoint write for step {step} failed") from e

    def wait(self) -> None:
        if self._writer is not None:
            self._writer.join()
            self._writer = None
        self._raise_pending()

    def close(self) -> None:
        """Drain the writer and surface any captured failure.  Call at end of
        job (or use ``wait()``) -- otherwise a failed final snapshot is only
        detected by the next save."""
        self.wait()

    def _write(self, step: int, host_state, extra: dict) -> None:
        leaves, paths, _ = _flatten(host_state)
        final = os.path.join(self.dir, f"step_{step:010d}")
        tmp = final + ".tmp"
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        manifest = {"step": step, "extra": extra, "leaves": []}
        for p, leaf in zip(paths, leaves):
            arr = np.asarray(leaf)
            fname = f"leaf_{p}.npy"
            np.save(os.path.join(tmp, fname), arr)
            manifest["leaves"].append({
                "path": p, "file": fname, "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "crc": zlib.crc32(np.ascontiguousarray(arr).tobytes()),
            })
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        shutil.rmtree(final, ignore_errors=True)
        os.rename(tmp, final)
        # atomic LATEST pointer
        ptr_tmp = os.path.join(self.dir, "LATEST.tmp")
        with open(ptr_tmp, "w") as f:
            f.write(os.path.basename(final))
        os.replace(ptr_tmp, os.path.join(self.dir, "LATEST"))
        self._prune()

    def _prune(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.dir, f"step_{s:010d}"),
                          ignore_errors=True)

    # ---------------------------------------------------------- restore --
    def all_steps(self) -> list[int]:
        out = []
        for name in os.listdir(self.dir):
            if name.startswith("step_") and not name.endswith(".tmp"):
                try:
                    out.append(int(name.split("_")[1]))
                except ValueError:
                    continue
        return sorted(out)

    def restore_latest(self, like, shardings=None):
        """Restore the newest valid checkpoint.

        ``like``: a pytree with the target structure (arrays or
        ShapeDtypeStructs).  ``shardings``: optional matching pytree of
        Shardings for elastic placement.  Returns (state, extra, step) or
        None if no valid checkpoint exists.
        """
        for step in reversed(self.all_steps()):
            try:
                return self._restore(step, like, shardings)
            except Exception as e:  # corrupt -> try older
                print(f"[checkpoint] step {step} unusable ({e}); trying older")
        return None

    def _restore(self, step: int, like, shardings):
        d = os.path.join(self.dir, f"step_{step:010d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        _, paths, treedef = _flatten(like)
        by_path = {l["path"]: l for l in manifest["leaves"]}
        if set(paths) != set(by_path):
            raise ValueError("checkpoint structure mismatch")
        arrays = []
        for p in paths:
            entry = by_path[p]
            arr = np.load(os.path.join(d, entry["file"]))
            if zlib.crc32(np.ascontiguousarray(arr).tobytes()) != entry["crc"]:
                raise IOError(f"crc mismatch in leaf {p}")
            if arr.dtype.kind == "V":  # bfloat16 etc round-trip as raw void
                import ml_dtypes  # noqa: F401  (registers numpy dtypes)
                arr = arr.view(np.dtype(entry["dtype"]))
            arrays.append(arr)
        state = jax.tree.unflatten(treedef, arrays)
        if shardings is not None:
            state = jax.tree.map(
                lambda a, s: jax.device_put(a, s), state, shardings)
        return state, manifest["extra"], step
