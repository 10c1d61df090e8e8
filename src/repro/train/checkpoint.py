"""Fault-tolerant checkpointing (the NV-element analogue, DESIGN.md §2).

Design mirrors the paper's two-tier retention:
  * FULL checkpoints (params + optimizer + data cursor) — the "NV write
    every N frames": async (background thread), atomic (write tmp ->
    fsync -> rename), self-describing manifest, keep-k GC.
  * ACCUMULATION snapshots (see intermittent.py) — the NV-FA partial-sum
    retention: tiny, frequent, resumable mid-step.

No orbax dependency: npz + json manifest, multi-host-aware naming
(process_index suffix) so each host writes only its addressable shards.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time
from typing import Any, Optional

import jax
import numpy as np


def _flatten(tree) -> dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx)
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _unflatten(template, flat: dict[str, np.ndarray]):
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = []
    for path, leaf in leaves_p:
        key = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx)
                       for p in path)
        arr = flat[key]
        leaves.append(arr.astype(leaf.dtype) if hasattr(leaf, "dtype") else arr)
    return jax.tree_util.tree_unflatten(treedef, leaves)


class CheckpointWriteError(RuntimeError):
    """An async checkpoint write failed after `save()` already returned."""


class Checkpointer:
    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.dir = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        os.makedirs(directory, exist_ok=True)
        # sweep stale .tmp_* dirs left by a process killed mid-write: they
        # never published (rename never ran) so they hold no durable state,
        # but they escape keep-k GC and would otherwise accumulate forever
        for name in os.listdir(directory):
            if name.startswith(".tmp_"):
                shutil.rmtree(os.path.join(directory, name),
                              ignore_errors=True)

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state: Any, extra: dict | None = None,
             tag: str = "ckpt") -> str:
        """Returns the final path (rename happens after write completes)."""
        self.wait()  # one in-flight save at a time
        flat = _flatten(state)  # device->host copy happens here, synchronously
        final = os.path.join(self.dir, f"{tag}_{step:08d}")

        def _write():
            tmp = tempfile.mkdtemp(dir=self.dir, prefix=".tmp_")
            try:
                np.savez(os.path.join(tmp, "arrays.npz"), **flat)
                manifest = dict(step=step, time=time.time(),
                                n_arrays=len(flat), tag=tag,
                                process_index=jax.process_index(),
                                extra=extra or {})
                with open(os.path.join(tmp, "manifest.json"), "w") as f:
                    json.dump(manifest, f)
                    f.flush()
                    os.fsync(f.fileno())
                if os.path.exists(final):  # same-step overwrite (re-snapshot
                    old = final + ".old"   # after a mid-step restart)
                    shutil.rmtree(old, ignore_errors=True)
                    os.rename(final, old)
                    os.rename(tmp, final)  # atomic publish
                    shutil.rmtree(old, ignore_errors=True)
                else:
                    os.rename(tmp, final)  # atomic publish
            except BaseException:
                shutil.rmtree(tmp, ignore_errors=True)
                raise
            self._gc(tag)

        if self.async_save:
            # A daemon thread's exception would otherwise only reach
            # threading's default excepthook (stderr) — the caller would
            # believe the NV write succeeded and GC the durable state it
            # replaces.  Capture it; wait()/the next save() re-raises.
            def _run():
                try:
                    _write()
                except BaseException as e:  # noqa: BLE001  repro-lint: disable=RL003 — captured into _error; wait()/next save() re-raises
                    self._error = e

            self._thread = threading.Thread(target=_run, daemon=True)
            self._thread.start()
        else:
            _write()
        return final

    def wait(self):
        """Block until the in-flight save completes; raise if it failed.

        A failed async write surfaces here (or at the next ``save()``,
        which waits first) instead of being silently dropped — callers
        treating ``wait()`` as the durability barrier get the truth.
        """
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise CheckpointWriteError(
                f"async checkpoint write failed: {err!r}") from err

    # -- restore --------------------------------------------------------------
    def latest_step(self, tag: str = "ckpt") -> Optional[int]:
        steps = []
        for name in os.listdir(self.dir):
            if name.startswith(f"{tag}_") and not name.startswith("."):
                p = os.path.join(self.dir, name, "manifest.json")
                if os.path.exists(p):  # only fully-published checkpoints
                    steps.append(int(name.split("_")[-1]))
        return max(steps) if steps else None

    def restore(self, template: Any, step: Optional[int] = None,
                tag: str = "ckpt"):
        """Returns (step, state) or (None, None) when nothing to restore."""
        step = step if step is not None else self.latest_step(tag)
        if step is None:
            return None, None
        path = os.path.join(self.dir, f"{tag}_{step:08d}")
        with np.load(os.path.join(path, "arrays.npz")) as z:
            flat = {k: z[k] for k in z.files}
        return step, _unflatten(template, flat)

    def manifest(self, step: int, tag: str = "ckpt") -> dict:
        path = os.path.join(self.dir, f"{tag}_{step:08d}", "manifest.json")
        with open(path) as f:
            return json.load(f)

    def purge(self, prefix: str) -> int:
        """Remove every published checkpoint whose name starts with
        ``prefix``; returns how many were removed.  Prefix (not exact-tag)
        matching on purpose: a family of derived tags can be dropped
        wholesale with its common stem.  Waits for any in-flight async
        save first so a concurrent write cannot republish what was just
        purged."""
        self.wait()
        n = 0
        for name in list(os.listdir(self.dir)):
            if name.startswith(prefix) and not name.startswith("."):
                shutil.rmtree(os.path.join(self.dir, name),
                              ignore_errors=True)
                n += 1
        return n

    def _gc(self, tag: str):
        entries = sorted(
            n for n in os.listdir(self.dir)
            if n.startswith(f"{tag}_") and not n.startswith("."))
        for name in entries[: max(0, len(entries) - self.keep)]:
            shutil.rmtree(os.path.join(self.dir, name), ignore_errors=True)
