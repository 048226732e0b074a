"""Checkpointing: atomic, async, resumable (orbax is not available offline).

Layout (one directory per step):
    <root>/step_000123/
        arrays.npz          — flattened pytree leaves (host numpy)
        meta.json           — step, controller state, RNG, treedef repr
    <root>/LATEST           — atomically updated pointer file

Guarantees:
  * atomicity  — writes land in a tmp dir, fsync'd, then os.rename (POSIX
    atomic) + pointer update; a crash mid-save never corrupts LATEST;
  * async      — ``save_async`` snapshots to host memory synchronously
    (cheap) and writes in a daemon thread, overlapping the next steps;
  * resume     — ``restore_latest`` reloads (params, opt_state, extras),
    re-sharding leaves onto the CURRENT mesh (elastic restarts onto a
    different topology re-use the same files);
  * retention  — keep_last N checkpoints, older ones pruned post-save.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["CheckpointError", "CheckpointManager", "CHECKPOINT_SCHEMA"]

#: bump when the on-disk layout changes incompatibly. Checkpoints written
#: before the field existed load as version 1.
CHECKPOINT_SCHEMA = 1


class CheckpointError(RuntimeError):
    """A checkpoint on disk cannot be loaded: truncated or corrupt
    ``arrays.npz``/``meta.json``, or a schema version this build does not
    understand. Always names the offending path — the recovery action
    (delete the directory, fall back to an older step, upgrade the code)
    depends on WHICH file is bad."""


def _flatten_with_paths(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(p) for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _fsync_dir(path: Path) -> None:
    """fsync a DIRECTORY: durably commit its entries (the renames).

    File-content fsyncs alone do not make an os.rename durable — the
    new directory entry lives in the parent directory's data, which has
    its own fd to sync. No-op on platforms without directory fds.
    """
    flags = os.O_RDONLY | getattr(os, "O_DIRECTORY", 0)
    try:
        fd = os.open(path, flags)
    except OSError:
        return
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


class CheckpointManager:
    def __init__(self, root: str | Path, keep_last: int = 3):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep_last = keep_last
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # -- save ---------------------------------------------------------------
    def save(self, step: int, state, extras: Optional[dict] = None) -> Path:
        """Synchronous atomic save of a pytree + json-serializable extras."""
        arrays = _flatten_with_paths(state)
        tmp = self.root / f".tmp_step_{step:09d}_{os.getpid()}"
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        np.savez(tmp / "arrays.npz", **arrays)
        # npz keeps only numpy's own dtypes: a bfloat16 leaf reads back as
        # raw 2-byte voids. The meta records every leaf's dtype by name.
        meta = {"step": step, "time": time.time(),
                "schema": CHECKPOINT_SCHEMA, "extras": extras or {},
                "dtypes": {k: str(a.dtype) for k, a in arrays.items()}}
        (tmp / "meta.json").write_text(json.dumps(meta))
        # Durability order: file contents -> tmp dir entries -> atomic
        # rename -> parent dir entry (the rename itself) -> LATEST.
        for f in tmp.iterdir():
            with open(f, "rb") as fh:
                os.fsync(fh.fileno())
        _fsync_dir(tmp)
        final = self.root / f"step_{step:09d}"
        if final.exists():
            shutil.rmtree(final)
        os.rename(tmp, final)
        _fsync_dir(self.root)
        self._update_latest(final.name)
        self._prune()
        return final

    def save_async(self, step: int, state, extras: Optional[dict] = None):
        """Snapshot to host memory now; write in the background."""
        self.wait()  # one in-flight save at a time
        host_state = jax.tree.map(lambda x: np.asarray(x), state)

        def work():
            try:
                self.save(step, host_state, extras)
            except BaseException as e:  # noqa: BLE001 — surfaced via wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    # -- restore --------------------------------------------------------------
    def latest_step(self) -> Optional[int]:
        ptr = self.root / "LATEST"
        if not ptr.exists():
            return None
        name = ptr.read_text().strip()
        if not (self.root / name).exists():
            return None
        try:
            return int(name.split("_")[-1])
        except ValueError as e:
            raise CheckpointError(
                f"corrupt LATEST pointer {ptr}: {name!r} is not a "
                "step_NNNNNNNNN directory name"
            ) from e

    def restore(
        self,
        step: int,
        like,
        device_put_fn: Optional[Callable[[np.ndarray, Any], Any]] = None,
    ) -> Tuple[Any, dict]:
        """Restore into the structure of ``like`` (a pytree of arrays or
        ShapeDtypeStructs). device_put_fn(leaf, like_leaf) can re-shard
        onto the current mesh (elastic restart)."""
        d = self.root / f"step_{step:09d}"
        if not d.is_dir():
            raise CheckpointError(f"no checkpoint directory at {d}")
        arrays_path, meta_path = d / "arrays.npz", d / "meta.json"
        try:
            with np.load(arrays_path) as data:
                arrays = {k: data[k] for k in data.files}
        except FileNotFoundError as e:
            raise CheckpointError(f"checkpoint missing {arrays_path}") from e
        except Exception as e:  # zipfile.BadZipFile, OSError, ValueError, ...
            raise CheckpointError(
                f"truncated or corrupt checkpoint arrays at {arrays_path}: {e}"
            ) from e
        try:
            meta = json.loads(meta_path.read_text())
        except FileNotFoundError as e:
            raise CheckpointError(f"checkpoint missing {meta_path}") from e
        except (json.JSONDecodeError, UnicodeDecodeError, OSError) as e:
            raise CheckpointError(
                f"truncated or corrupt checkpoint metadata at {meta_path}: {e}"
            ) from e
        schema = meta.get("schema", 1)
        if schema != CHECKPOINT_SCHEMA:
            raise CheckpointError(
                f"checkpoint {meta_path} has schema version {schema!r}; this "
                f"build reads version {CHECKPOINT_SCHEMA} — load it with a "
                "matching build instead of guessing at the layout"
            )

        dtypes = meta.get("dtypes", {})
        leaves_with_paths = jax.tree_util.tree_flatten_with_path(like)[0]
        treedef = jax.tree_util.tree_structure(like)
        out = []
        for path, leaf in leaves_with_paths:
            key = "/".join(str(p) for p in path)
            if key not in arrays:
                raise KeyError(f"checkpoint missing leaf {key}")
            val = arrays[key]
            if key in dtypes:
                val = val.view(jnp.dtype(dtypes[key]))
            if hasattr(leaf, "dtype"):
                val = val.astype(leaf.dtype, copy=False)
            if device_put_fn is not None:
                val = device_put_fn(val, leaf)
            out.append(val)
        return jax.tree_util.tree_unflatten(treedef, out), meta["extras"]

    def restore_latest(self, like, device_put_fn=None):
        step = self.latest_step()
        if step is None:
            return None
        state, extras = self.restore(step, like, device_put_fn)
        return step, state, extras

    # -- internals ------------------------------------------------------------
    def _update_latest(self, name: str):
        ptr_tmp = self.root / ".LATEST_tmp"
        with open(ptr_tmp, "w") as fh:
            fh.write(name)
            fh.flush()
            os.fsync(fh.fileno())
        os.rename(ptr_tmp, self.root / "LATEST")
        _fsync_dir(self.root)  # the pointer flip must survive a crash too

    def _prune(self):
        steps = sorted(
            p for p in self.root.iterdir()
            if p.is_dir() and p.name.startswith("step_")
        )
        for old in steps[: -self.keep_last]:
            shutil.rmtree(old, ignore_errors=True)
