"""Distributed checkpointing: atomic, integrity-checked, elastic-reshardable.

Layout (one directory per step, one .npy per pytree leaf):

    <root>/step_000123/
        MANIFEST.json   — tree structure, shapes, dtypes, sha256 per leaf,
                          user metadata, "committed": true (written LAST)
        leaf_00000.npy ...

Fault-tolerance properties (the paper's C6, adapted — see DESIGN.md):
  * atomic commit: leaves are written into a ``.tmp`` dir which is fsynced
    and renamed; a crash mid-save never corrupts the latest checkpoint;
  * integrity: sha256 per leaf, verified on restore;
  * elastic reshard: ``restore(shardings=...)`` device_puts each leaf under
    an arbitrary target sharding — save on a 16x16 mesh, restore on 2x16x16
    (or 1 CPU device) with no format change;
  * async: ``save(..., sync=False)`` snapshots to host then writes in a
    background thread, so the train loop overlaps I/O with compute.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import threading
from pathlib import Path
from typing import Any

import jax
import numpy as np


def _tree_paths(tree: Any) -> list[tuple[str, Any]]:
    leaves = jax.tree.leaves_with_path(tree)
    return [(jax.tree_util.keystr(p), v) for p, v in leaves]


class CheckpointManager:
    def __init__(self, root: str | Path, keep: int = 3):
        self.root = Path(root)
        self.root.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None

    # ------------------------------------------------------------------
    def _step_dir(self, step: int) -> Path:
        return self.root / f"step_{step:08d}"

    def steps(self) -> list[int]:
        out = []
        for d in self.root.glob("step_*"):
            mf = d / "MANIFEST.json"
            if mf.exists():
                try:
                    if json.loads(mf.read_text()).get("committed"):
                        out.append(int(d.name.split("_")[1]))
                except (json.JSONDecodeError, ValueError, IndexError):
                    continue
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    # ------------------------------------------------------------------
    def wait(self):
        """Block until a pending async save completes (re-raises its error)."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def save(self, step: int, tree: Any, meta: dict | None = None, sync: bool = True):
        self.wait()
        # snapshot to host memory first (cheap on CPU; on TPU this is the
        # device->host transfer that the async thread must not race with)
        host = [(k, np.asarray(v)) for k, v in _tree_paths(tree)]
        structure = jax.tree.structure(tree)

        def write():
            try:
                self._write(step, host, structure, meta or {})
            except BaseException as e:  # surfaced on next wait()/save()
                self._error = e

        if sync:
            write()
            self.wait()
        else:
            self._thread = threading.Thread(target=write, daemon=True)
            self._thread.start()

    def _write(self, step: int, host: list, structure, meta: dict):
        final = self._step_dir(step)
        tmp = final.with_suffix(".tmp")
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)
        entries = []
        for i, (keypath, arr) in enumerate(host):
            fname = f"leaf_{i:05d}.npy"
            np.save(tmp / fname, arr)
            digest = hashlib.sha256((tmp / fname).read_bytes()).hexdigest()
            entries.append(
                {
                    "key": keypath,
                    "file": fname,
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "sha256": digest,
                }
            )
        manifest = {
            "step": step,
            "leaves": entries,
            "treedef": str(structure),
            "meta": meta,
            "committed": True,
        }
        (tmp / "MANIFEST.json").write_text(json.dumps(manifest, indent=1))
        fd = os.open(tmp, os.O_RDONLY)
        try:
            os.fsync(fd)
        finally:
            os.close(fd)
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)
        self._gc()

    def _gc(self):
        steps = self.steps()
        for s in steps[: -self.keep] if self.keep else []:
            shutil.rmtree(self._step_dir(s), ignore_errors=True)

    # ------------------------------------------------------------------
    def restore(
        self,
        like: Any,
        step: int | None = None,
        shardings: Any = None,
        verify: bool = True,
    ) -> tuple[Any, dict]:
        """Restore into the structure of ``like`` (a pytree of arrays or
        ShapeDtypeStructs). ``shardings``: matching pytree of NamedShardings
        for elastic placement; None -> plain host arrays.
        """
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint under {self.root}")
        d = self._step_dir(step)
        manifest = json.loads((d / "MANIFEST.json").read_text())
        by_key = {e["key"]: e for e in manifest["leaves"]}

        flat_like = _tree_paths(like)
        flat_shard = (
            [v for _, v in _tree_paths(shardings)] if shardings is not None else [None] * len(flat_like)
        )
        out = []
        for (key, ref), shd in zip(flat_like, flat_shard):
            e = by_key.get(key)
            if e is None:
                raise KeyError(f"checkpoint {d} missing leaf {key}")
            raw = (d / e["file"]).read_bytes()
            if verify:
                digest = hashlib.sha256(raw).hexdigest()
                if digest != e["sha256"]:
                    raise IOError(f"integrity failure for {key} in {d}")
            arr = np.load(d / e["file"])
            want_shape = tuple(ref.shape)
            if tuple(arr.shape) != want_shape:
                raise ValueError(f"{key}: ckpt {arr.shape} vs expected {want_shape}")
            arr = arr.astype(ref.dtype) if str(arr.dtype) != str(ref.dtype) else arr
            out.append(jax.device_put(arr, shd) if shd is not None else arr)
        tree = jax.tree.unflatten(jax.tree.structure(like), out)
        return tree, manifest["meta"]
