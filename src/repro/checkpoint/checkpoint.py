"""Distributed checkpointing: atomic, retained, async, elastic.

Design (mirrors production Orbax/tensorstore semantics at npz scale):

* **Atomicity** — writes go to ``step_<N>.tmp/`` and are renamed to
  ``step_<N>/`` only after every file and the manifest are fsync'd; a crash
  mid-write can never corrupt the latest checkpoint.
* **Manifest** — tree structure, leaf dtypes/shapes, mesh shape, data-loader
  state and a payload checksum are stored in ``manifest.json``; restore
  validates structure before touching the model.
* **Retention** — keep the last ``keep`` checkpoints (and optionally every
  k-th for archival).
* **Async** — ``save_async`` snapshots device arrays to host, then writes in
  a background thread: the training loop resumes after the device->host
  copy (the same overlap discipline the paper uses to hide memory traffic).
* **Elasticity** — arrays are stored unsharded (gathered); ``restore``
  re-shards onto whatever mesh the new process runs (device count may
  differ — node failures shrink the pool).  See ``elastic.py`` for the
  policy layer.
* **Torn-write recovery** — a crash mid-``save`` leaves an orphaned
  ``step_<N>.tmp/`` (never a corrupt published step: the rename is the
  commit point).  ``sweep_orphans`` deletes those at restore time, and
  ``steps()`` only counts *valid* checkpoints (readable manifest + payload
  present), so ``restore()`` transparently falls back to the latest intact
  step even if the newest directory was damaged on disk after publish.

Serving-state layout (``SensorFleetEngine.save``/``.restore``): the fleet
engine checkpoints through this module as one pytree —

* ``qh`` / ``qc`` — the full ``(L, slots, H)`` int32 recurrent carry
  (gathered to host, so a restore can re-shard it onto any D′-device mesh
  via the slot→device block-partition invariant);
* ``streams/<slot>/qxs`` — each in-flight stream's quantised input,
  ``streams/<slot>/h_seq`` — its emitted top-layer outputs so far, plus
  optional ``qh0``/``qc0``;

with the JSON side-car (``manifest.json``'s ``extra``) recording the slot
table (``slot -> rid, cursor``), engine geometry (``L``, ``n_in``, ``H``,
``batch_slots``, ``chunk``, fxp format, backend), serving counters, and a
sha256 over the quantised parameters so a restore refuses to resume a
stream fleet onto different weights (that would silently break the
integer-identical-continuation contract).

Multi-host note: in a real multi-controller job each host writes only its
addressable shards (``jax.experimental.multihost_utils``); on this
single-process container host 0 owns everything, and the layout is
identical.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import shutil
import threading
import time
from pathlib import Path
from typing import Any

import jax
import numpy as np

from repro.obs.metrics import get_registry as _metrics

__all__ = ["CheckpointManager", "save_pytree", "restore_pytree"]


def _flatten_with_names(tree: Any):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names, leaves = [], []
    for path, leaf in flat:
        parts = []
        for p in path:
            if hasattr(p, "key"):        # DictKey
                parts.append(str(p.key))
            elif hasattr(p, "name"):     # GetAttrKey (dataclasses)
                parts.append(str(p.name))
            elif hasattr(p, "idx"):      # SequenceKey
                parts.append(str(p.idx))
            else:
                parts.append(str(p).strip("."))
        names.append("/".join(parts))
        leaves.append(leaf)
    return names, leaves, treedef


def save_pytree(tree: Any, directory: Path, extra: dict | None = None):
    """Atomic checkpoint write (synchronous)."""
    m = _metrics()
    with m.time("ckpt/save_us"):
        directory = Path(directory)
        tmp = directory.with_suffix(".tmp")
        if tmp.exists():
            shutil.rmtree(tmp)
        tmp.mkdir(parents=True)

        names, leaves, _ = _flatten_with_names(tree)
        arrays = {}
        checksum = hashlib.sha256()
        for name, leaf in zip(names, leaves):
            arr = np.asarray(jax.device_get(leaf))
            arrays[name] = arr
            checksum.update(name.encode())
            checksum.update(arr.tobytes()[:4096])  # prefix checksum: cheap + catches truncation
        np.savez(tmp / "arrays.npz", **{n.replace("/", "%"): a for n, a in arrays.items()})

        manifest = {
            "leaves": {n: {"shape": list(arrays[n].shape), "dtype": str(arrays[n].dtype)}
                       for n in names},
            "checksum": checksum.hexdigest(),
            "time": time.time(),
            "extra": extra or {},
        }
        (tmp / "manifest.json").write_text(json.dumps(manifest, indent=1))
        if directory.exists():
            shutil.rmtree(directory)
        tmp.rename(directory)  # atomic publish
    m.inc("ckpt/saves_total")
    if m.enabled:
        m.inc("ckpt/payload_bytes", sum(a.nbytes for a in arrays.values()))


def restore_pytree(template: Any, directory: Path, shardings: Any = None) -> Any:
    """Restore into ``template``'s structure; re-shard onto ``shardings``
    (elastic restore: the mesh may differ from the one that saved)."""
    m = _metrics()
    with m.time("ckpt/restore_us"):
        tree = _restore_pytree_inner(template, directory, shardings)
    m.inc("ckpt/restores_total")
    return tree


def _restore_pytree_inner(template, directory, shardings):
    directory = Path(directory)
    manifest = json.loads((directory / "manifest.json").read_text())
    data = np.load(directory / "arrays.npz")
    names, leaves, treedef = _flatten_with_names(template)

    out = []
    shard_leaves = (jax.tree.leaves(shardings, is_leaf=lambda x: x is None)
                    if shardings is not None else [None] * len(leaves))
    if len(shard_leaves) != len(leaves):
        shard_leaves = [None] * len(leaves)
    checksum = hashlib.sha256()
    for name, leaf, sh in zip(names, leaves, shard_leaves):
        key = name.replace("/", "%")
        if key not in data:
            raise KeyError(f"checkpoint missing leaf {name}")
        arr = data[key]
        want = manifest["leaves"][name]
        if list(arr.shape) != want["shape"]:
            raise ValueError(f"manifest/payload mismatch at {name}")
        checksum.update(name.encode())
        checksum.update(arr.tobytes()[:4096])
        if sh is not None:
            out.append(jax.device_put(arr, sh))
        else:
            out.append(jax.device_put(arr))
    # full-tree restores re-verify the payload prefix checksum (bit rot /
    # truncation after publish); partial-template restores can't — their
    # leaf order wouldn't reproduce the manifest's digest
    if len(names) == len(manifest["leaves"]) \
            and checksum.hexdigest() != manifest["checksum"]:
        raise ValueError(f"payload checksum mismatch under {directory}")
    return jax.tree.unflatten(treedef, out)


@dataclasses.dataclass
class CheckpointManager:
    root: Path
    keep: int = 3

    def __post_init__(self):
        self.root = Path(self.root)
        self.root.mkdir(parents=True, exist_ok=True)
        self._thread: threading.Thread | None = None

    # -- discovery -----------------------------------------------------------

    def _is_valid(self, d: Path) -> bool:
        """A published step dir with a readable manifest and its payload —
        anything else (torn tmp, post-publish disk damage) must not be
        offered as the latest checkpoint."""
        try:
            json.loads((d / "manifest.json").read_text())
        except (OSError, ValueError):
            return False
        return (d / "arrays.npz").exists()

    def steps(self) -> list[int]:
        out = []
        for d in self.root.glob("step_*"):
            if d.is_dir() and not d.name.endswith(".tmp") and self._is_valid(d):
                try:
                    out.append(int(d.name.split("_")[1]))
                except ValueError:
                    pass
        return sorted(out)

    def latest_step(self) -> int | None:
        s = self.steps()
        return s[-1] if s else None

    def sweep_orphans(self) -> list[str]:
        """Delete ``step_<N>.tmp/`` dirs left by a crash mid-``save`` (the
        torn-write state: payload partially written, never renamed).  Called
        automatically before ``restore``; safe because ``wait()`` ensures no
        in-process async write is mid-flight."""
        swept = []
        for d in self.root.glob("step_*.tmp"):
            if d.is_dir():
                shutil.rmtree(d, ignore_errors=True)
                swept.append(d.name)
        if swept:
            _metrics().inc("ckpt/torn_sweeps_total", len(swept))
        return swept

    def manifest(self, step: int) -> dict:
        """The parsed ``manifest.json`` of one published step."""
        return json.loads((self.root / f"step_{step}" / "manifest.json").read_text())

    # -- save/restore ---------------------------------------------------------

    def save(self, step: int, tree: Any, extra: dict | None = None):
        save_pytree(tree, self.root / f"step_{step}", extra=extra)
        self._retain()

    def save_async(self, step: int, tree: Any, extra: dict | None = None):
        """Snapshot to host now, write in the background."""
        self.wait()  # one in flight at a time
        # a copy, also of leaves already on the host: the caller goes on
        # mutating its buffers (a serving fleet's h_seq) during the write
        host_tree = jax.tree.map(lambda x: np.array(jax.device_get(x)), tree)

        def work():
            save_pytree(host_tree, self.root / f"step_{step}", extra=extra)
            self._retain()

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def restore(self, template: Any, step: int | None = None, shardings: Any = None):
        self.wait()
        self.sweep_orphans()
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.root}")
        tree = restore_pytree(template, self.root / f"step_{step}", shardings)
        extra = json.loads((self.root / f"step_{step}" / "manifest.json").read_text())["extra"]
        return tree, extra, step

    def _retain(self):
        steps = self.steps()
        for s in steps[: max(0, len(steps) - self.keep)]:
            shutil.rmtree(self.root / f"step_{s}", ignore_errors=True)
