"""Sharded, atomic, async checkpointing with reshard-on-restore.

Design (DESIGN.md §4 fault tolerance):

* **Layout**: one ``.npy`` per pytree leaf + a JSON manifest (tree structure,
  shapes, dtypes, step, mesh axes and PartitionSpecs at save time). On a real
  multi-host pod each host writes only the shards it owns; on this container
  the addressable shard set is the whole array — same code path.
* **Atomicity**: everything lands in ``<dir>/.tmp-<step>``; the final
  ``rename`` to ``step_<n>`` is the commit point. A crash mid-write leaves
  only a tmp dir that the next writer garbage-collects; ``latest`` never
  points at a torn checkpoint.
* **Async**: ``save_async`` snapshots to host memory synchronously (cheap)
  and writes on a background thread — the train loop continues. ``wait()``
  joins before the next save (single writer).
* **Reshard-on-restore**: ``restore`` takes the *current* mesh + specs; the
  loader re-shards every leaf via device_put, so a checkpoint taken on a
  (16,16) mesh restores onto (2,16,16) or a shrunk elastic mesh unchanged.
"""
from __future__ import annotations

import json
import shutil
import threading
from pathlib import Path
from typing import Any, Optional

import jax
import numpy as np

PyTree = Any
_SEP = "/"


def _flatten(tree: PyTree) -> dict[str, Any]:
    flat = {}

    def walk(path, node):
        if isinstance(node, dict):
            for k, v in node.items():
                walk(path + [str(k)], v)
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(path + [str(i)], v)
        else:
            flat[_SEP.join(path)] = node

    walk([], tree)
    return flat


def _unflatten_into(skeleton: PyTree, flat: dict[str, Any]) -> PyTree:
    def walk(path, node):
        if isinstance(node, dict):
            return {k: walk(path + [str(k)], v) for k, v in node.items()}
        if isinstance(node, list):
            return [walk(path + [str(i)], v) for i, v in enumerate(node)]
        if isinstance(node, tuple):
            return tuple(walk(path + [str(i)], v) for i, v in enumerate(node))
        return flat[_SEP.join(path)]

    return walk([], skeleton)


class CheckpointStore:
    """Directory of step_<n> checkpoints with a single async writer."""

    def __init__(self, directory: str | Path, *, keep: int = 3):
        self.dir = Path(directory)
        self.dir.mkdir(parents=True, exist_ok=True)
        self.keep = keep
        self._thread: Optional[threading.Thread] = None

    # ------------------------------------------------------------------ save
    def save(self, step: int, tree: PyTree, *, extra: Optional[dict] = None) -> Path:
        self.wait()
        return self._write(step, _to_host(_flatten(tree)), extra or {})

    def save_async(self, step: int, tree: PyTree, *, extra: Optional[dict] = None) -> None:
        self.wait()
        host_flat = _to_host(_flatten(tree))  # snapshot before returning

        def run():
            self._write(step, host_flat, extra or {})

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None

    def _write(self, step: int, host_flat: dict[str, np.ndarray], extra: dict) -> Path:
        for stale in self.dir.glob(".tmp-*"):
            shutil.rmtree(stale, ignore_errors=True)  # GC torn writes
        tmp = self.dir / f".tmp-{step}"
        tmp.mkdir(parents=True)
        manifest = {"step": step, "extra": extra, "leaves": {}}
        for i, (key, arr) in enumerate(sorted(host_flat.items())):
            fname = f"leaf_{i:05d}.npy"
            np.save(tmp / fname, arr)
            manifest["leaves"][key] = {
                "file": fname, "shape": list(arr.shape), "dtype": str(arr.dtype)}
        (tmp / "manifest.json").write_text(json.dumps(manifest))
        final = self.dir / f"step_{step:08d}"
        if final.exists():
            shutil.rmtree(final)
        tmp.rename(final)  # commit point
        self._gc()
        return final

    def _gc(self) -> None:
        steps = sorted(self.all_steps())
        for s in steps[: -self.keep]:
            shutil.rmtree(self.dir / f"step_{s:08d}", ignore_errors=True)

    # --------------------------------------------------------------- restore
    def all_steps(self) -> list[int]:
        return sorted(int(p.name.split("_")[1]) for p in self.dir.glob("step_*"))

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def leaf_keys(self, step: Optional[int] = None) -> set[str]:
        """Flat key set of a saved checkpoint (no leaf data loaded) — lets a
        caller trim optional template keys (e.g. §16 shield carry) before
        ``restore`` when resuming from a checkpoint that predates them."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.dir}")
        d = self.dir / f"step_{step:08d}"
        manifest = json.loads((d / "manifest.json").read_text())
        return set(manifest["leaves"])

    def restore(self, skeleton: PyTree, *, step: Optional[int] = None,
                shardings: Optional[PyTree] = None,
                host: bool = False) -> tuple[PyTree, int, dict]:
        """Load into the structure of ``skeleton``; if ``shardings`` (a pytree
        of NamedSharding matching skeleton) is given, every leaf is placed
        with it — this is the elastic reshard-on-restore path.

        ``host=True`` returns raw numpy leaves exactly as saved. The default
        device path goes through ``jnp.asarray``, which under x64-off
        silently truncates float64/int64 leaves (simulator clocks, RNG
        words, bin hit counts) — callers restoring host-side state that must
        round-trip bitwise (the serve controller) use the host path and
        device_put only the leaves that belong on device."""
        step = step if step is not None else self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {self.dir}")
        d = self.dir / f"step_{step:08d}"
        manifest = json.loads((d / "manifest.json").read_text())
        flat_shard = _flatten(shardings) if shardings is not None else {}
        flat = {}
        for key, info in manifest["leaves"].items():
            arr = np.load(d / info["file"])
            sh = flat_shard.get(key)
            if sh is not None:
                flat[key] = jax.device_put(arr, sh)
            elif host:
                flat[key] = arr
            else:
                flat[key] = jax.numpy.asarray(arr)
        tree = _unflatten_into(skeleton, flat)
        return tree, manifest["step"], manifest.get("extra", {})


def _to_host(flat: dict[str, Any]) -> dict[str, np.ndarray]:
    return {k: np.asarray(v) for k, v in flat.items()}
