"""Sharded checkpointing with atomic manifests, async commit, and elastic
resharding (restore onto any mesh shape).

Layout:
  <dir>/step_<N>/
      manifest.json        # leaf paths, shapes, dtypes, step, wall time
      <leaf-key>.bin       # raw little-endian bytes per leaf
      COMMITTED            # written last — a step without it is incomplete

Fault-tolerance contract: `restore_latest` scans for the newest *committed*
step, so a crash mid-save can never be resumed from.  `save(async_commit=
True)` runs serialization on a worker thread — the training loop keeps
stepping while bytes land (the paper's "keep crossings off the critical
path", applied to checkpoint traffic).

Elastic resharding: leaves are stored unsharded; `restore_latest` places
them with whatever shardings the *current* params template carries, so a
checkpoint from a (16,16) mesh restores onto (2,16,16), (8,8) or a single
host without conversion (launch/elastic.py drives this).
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Optional

import jax
import jax.numpy as jnp
import ml_dtypes  # noqa: F401  (registers bfloat16 etc. with numpy)
import numpy as np

_PENDING: list[threading.Thread] = []


def _leaf_paths(tree) -> list[tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        out.append((key, leaf))
    return out


def _dtype_name(x) -> str:
    return str(x.dtype)


def save(ckpt_dir: str, params, opt_state, step: int, *,
         async_commit: bool = False) -> str:
    """Write a checkpoint; returns the step directory path."""
    state = {"params": params, "opt": opt_state}
    # snapshot to host (so donated/updated buffers can't race the writer)
    host = jax.tree.map(lambda x: np.asarray(x), state)

    def _write():
        d = os.path.join(ckpt_dir, f"step_{step}")
        tmp = d + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        manifest = {"step": step, "time": time.time(), "leaves": {}}
        for key, leaf in _leaf_paths(host):
            fname = key.replace("/", "__") + ".bin"
            arr = np.asarray(leaf)
            with open(os.path.join(tmp, fname), "wb") as f:
                f.write(arr.tobytes())
            manifest["leaves"][key] = {
                "file": fname, "shape": list(arr.shape), "dtype": _dtype_name(arr)}
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(d):
            shutil.rmtree(d)
        os.rename(tmp, d)
        with open(os.path.join(d, "COMMITTED"), "w") as f:
            f.write(str(step))
        return d

    if async_commit:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        _PENDING.append(t)
        return os.path.join(ckpt_dir, f"step_{step}")
    return _write()


def wait_for_pending() -> None:
    for t in _PENDING:
        t.join()
    _PENDING.clear()


def committed_steps(ckpt_dir: str) -> list[int]:
    if not os.path.isdir(ckpt_dir):
        return []
    steps = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(ckpt_dir, name, "COMMITTED")):
                steps.append(int(name.split("_")[1]))
    return sorted(steps)


def _load_tree(d: str, template, manifest, prefix: str):
    """Rebuild a pytree from stored leaves, placed per the template's sharding."""
    leaves_meta = manifest["leaves"]

    def place(key_leaf):
        key, leaf = key_leaf
        meta = leaves_meta[f"{prefix}/{key}" if key else prefix]
        with open(os.path.join(d, meta["file"]), "rb") as f:
            arr = np.frombuffer(f.read(), dtype=np.dtype(meta["dtype"])).reshape(meta["shape"])
        if hasattr(leaf, "sharding") and leaf.sharding is not None:
            try:
                return jax.device_put(arr, leaf.sharding)
            except Exception:
                pass
        return jnp.asarray(arr)

    keyed = _leaf_paths(template)
    placed = [place(kl) for kl in keyed]
    return jax.tree.unflatten(jax.tree.structure(template), placed)


def restore(ckpt_dir: str, step: int, params_template,
            opt_template: Optional[Any] = None):
    d = os.path.join(ckpt_dir, f"step_{step}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    params = _load_tree(d, params_template, manifest, "params")
    opt = None
    if opt_template is not None:
        opt = _load_tree(d, opt_template, manifest, "opt")
    else:
        # rebuild opt tree directly from the manifest (shape-driven)
        opt = _manifest_subtree(d, manifest, "opt")
    return params, opt, manifest["step"]


def _manifest_subtree(d: str, manifest, prefix: str):
    """Reconstruct a nested dict for all leaves under `prefix`."""
    root: dict = {}
    for key, meta in manifest["leaves"].items():
        if not key.startswith(prefix + "/") and key != prefix:
            continue
        with open(os.path.join(d, meta["file"]), "rb") as f:
            arr = np.frombuffer(f.read(), dtype=np.dtype(meta["dtype"])).reshape(meta["shape"])
        parts = key[len(prefix) + 1:].split("/") if key != prefix else []
        node = root
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        if parts:
            node[parts[-1]] = jnp.asarray(arr)
        else:
            return jnp.asarray(arr)
    return root


def restore_latest(ckpt_dir: str, params_template,
                   opt_template: Optional[Any] = None):
    steps = committed_steps(ckpt_dir)
    if not steps:
        return None
    return restore(ckpt_dir, steps[-1], params_template, opt_template)
