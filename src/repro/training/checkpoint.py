"""Checkpoint save/restore for train state (fault tolerance substrate).

Sharded-friendly: each leaf is pulled to host as numpy and written into a
single .npz per step with a flattened key path; restore rebuilds the exact
pytree (using a template for structure) and can re-shard onto a *different*
mesh — this is what the elastic runtime uses for shrink/expand and what the
scheduler's preempt/resume relies on.

A lightweight manifest (latest.txt) gives atomic "latest checkpoint"
semantics: write npz -> fsync -> update manifest.

Spans (``repro.telemetry``): a save records ``ckpt.device_get`` (the
state to host memory), ``ckpt.write`` (``np.savez``) and ``ckpt.fsync``
(the file's, then the manifest's); a restore records ``ckpt.load`` (the
file to host memory) and ``ckpt.place`` (the arrays handed to their
devices, not awaited).  Each counts bytes.
"""
from __future__ import annotations

import io
import os
import tempfile
from typing import Any, Optional

import jax
import numpy as np

from repro import telemetry


def _flatten(tree) -> dict:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(p) for p in path)
        arr = np.asarray(jax.device_get(leaf))
        if arr.dtype.name == "bfloat16":  # npz cannot round-trip bf16
            flat[key + ".bf16"] = arr.view(np.uint16)
        else:
            flat[key] = arr
    return flat


def step_file(path: str, step: int) -> str:
    """The file that holds step ``step``'s checkpoint under ``path``."""
    return os.path.join(path, f"step_{step:08d}.npz")


def save(path: str, step: int, tree: Any) -> str:
    """Write `tree` to <path>/step_<n>.npz atomically; returns file path."""
    os.makedirs(path, exist_ok=True)
    fname = step_file(path, step)
    with telemetry.span("ckpt.device_get") as sp:
        flat = _flatten(tree)
        sp.n = sum(a.nbytes for a in flat.values())
    with tempfile.NamedTemporaryFile(dir=path, delete=False) as tmp:
        with telemetry.span("ckpt.write", n=sp.n):
            np.savez(tmp, **flat)
        with telemetry.span("ckpt.fsync"):
            tmp.flush()
            os.fsync(tmp.fileno())
        tmpname = tmp.name
    os.replace(tmpname, fname)
    manifest = os.path.join(path, "latest.txt")
    with telemetry.span("ckpt.fsync"), \
            tempfile.NamedTemporaryFile("w", dir=path, delete=False) as tmp:
        tmp.write(f"{step}\n{fname}\n")
        tmp.flush()
        os.fsync(tmp.fileno())
        tmpname = tmp.name
    os.replace(tmpname, manifest)
    return fname


def latest_step(path: str) -> Optional[int]:
    manifest = os.path.join(path, "latest.txt")
    if not os.path.exists(manifest):
        return None
    with open(manifest) as f:
        return int(f.readline().strip())


def restore(path: str, template: Any, step: Optional[int] = None,
            shardings: Any = None) -> Any:
    """Rebuild the pytree of `template`'s structure from the checkpoint.

    With `shardings` (a matching pytree of NamedSharding), leaves are placed
    directly onto the (possibly different) mesh — elastic re-sharding.
    """
    if step is None:
        step = latest_step(path)
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {path}")
    leaves_p, treedef = jax.tree_util.tree_flatten_with_path(template)
    shard_leaves = (jax.tree_util.tree_leaves(shardings)
                    if shardings is not None else [None] * len(leaves_p))
    import ml_dtypes
    arrs = []
    with telemetry.span("ckpt.load") as sp, \
            np.load(step_file(path, step)) as data:
        for pth, _ in leaves_p:
            key = "/".join(str(p) for p in pth)
            if key + ".bf16" in data:
                arrs.append(np.asarray(data[key + ".bf16"]).view(
                    ml_dtypes.bfloat16))
            else:
                arrs.append(np.asarray(data[key]))
        sp.n = sum(a.nbytes for a in arrs)
    with telemetry.span("ckpt.place", n=sp.n):
        out = [jax.device_put(arr, sh) if sh is not None
               else jax.numpy.asarray(arr, dtype=leaf.dtype)
               for arr, (_, leaf), sh in zip(arrs, leaves_p, shard_leaves)]
    return jax.tree_util.tree_unflatten(treedef, out)
