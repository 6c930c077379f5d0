"""Sharded, integrity-checked checkpoints (no orbax dependency).

Format: one directory per step:
    step_000042/
      manifest.json     — tree structure, shapes, dtypes, per-leaf blake2b,
                          shard layout, framework metadata
      shard_<h>.bin     — zstd-compressed concatenation of this host's leaves

On a real multi-host cluster each host writes only the leaves (or leaf
slices) it owns (``host_id``/``n_hosts`` sharding of the leading axis when
``shard_leaves`` is on); here the single-process tests exercise the same
code path with n_hosts=1 and a simulated multi-host roundtrip.

Writes are atomic (tmp dir + rename) so a failure mid-save never corrupts
the latest complete checkpoint — the restart path of the fault-tolerance
drill relies on this.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

import zstandard

Pytree = Any

_MAGIC = "repro-imagine-ckpt-v1"


def _leaf_paths(tree: Pytree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp)
             for kp, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def save_checkpoint(
    directory: str,
    step: int,
    tree: Pytree,
    *,
    host_id: int = 0,
    n_hosts: int = 1,
    extra: Optional[Dict[str, Any]] = None,
) -> str:
    """Write checkpoint; returns final path.  Atomic per host."""
    paths, leaves, _ = _leaf_paths(tree)
    final = os.path.join(directory, f"step_{step:08d}")
    tmp = final + f".tmp-{host_id}"
    os.makedirs(tmp, exist_ok=True)

    manifest = {"magic": _MAGIC, "step": step, "n_hosts": n_hosts,
                "codec": "zstd",
                "extra": extra or {}, "leaves": []}
    cctx = zstandard.ZstdCompressor(level=3)
    blob = bytearray()
    for i, (p, leaf) in enumerate(zip(paths, leaves)):
        if i % n_hosts != host_id:
            owner = i % n_hosts
            manifest["leaves"].append({"path": p, "owner": owner})
            continue
        arr = np.asarray(leaf)
        raw = arr.tobytes()
        manifest["leaves"].append({
            "path": p,
            "owner": host_id,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "offset": len(blob),
            "nbytes": len(raw),
            "blake2b": hashlib.blake2b(raw, digest_size=16).hexdigest(),
        })
        blob.extend(raw)
    with open(os.path.join(tmp, f"shard_{host_id}.bin"), "wb") as f:
        f.write(cctx.compress(bytes(blob)))
    with open(os.path.join(tmp, f"manifest_{host_id}.json"), "w") as f:
        json.dump(manifest, f)

    # host 0 finalizes: merge per-host tmp dirs into the final directory
    if host_id == 0:
        os.makedirs(final, exist_ok=True)
        for h in range(n_hosts):
            hdir = final + f".tmp-{h}"
            if not os.path.isdir(hdir):
                continue
            for name in os.listdir(hdir):
                shutil.move(os.path.join(hdir, name), os.path.join(final, name))
            os.rmdir(hdir)
        # mark complete
        with open(os.path.join(final, "COMMITTED"), "w") as f:
            f.write(_MAGIC)
    return final


def load_checkpoint(
    directory: str,
    template: Pytree,
    step: Optional[int] = None,
) -> Tuple[Pytree, Dict[str, Any]]:
    """Restore into the structure of ``template`` (shapes must match)."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {directory}")
    final = os.path.join(directory, f"step_{step:08d}")
    if not os.path.exists(os.path.join(final, "COMMITTED")):
        raise FileNotFoundError(f"checkpoint {final} not committed")

    manifests = {}
    for name in os.listdir(final):
        if name.startswith("manifest_"):
            with open(os.path.join(final, name)) as f:
                m = json.load(f)
            assert m["magic"] == _MAGIC
            manifests[int(name.split("_")[1].split(".")[0])] = m

    paths, leaves, treedef = _leaf_paths(template)
    by_path: Dict[str, Tuple[int, dict]] = {}
    for h, m in manifests.items():
        for entry in m["leaves"]:
            if "offset" in entry:
                by_path[entry["path"]] = (h, entry)

    blobs = {}
    for h in manifests:
        codec = manifests[h].get("codec", "zstd")
        if codec != "zstd":
            raise ValueError(f"unknown checkpoint codec {codec!r}")
        dctx = zstandard.ZstdDecompressor()
        with open(os.path.join(final, f"shard_{h}.bin"), "rb") as f:
            blobs[h] = dctx.decompress(f.read())

    out = []
    for p, leaf in zip(paths, leaves):
        h, entry = by_path[p]
        raw = blobs[h][entry["offset"] : entry["offset"] + entry["nbytes"]]
        digest = hashlib.blake2b(raw, digest_size=16).hexdigest()
        if digest != entry["blake2b"]:
            raise IOError(f"checksum mismatch for {p} in step {step}")
        arr = np.frombuffer(raw, dtype=entry["dtype"]).reshape(entry["shape"])
        tmpl = np.asarray(leaf)
        if tuple(arr.shape) != tmpl.shape:
            raise ValueError(f"{p}: ckpt shape {arr.shape} != template {tmpl.shape}")
        out.append(arr.astype(tmpl.dtype) if str(tmpl.dtype) != entry["dtype"] else arr)
    extra = manifests[min(manifests)]["extra"]
    return jax.tree_util.tree_unflatten(treedef, out), extra


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith("step_") and not name.endswith(".tmp"):
            if os.path.exists(os.path.join(directory, name, "COMMITTED")):
                steps.append(int(name.split("_")[1]))
    return max(steps) if steps else None
