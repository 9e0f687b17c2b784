"""Checkpointing: msgpack + zstd, async save, content hashes, elastic
reshard-on-restore.

Layout per checkpoint directory (``<dir>/step_<N>/``):

    manifest.msgpack   {step, keys: {path: {shape, dtype, bytes, sha256}},
                        tree_hash, meta}
    data.msgpack.zst   {path: raw bytes}

Fault-tolerance contract:
- ``save`` writes to ``step_<N>.tmp`` then atomically renames — a crash
  mid-save never corrupts the latest checkpoint.
- every tensor carries a sha256; ``restore`` verifies before use.
- ``restore`` takes optional shardings: tensors are placed shard-by-shard
  via ``jax.make_array_from_callback`` for whatever mesh the NEW job has —
  elastic rescale = restore with different shardings, no resave needed.
- ``keep`` bounds disk usage; old checkpoints are pruned after a
  successful save (never before).
"""

from __future__ import annotations

import hashlib
import os
import pathlib
import re
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

_SAVE_LOCK = threading.Lock()
_PENDING: List[threading.Thread] = []


def _flatten_with_paths(tree: Any) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        key = "/".join(_path_str(p) for p in path)
        out.append((key, leaf))
    return out


def _path_str(p) -> str:
    if hasattr(p, "key"):
        return str(p.key)
    if hasattr(p, "idx"):
        return str(p.idx)
    if hasattr(p, "name"):
        return str(p.name)
    return str(p)


def _compress(raw: bytes) -> bytes:
    return zstandard.ZstdCompressor(level=3).compress(raw)


def _decompress(raw: bytes) -> bytes:
    return zstandard.ZstdDecompressor().decompress(raw)


def _tree_def_hash(keys: List[str]) -> str:
    h = hashlib.sha256()
    for k in keys:
        h.update(k.encode())
    return h.hexdigest()[:16]


def save(
    ckpt_dir: str | os.PathLike,
    step: int,
    tree: Any,
    meta: Optional[Dict] = None,
    keep: int = 3,
    async_save: bool = True,
) -> threading.Thread | None:
    """Serialize ``tree`` (pytree of arrays) for ``step``. Returns the
    writer thread when async (join it or call wait_all())."""
    ckpt_dir = pathlib.Path(ckpt_dir)
    ckpt_dir.mkdir(parents=True, exist_ok=True)
    # snapshot to host memory synchronously (device buffers may mutate next step)
    flat = _flatten_with_paths(tree)
    host = [(k, np.asarray(jax.device_get(v))) for k, v in flat]

    def write():
        with _SAVE_LOCK:
            final = ckpt_dir / f"step_{step:010d}"
            tmp = ckpt_dir / f"step_{step:010d}.tmp"
            if tmp.exists():
                shutil.rmtree(tmp)
            tmp.mkdir(parents=True)
            manifest = {"step": step, "keys": {}, "meta": meta or {}}
            blobs = {}
            for k, arr in host:
                raw = arr.tobytes()
                manifest["keys"][k] = {
                    "shape": list(arr.shape),
                    "dtype": str(arr.dtype),
                    "bytes": len(raw),
                    "sha256": hashlib.sha256(raw).hexdigest(),
                }
                blobs[k] = raw
            manifest["tree_hash"] = _tree_def_hash(sorted(blobs))
            with open(tmp / "data.msgpack.zst", "wb") as f:
                f.write(_compress(msgpack.packb(blobs, use_bin_type=True)))
            with open(tmp / "manifest.msgpack", "wb") as f:
                f.write(msgpack.packb(manifest, use_bin_type=True))
            if final.exists():
                shutil.rmtree(final)
            tmp.rename(final)
            _prune(ckpt_dir, keep)

    if async_save:
        t = threading.Thread(target=write, daemon=True)
        t.start()
        _PENDING.append(t)
        return t
    write()
    return None


def wait_all() -> None:
    for t in list(_PENDING):
        t.join()
        _PENDING.remove(t)


def _prune(ckpt_dir: pathlib.Path, keep: int) -> None:
    steps = sorted(all_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(ckpt_dir / f"step_{s:010d}", ignore_errors=True)


def all_steps(ckpt_dir: str | os.PathLike) -> List[int]:
    ckpt_dir = pathlib.Path(ckpt_dir)
    out = []
    if not ckpt_dir.exists():
        return out
    for p in ckpt_dir.iterdir():
        m = re.fullmatch(r"step_(\d+)", p.name)
        if m and p.is_dir():
            out.append(int(m.group(1)))
    return sorted(out)


def latest_step(ckpt_dir: str | os.PathLike) -> Optional[int]:
    steps = all_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(
    ckpt_dir: str | os.PathLike,
    step: Optional[int] = None,
    target: Any = None,
    shardings: Any = None,
) -> Tuple[int, Any]:
    """Load a checkpoint. With ``target`` (a pytree of like-structured
    arrays/ShapeDtypeStructs) the tree structure is rebuilt; with
    ``shardings`` each tensor is placed for the CURRENT mesh (elastic
    reshard-on-restore). Returns (step, tree)."""
    ckpt_dir = pathlib.Path(ckpt_dir)
    if step is None:
        step = latest_step(ckpt_dir)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {ckpt_dir}")
    d = ckpt_dir / f"step_{step:010d}"
    with open(d / "manifest.msgpack", "rb") as f:
        manifest = msgpack.unpackb(f.read(), raw=False)
    with open(d / "data.msgpack.zst", "rb") as f:
        blobs = msgpack.unpackb(_decompress(f.read()), raw=False)

    arrays: Dict[str, np.ndarray] = {}
    for k, info in manifest["keys"].items():
        raw = blobs[k]
        if hashlib.sha256(raw).hexdigest() != info["sha256"]:
            raise IOError(f"checkpoint corruption: sha256 mismatch for {k}")
        arrays[k] = np.frombuffer(raw, dtype=np.dtype(info["dtype"])).reshape(
            info["shape"]
        )

    if target is None:
        return step, arrays

    flat = _flatten_with_paths(target)
    sh_flat = _flatten_with_paths(shardings) if shardings is not None else None
    leaves = []
    for i, (k, tgt) in enumerate(flat):
        if k not in arrays:
            raise KeyError(f"checkpoint missing tensor {k}")
        arr = arrays[k]
        want_dtype = np.dtype(
            tgt.dtype if hasattr(tgt, "dtype") else np.float32
        )
        if arr.dtype != want_dtype:
            arr = arr.astype(want_dtype)
        if sh_flat is not None:
            sh = sh_flat[i][1]
            leaves.append(
                jax.make_array_from_callback(arr.shape, sh, lambda idx, a=arr: a[idx])
            )
        else:
            leaves.append(jnp.asarray(arr))
    treedef = jax.tree_util.tree_structure(target)
    return step, jax.tree_util.tree_unflatten(treedef, leaves)
