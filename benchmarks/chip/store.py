"""The index store: a deployment builds its WISK index once and serves it.

Building takes minutes on the chip, so a cell's first run in a checkout
builds the index and stores it, and every later run loads it. The layout
is fully given by the collection (made again from the configuration's
``data_seed``), the bottom clusters' assignment and the packed hierarchy's
parent slots, so that is all a store entry holds: a few MB of ``.npz``.

An entry's key is the part of the configuration file that decides the
build (``BUILD_KEYS``: the collection, its seed, the training workload and
the build settings) plus a hash of every file under ``src/repro``, so a
change to either builds again, while a change to how the index is served
does not. Entries are
written to a temporary file in the same directory and renamed into place,
so a killed first run leaves no half-written entry. The directory is
``benchmarks/chip/.store`` (git-ignored).
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path
from typing import Dict, List, Optional, Tuple

import numpy as np

STORE_DIR = Path(__file__).resolve().parent / ".store"
BUILD_KEYS = ("data", "data_seed", "train_workload", "build")


def tree_hash(root: Path) -> str:
    """sha256 over every file's relative path and bytes under ``root``."""
    h = hashlib.sha256()
    for p in sorted(root.rglob("*")):
        if p.is_file() and "__pycache__" not in p.parts:
            h.update(str(p.relative_to(root)).encode())
            h.update(p.read_bytes())
    return h.hexdigest()


def entry_key(config: Dict, program_hash: str) -> str:
    build = json.dumps({k: config[k] for k in BUILD_KEYS}, sort_keys=True)
    return hashlib.sha256((build + program_hash).encode()).hexdigest()[:24]


def load(path: Path) -> Optional[Tuple[np.ndarray, List[np.ndarray], Dict]]:
    """``(assign, parents, info)`` of a stored entry, or None."""
    if not path.is_file():
        return None
    with np.load(path, allow_pickle=False) as z:
        n_levels = int(z["n_parent_levels"])
        info = json.loads(str(z["info"]))
        return z["assign"], [z[f"parents_{i}"] for i in range(n_levels)], info


def save(path: Path, assign: np.ndarray, parents: List[np.ndarray], info: Dict) -> None:
    """Write an entry atomically (temporary file, fsync, rename)."""
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_name(f".{path.name}.{os.getpid()}.tmp")
    arrays = {f"parents_{i}": np.asarray(p, np.int32) for i, p in enumerate(parents)}
    with open(tmp, "wb") as f:
        np.savez(
            f, assign=np.asarray(assign, np.int32), n_parent_levels=np.int64(len(parents)),
            info=np.asarray(json.dumps(info)), **arrays,
        )
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
