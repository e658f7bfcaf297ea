"""Restore side — checksum-verified shard reads and manifest resharding.

The core restore primitive is :func:`read_block`: give it a manifest
leaf entry and any index block of that leaf, and it reads exactly the
shard files whose saved spans overlap the block, verifies each against
its manifest crc32, and assembles the requested region. That one
function is what makes restore *layout-free*: a rank restoring into a
different process count or mesh never sees the save-time layout — it
asks for its new addressable blocks and the overlap math fetches the
right spans (the elastic grow/shrink gap called out in ISSUE.md: a
rejoined worker no longer has to swallow the full broadcast pytree).

Corruption surfaces as the typed :exc:`CorruptShardError` (missing
file, byte-count mismatch, crc mismatch, undecodable payload) — the
engine catches it and falls back to the previous committed step.
"""

from __future__ import annotations

import io
import os
import re
import zlib
from typing import Any, Dict, List, Optional

import numpy as np

from . import manifest as _manifest
from .layout import (Index, full_index, intersect_spans, relative_slices)


class CorruptShardError(RuntimeError):
    """A shard file failed integrity verification against the manifest."""

    def __init__(self, path: str, reason: str):
        super().__init__(f"corrupt checkpoint shard {path}: {reason}")
        self.path = path
        self.reason = reason


def load_shard(step_dir: str, shard_entry: dict) -> np.ndarray:
    """One shard file, crc32-verified against its manifest entry."""
    path = os.path.join(step_dir, shard_entry["file"])
    try:
        with open(path, "rb") as f:
            data = f.read()
    except FileNotFoundError:
        raise CorruptShardError(path, "shard file missing")
    if len(data) != int(shard_entry["nbytes"]):
        raise CorruptShardError(
            path, f"size {len(data)} != manifest {shard_entry['nbytes']}")
    crc = f"{zlib.crc32(data) & 0xFFFFFFFF:08x}"
    if crc != shard_entry["crc32"]:
        raise CorruptShardError(
            path, f"crc32 {crc} != manifest {shard_entry['crc32']}")
    try:
        return np.load(io.BytesIO(data), allow_pickle=False)
    except Exception as e:
        raise CorruptShardError(path, f"undecodable payload: {e}")


def shards_overlapping(leaf_entry: dict, block: Index) -> List[dict]:
    """Manifest shard entries whose saved spans intersect ``block`` —
    the exact file set a resharded restore of that block must read."""
    out = []
    for shard_entry in leaf_entry["shards"]:
        if intersect_spans(_manifest.parse_index(shard_entry["index"]),
                           block) is not None:
            out.append(shard_entry)
    return out


def read_block(step_dir: str, leaf_entry: dict,
               block: Optional[Index] = None) -> np.ndarray:
    """Assemble one index block of a leaf from overlapping shard files.

    ``block=None`` means the full leaf. Raises CorruptShardError on any
    bad shard, and ValueError if the saved shards do not cover the
    requested block (a manifest from an incompatible layout)."""
    shape = tuple(int(d) for d in leaf_entry["shape"])
    if block is None:
        block = full_index(shape)
    dtype = np.dtype(leaf_entry["dtype"])
    out = np.empty(tuple(b - a for a, b in block), dtype=dtype)
    covered = 0
    for shard_entry in leaf_entry["shards"]:
        src_index = _manifest.parse_index(shard_entry["index"])
        inter = intersect_spans(src_index, block) if block else src_index
        if block and inter is None:
            continue
        data = load_shard(step_dir, shard_entry)
        if data.dtype.kind == "V" and data.dtype.itemsize == dtype.itemsize:
            # np.save writes a dtype numpy does not know by name
            # (bfloat16 moments) as raw bytes of its width.
            data = data.view(dtype)
        if tuple(data.shape) != tuple(b - a for a, b in src_index):
            raise CorruptShardError(
                os.path.join(step_dir, shard_entry["file"]),
                f"shape {data.shape} != manifest span {src_index}")
        if not block:  # 0-d leaf: single full shard
            return data.astype(dtype, copy=False).reshape(())
        out[relative_slices(block, inter)] = \
            data[relative_slices(src_index, inter)]
        n = 1
        for a, b in inter:
            n *= b - a
        covered += n
    want = int(np.prod([b - a for a, b in block], dtype=np.int64)) \
        if block else 1
    if covered < want:
        raise ValueError(
            f"checkpoint shards cover {covered} of {want} elements of "
            f"{leaf_entry['key']!r} block {block} — incomplete layout")
    return out


def read_tree(step_dir: str, man: dict,
              template: Any = None) -> Any:
    """Full-leaf restore of every leaf, rebuilt into a pytree.

    With ``template``, leaves are matched by tree-path string and the
    result has the template's structure (works for any pytree —
    NamedTuple optax states included). Without one, the structure is
    rebuilt from the manifest keys, which works for trees of
    dicts/lists/tuples and raises a clear error otherwise.
    """
    import jax

    by_key: Dict[str, np.ndarray] = {}
    for leaf_entry in man["leaves"]:
        by_key[leaf_entry["key"]] = read_block(step_dir, leaf_entry)
    if template is not None:
        flat, treedef = jax.tree_util.tree_flatten_with_path(template)
        leaves = []
        for path, _ in flat:
            key = jax.tree_util.keystr(path)
            if key not in by_key:
                raise KeyError(
                    f"checkpoint has no leaf {key!r}; manifest holds "
                    f"{sorted(by_key)[:8]}...")
            leaves.append(by_key.pop(key))
        if by_key:
            raise KeyError(
                f"checkpoint leaves {sorted(by_key)} missing from the "
                "restore template")
        return jax.tree_util.tree_unflatten(treedef, leaves)
    return rebuild_tree(by_key)


_PART_RE = re.compile(r"\['([^']*)'\]|\[(\d+)\]")


def rebuild_tree(by_key: Dict[str, np.ndarray]) -> Any:
    """Rebuild nested dicts/lists from tree-path keys (templateless
    restore). Attribute paths (``.field`` — NamedTuples, custom nodes)
    need a template: the manifest records no class to rebuild."""
    root: Dict[Any, Any] = {}
    for key, value in by_key.items():
        parts = []
        pos = 0
        for m in _PART_RE.finditer(key):
            if m.start() != pos:
                raise ValueError(
                    f"cannot rebuild pytree node for leaf {key!r} "
                    "without a template (pass template= to restore — "
                    "required for NamedTuple/custom-node states)")
            parts.append(m.group(1) if m.group(1) is not None
                         else int(m.group(2)))
            pos = m.end()
        if pos != len(key) or not parts:
            raise ValueError(
                f"cannot rebuild pytree node for leaf {key!r} without "
                "a template (pass template= to restore)")
        node = root
        for part, nxt in zip(parts[:-1], parts[1:]):
            node = node.setdefault(part, {})
        node[parts[-1]] = value
    return _listify(root)


def _listify(node: Any) -> Any:
    """Integer-keyed dicts back into lists (list/tuple tree nodes round-
    trip as lists — tuple-ness is not recorded in the manifest)."""
    if not isinstance(node, dict):
        return node
    out = {k: _listify(v) for k, v in node.items()}
    if out and all(isinstance(k, int) for k in out):
        if sorted(out) == list(range(len(out))):
            return [out[i] for i in range(len(out))]
    return out
