"""Checkpoint serialization: nested dict of arrays <-> one msgpack file.

Self-contained (no orbax offline): dtype-faithful (bfloat16 via ml_dtypes
raw bytes), atomic (tmp + os.replace), zstd-compressed by default.
Restore returns host numpy arrays, so a checkpoint written under one mesh
can be re-placed under any other - this is the elasticity primitive.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import zlib

import jax
import ml_dtypes  # ships with jax
import msgpack
import numpy as np
import zstandard

from repro.quant.qtensor import QTensor
from repro.sparse.prune import PackedRows

_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
}

# Quantized leaves serialize as two sibling arrays under reserved names
# (the dunders cannot collide with real param keys), so a calibrated+
# quantized base is written once in int8/fp8 and `load_tree` reassembles
# the QTensors - a cold restore never takes an fp32 detour.
_QT_VALUES, _QT_SCALES = "__qvalues__", "__qscales__"

# Packed sparse-adapter leaves (repro.sparse.PackedRows) likewise: the
# layer bitmask, the kept rows, and the identity fill value serialize as
# sibling arrays, so a pruned tenant's registry snapshot stores only its
# active rows and restores as the same packed object - the on-disk form
# IS the 2-3x-smaller one.
_SP_MASK, _SP_ROWS, _SP_FILL = "__spmask__", "__sprows__", "__spfill__"


def _np_dtype(name: str):
    return _DTYPES.get(name, np.dtype(name))


def _flatten(tree, prefix="") -> Dict[str, np.ndarray]:
    out = {}
    if isinstance(tree, QTensor):
        out[f"{prefix}{_QT_VALUES}"] = np.asarray(tree.values)
        out[f"{prefix}{_QT_SCALES}"] = np.asarray(tree.scales)
    elif isinstance(tree, PackedRows):
        out[f"{prefix}{_SP_MASK}"] = np.asarray(tree.mask)
        out[f"{prefix}{_SP_ROWS}"] = np.asarray(tree.rows)
        out[f"{prefix}{_SP_FILL}"] = np.asarray(tree.fill, np.float32)
    elif isinstance(tree, dict):
        for k, v in tree.items():
            out.update(_flatten(v, f"{prefix}{k}/"))
    elif tree is None:
        pass
    else:
        out[prefix[:-1]] = np.asarray(tree)
    return out


def _unflatten(flat: Dict[str, np.ndarray]):
    root: dict = {}
    for path, v in flat.items():
        node = root
        parts = path.split("/")
        for p in parts[:-1]:
            node = node.setdefault(p, {})
        node[parts[-1]] = v

    def reassemble(node):
        if not isinstance(node, dict):
            return node
        if set(node) == {_QT_VALUES, _QT_SCALES}:
            return QTensor(node[_QT_VALUES], node[_QT_SCALES])
        if set(node) == {_SP_MASK, _SP_ROWS, _SP_FILL}:
            return PackedRows(node[_SP_MASK], node[_SP_ROWS],
                              float(node[_SP_FILL]))
        return {k: reassemble(v) for k, v in node.items()}

    return reassemble(root)


def save_tree(path: str, tree, *, compress: bool = True,
              metadata: Optional[dict] = None):
    flat = _flatten(jax.device_get(tree))
    payload = {
        "meta": metadata or {},
        "arrays": {
            k: {"dtype": str(v.dtype), "shape": list(v.shape),
                "data": v.tobytes()}
            for k, v in flat.items()
        },
    }
    raw = msgpack.packb(payload, use_bin_type=True)
    if compress:
        # write_checksum: zstd only validates frames that carry one, and
        # the integrity check is what lets load_tree reject bit flips
        # instead of deserializing corrupted numbers
        raw = b"ZSTD" + zstandard.ZstdCompressor(
            level=3, write_checksum=True).compress(raw)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(raw)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)  # atomic publish


def load_tree(path: str):
    """Load a snapshot. Any corruption - truncated file, flipped bytes,
    bad compression stream, or array bytes that do not match their
    declared dtype*shape - raises ValueError naming the file, so callers
    (restore/resume, adapter registries) distinguish 'unreadable snapshot'
    from programming errors and can fall back to an older version."""
    with open(path, "rb") as f:
        raw = f.read()
    try:
        if raw[:4] == b"ZSTD":
            raw = zstandard.ZstdDecompressor().decompress(raw[4:])
        elif raw[:4] == b"ZLIB":  # written by older versions
            raw = zlib.decompress(raw[4:])
        payload = msgpack.unpackb(raw, raw=False)
        if not isinstance(payload, dict) or "arrays" not in payload \
                or "meta" not in payload:
            raise ValueError("payload is not a snapshot envelope")
        flat = {}
        for k, spec in payload["arrays"].items():
            arr = np.frombuffer(spec["data"], dtype=_np_dtype(spec["dtype"]))
            flat[k] = arr.reshape(spec["shape"])
    except Exception as e:
        raise ValueError(f"corrupt checkpoint {path}: {e!r}") from e
    return _unflatten(flat), payload["meta"]
