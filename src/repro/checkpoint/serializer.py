"""Pytree <-> bytes serialization (msgpack framing + raw numpy buffers).

No external checkpoint libs: arrays are flattened to (dtype, shape, bytes)
triples keyed by their tree path, so checkpoints are portable across
processes and restartable onto different meshes (the loader re-shards).
"""
from __future__ import annotations

import io
import time
from typing import Any, Dict, List, Tuple, Union

import jax
import jax.numpy as jnp
import ml_dtypes
import msgpack
import numpy as np

from repro import spans

# numpy can't construct extension dtypes from their .str; map them by name.
_EXTENSION_DTYPES = {
    "bfloat16": ml_dtypes.bfloat16,
    "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
    "float8_e5m2": ml_dtypes.float8_e5m2,
}


# What a blob may arrive as: ``bytes`` from a serializer or a file, a
# ``memoryview`` of a received frame's buffer (the live transport's
# payloads).  Decoding reads it in place through the buffer protocol.
BytesLike = Union[bytes, bytearray, memoryview]


class DeserializationError(ValueError):
    """The blob itself is unreadable — truncated, bit-flipped, or not a
    checkpoint at all.  Distinct from a *valid* blob that mismatches the
    ``like`` template (missing leaf -> KeyError, shape drift ->
    ValueError): those mean the wrong checkpoint for this model, this
    means corruption — §4.3 restore paths and the live driver's
    corrupt-frame handling catch it and fall back / re-request."""


def _dtype_name(dtype: np.dtype) -> str:
    return dtype.name


def _dtype_from_name(name: str) -> np.dtype:
    if name in _EXTENSION_DTYPES:
        return np.dtype(_EXTENSION_DTYPES[name])
    return np.dtype(name)


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def serialize_pytree(tree: Any) -> bytes:
    """Pack a pytree of arrays into one self-describing byte blob.

    Each leaf's host array is dropped once its bytes are taken, before
    the next leaf is copied.  Counters: ``d2h_s``/``d2h_bytes`` (device leaves'
    ``np.asarray``), ``pack_s`` (``tobytes`` and ``packb``)."""
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    entries = []
    d2h_s = pack_s = 0.0
    d2h_bytes = 0
    with spans.span("fl.serialize") as sp:
        for path, leaf in leaves_with_paths:
            t0 = time.perf_counter()
            arr = np.asarray(leaf)
            t1 = time.perf_counter()
            if isinstance(leaf, jax.Array):
                d2h_s += t1 - t0
                d2h_bytes += arr.nbytes
            entries.append(
                {
                    "path": _path_str(path),
                    "dtype": _dtype_name(arr.dtype),
                    "shape": list(arr.shape),
                    "data": arr.tobytes(),
                }
            )
            pack_s += time.perf_counter() - t1
        t0 = time.perf_counter()
        blob: bytes = msgpack.packb({"version": 1, "entries": entries}, use_bin_type=True)
        pack_s += time.perf_counter() - t0
        sp.nbytes = len(blob)
    spans.add("d2h_s", d2h_s)
    spans.add("d2h_bytes", d2h_bytes)
    spans.add("pack_s", pack_s)
    return blob


def deserialize_pytree(blob: BytesLike, like: Any) -> Any:
    """Restore into the structure of `like` (paths must match); `blob`
    is any bytes-like object and is read in place.

    Raises :class:`DeserializationError` when the blob is malformed
    (truncated msgpack, garbled entries, buffer/shape size mismatch) —
    template mismatches against `like` keep their KeyError/ValueError.
    Counters: ``unpack_s`` (``unpackb`` and ``frombuffer``), ``h2d_s``/
    ``h2d_bytes`` (each leaf's ``jnp.asarray``, host side only)."""
    with spans.span("fl.deserialize", nbytes=len(blob)):
        t0 = time.perf_counter()
        try:
            payload = msgpack.unpackb(blob, raw=False)
            by_path: Dict[str, np.ndarray] = {}
            for e in payload["entries"]:
                arr = np.frombuffer(
                    e["data"], dtype=_dtype_from_name(e["dtype"])
                ).reshape(e["shape"])
                by_path[e["path"]] = arr
        except Exception as exc:  # noqa: BLE001 — any parse failure is corruption
            raise DeserializationError(f"malformed checkpoint blob: {exc}") from exc
        spans.add("unpack_s", time.perf_counter() - t0)

        leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(like)
        new_leaves = []
        h2d_s = 0.0
        h2d_bytes = 0
        for path, leaf in leaves_with_paths:
            key = _path_str(path)
            if key not in by_path:
                raise KeyError(f"checkpoint missing leaf {key!r}")
            arr = by_path[key]
            if tuple(arr.shape) != tuple(np.shape(leaf)):
                raise ValueError(
                    f"shape mismatch for {key!r}: checkpoint {arr.shape} vs model {np.shape(leaf)}"
                )
            t0 = time.perf_counter()
            new_leaves.append(jnp.asarray(arr, dtype=leaf.dtype if hasattr(leaf, "dtype") else None))
            h2d_s += time.perf_counter() - t0
            h2d_bytes += arr.nbytes
        spans.add("h2d_s", h2d_s)
        spans.add("h2d_bytes", h2d_bytes)
        return jax.tree_util.tree_unflatten(treedef, new_leaves)


def pytree_num_bytes(tree: Any) -> int:
    return sum(np.asarray(l).nbytes for l in jax.tree.leaves(tree))
