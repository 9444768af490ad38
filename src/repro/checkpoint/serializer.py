"""Pytree checkpoint serialization with exact byte accounting, int8
compression and delta encoding.

The serialized size IS the feasibility model's S_j — the orchestrator reads
it from CheckpointManager, never from an estimate (DESIGN.md §4). Modes:

  full        raw little-endian buffers (bf16/f32/int32 as stored)
  int8        per-256-block symmetric int8 (kernels/quantize) + f32 scales
              -> ~2x (bf16) / ~4x (f32) smaller, lossy but training-safe
  delta-int8  int8-quantized (x - base) against a base checkpoint the
              destination already holds — the paper §VIII 'compressed model
              deltas' / incremental checkpoints, usually another ~step-
              dependent win on top (identical leaves collapse to zeros).

Format: MAGIC, the manifest's length (8 bytes, little-endian), the JSON
manifest (paths, shapes, dtypes, mode, block, each entry's offset and
size), then the concatenated payload. Works on any pytree of jax/numpy
arrays.

One writer and one reader. ``encode`` lays the manifest out from shapes
and dtypes, and stages only the int8 entries, as compressed blobs;
``write`` streams the header and then every raw entry straight from its
leaf's own buffer. ``read`` reads every raw entry straight into a fresh
array of its own and the int8 blobs as bytes; ``decode`` dequantizes the
blobs and rebuilds the tree. ``serialize_tree`` / ``deserialize_tree`` /
``to_bytes`` / ``from_bytes`` run the same writer and reader in memory.
Both ends count ``ckpt.bytes`` (every entry's payload) and
``ckpt.bytes_direct`` (what moved between a leaf's buffer and the file
with no copy in between) in ``repro.telemetry``.
"""
from __future__ import annotations

import io
import json
import zlib
from dataclasses import dataclass
from typing import Any, BinaryIO, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.layout import Format, Layout

from repro import telemetry
from repro.kernels import ops as kops

BLOCK = 256
MAGIC = b"GRNCKPT1"
HEAD = len(MAGIC) + 8  # MAGIC + the manifest's length


def _path_str(path) -> str:
    parts = []
    for p in path:
        parts.append(str(getattr(p, "key", getattr(p, "idx", p))))
    return "/".join(parts)


def host_array(x) -> np.ndarray:
    """``x`` on the host. A jax array the compiler keeps in a layout other
    than row-major (a train step's outputs on the TPU: a column-major
    embedding, attention weights with the model axis minor) would come
    back as a strided view; it is relaid out on the device first, leaf by
    leaf, so the transfer itself gives the file's C order and no strided
    copy is left for the host."""
    layout = getattr(getattr(x, "format", None), "layout", None)
    row_major = tuple(range(np.ndim(x)))
    if layout is not None and layout.major_to_minor != row_major:
        x = jax.device_put(x, Format(Layout(row_major), x.sharding))
    return np.asarray(x)


def _flatten_with_paths(tree):
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    return [(_path_str(p), host_array(x)) for p, x in leaves]


def _octets(arr: np.ndarray) -> np.ndarray:
    """The bytes of a C-contiguous array, as a flat uint8 view of its
    buffer (bfloat16 has no buffer-protocol format of its own)."""
    return arr.reshape(-1).view(np.uint8)


def tree_bytes(tree) -> int:
    """Exact raw (mode='full') checkpoint payload size in bytes."""
    return int(sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree)))


def _quant_flat(flat: np.ndarray) -> Tuple[bytes, bytes, int]:
    """int8-quantize a flat f32 array (padded to BLOCK)."""
    n = flat.size
    pad = (-n) % BLOCK
    padded = np.pad(flat.astype(np.float32), (0, pad))
    q, s = kops.quantize_int8(jnp.asarray(padded), block=BLOCK)
    return np.asarray(q).tobytes(), np.asarray(s).tobytes(), pad


def _header(manifest: Dict[str, Any]) -> bytes:
    mjson = json.dumps(manifest).encode()
    return MAGIC + len(mjson).to_bytes(8, "little") + mjson


@dataclass
class Image:
    """A checkpoint laid out for writing: its header, and per manifest
    entry the leaf itself (``enc`` raw) or its compressed blob (int8)."""
    manifest: Dict[str, Any]
    header: bytes
    parts: List[Any]  # np.ndarray | bytes


def encode(tree, mode: str = "full", base: Optional[Any] = None) -> Image:
    assert mode in ("full", "int8", "delta-int8"), mode
    if mode == "delta-int8" and base is None:
        raise ValueError("delta-int8 needs a base checkpoint tree")
    entries: List[Dict[str, Any]] = []
    parts: List[Any] = []
    offset = 0
    base_leaves = dict(_flatten_with_paths(base)) if base is not None else {}
    for path, arr in _flatten_with_paths(tree):
        entry: Dict[str, Any] = {
            "path": path,
            "shape": list(arr.shape),
            "dtype": str(arr.dtype),
            "offset": offset,
        }
        if mode == "full" or not jnp.issubdtype(arr.dtype, jnp.floating):
            entry["enc"] = "raw"
            part, n = arr, arr.nbytes
        else:
            flat = arr.astype(np.float32).reshape(-1)
            if mode == "delta-int8":
                b = base_leaves.get(path)
                if b is not None and b.shape == arr.shape:
                    flat = flat - b.astype(np.float32).reshape(-1)
                    entry["delta"] = True
            qb, sb, pad = _quant_flat(flat)
            # entropy-code the int8 payload: near-zero deltas collapse
            # (the paper's §VIII 'compressed model deltas', implemented)
            qz = zlib.compress(qb, level=1)
            sz = zlib.compress(sb, level=1)
            entry["enc"] = "int8"
            entry["pad"] = pad
            entry["qlen"] = len(qz)
            entry["q_raw"] = len(qb)
            entry["s_raw"] = len(sb)
            part = qz + sz
            n = len(part)
        entry["nbytes"] = n
        offset += n
        entries.append(entry)
        parts.append(part)
    manifest = {"mode": mode, "block": BLOCK, "entries": entries}
    return Image(manifest, _header(manifest), parts)


def write(f: BinaryIO, image: Image) -> int:
    """Write ``image`` to the binary stream ``f``: the header, then each
    entry, a raw one straight from its leaf's buffer (a non-contiguous
    leaf through one contiguous copy). Returns the bytes written."""
    f.write(image.header)
    total = direct = 0
    for part in image.parts:
        if isinstance(part, np.ndarray):
            if part.flags.c_contiguous:
                direct += part.nbytes
            part = _octets(np.ascontiguousarray(part))
        f.write(part)
        total += len(part)
    telemetry.count("ckpt.bytes", total)
    telemetry.count("ckpt.bytes_direct", direct)
    return len(image.header) + total


def _read_header(f: BinaryIO) -> Dict[str, Any]:
    head = f.read(HEAD)
    if len(head) < HEAD or head[:len(MAGIC)] != MAGIC:
        raise ValueError("not a GreenFlow checkpoint")
    mlen = int.from_bytes(head[len(MAGIC):], "little")
    mjson = f.read(mlen)
    if len(mjson) != mlen:
        raise ValueError("truncated checkpoint: manifest cut short")
    return json.loads(mjson.decode())


def _read_into(f: BinaryIO, buf: np.ndarray, path: str) -> None:
    view = memoryview(buf)
    got = 0
    while got < len(view):
        n = f.readinto(view[got:])
        if not n:
            raise ValueError(f"truncated checkpoint: entry {path!r} has "
                             f"{got} of {len(view)} bytes")
        got += n


def read(f: BinaryIO) -> Tuple[Dict[str, Any], Dict[str, Any]]:
    """Read a checkpoint from the binary stream ``f`` (at its start):
    ``(manifest, parts)``, ``parts`` by path, each raw entry read straight
    into an array of its own, each int8 entry as its blob."""
    manifest = _read_header(f)
    start = f.tell()
    parts: Dict[str, Any] = {}
    total = direct = 0
    for e in manifest["entries"]:
        f.seek(start + e["offset"])
        if e["enc"] == "raw":
            arr = np.empty(tuple(e["shape"]), np.dtype(e["dtype"]))
            _read_into(f, _octets(arr), e["path"])
            direct += arr.nbytes
            parts[e["path"]] = arr
        else:
            blob = f.read(e["nbytes"])
            if len(blob) != e["nbytes"]:
                raise ValueError(f"truncated checkpoint: entry {e['path']!r}"
                                 f" has {len(blob)} of {e['nbytes']} bytes")
            parts[e["path"]] = blob
        total += e["nbytes"]
    telemetry.count("ckpt.bytes", total)
    telemetry.count("ckpt.bytes_direct", direct)
    return manifest, parts


def decode(manifest: Dict[str, Any], parts: Dict[str, Any], like,
           base: Optional[Any] = None):
    """Rebuild a pytree with the structure of ``like`` (params template
    or ShapeDtypeStructs) from what ``read`` gave. delta-int8 checkpoints
    need the same base tree."""
    entries = {e["path"]: e for e in manifest["entries"]}
    base_leaves = dict(_flatten_with_paths(base)) if base is not None else {}

    def rebuild(path, leaf):
        p = _path_str(path)
        e = entries[p]
        if e["enc"] == "raw":
            return parts[p]
        raw = parts[p]
        q = np.frombuffer(zlib.decompress(raw[: e["qlen"]]), dtype=np.int8)
        s = np.frombuffer(zlib.decompress(raw[e["qlen"]:]), dtype=np.float32)
        flat = np.asarray(
            kops.dequantize_int8(jnp.asarray(q), jnp.asarray(s), block=manifest["block"])
        )
        if e["pad"]:
            flat = flat[: -e["pad"]]
        if e.get("delta") and p in base_leaves:
            flat = flat + base_leaves[p].astype(np.float32).reshape(-1)
        return flat.reshape(tuple(e["shape"])).astype(np.dtype(e["dtype"]))

    return jax.tree_util.tree_map_with_path(rebuild, like)


# -- in-memory form (tests, size reports): the same writer and reader ---------
@dataclass
class CheckpointPayload:
    manifest: Dict[str, Any]
    data: bytes

    @property
    def nbytes(self) -> int:
        return len(self.data) + len(json.dumps(self.manifest).encode())


def serialize_tree(
    tree,
    mode: str = "full",
    base: Optional[Any] = None,
) -> CheckpointPayload:
    buf = io.BytesIO()
    write(buf, encode(tree, mode, base))
    return from_bytes(buf.getvalue())


def deserialize_tree(
    payload: CheckpointPayload,
    like,
    base: Optional[Any] = None,
):
    """Rebuild a pytree with the structure/dtypes of `like` (params template
    or ShapeDtypeStructs). delta-int8 payloads need the same base tree."""
    return decode(*read(io.BytesIO(to_bytes(payload))), like, base=base)


def to_bytes(payload: CheckpointPayload) -> bytes:
    return _header(payload.manifest) + payload.data


def from_bytes(raw: bytes) -> CheckpointPayload:
    f = io.BytesIO(raw)
    manifest = _read_header(f)
    return CheckpointPayload(manifest, raw[f.tell():])
