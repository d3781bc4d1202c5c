"""Framed, compressed batch wire format.

Reference: GpuColumnarBatchSerializer.scala:124 over JCudfSerialization
(host-framed tables for the default shuffle path) + TableCompressionCodec
(batched nvcomp LZ4). Same layering here: a host-framed format whose column
payloads run through the native LZ4 (utils/native.py, C++) — used by the
disk spill tier and the multithreaded shuffle, and as the DCN wire format.

Frame layout (little-endian):
  magic 'RTPU' | u32 version | u32 crc32(body) | u32 ncols | i64 nrows
  per column:
    u8 has_lengths | u8 codec(0=none,1=lz4,2=zlib,3=zstd) padding x2
    u32 name_len | name bytes
    u8  numpy dtype string len | dtype bytes | u32 extra(max_len)
    i64 raw_data_len | i64 comp_data_len | payload
    i64 raw_valid_len | i64 comp_valid_len | payload
    [i64 raw_lengths_len | i64 comp_len | payload]
"""

from __future__ import annotations

import io
import struct
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..batch import ColumnarBatch, DeviceColumn, Schema
from ..types import TypeKind
from ..utils import native

MAGIC = b"RTPU"
#: v2 added the envelope CRC32 (integrity of wire frames + spill files)
VERSION = 2
_CODEC = {"none": 0, "lz4": 1, "zlib": 2, "zstd": 3}
_CODEC_R = {v: k for k, v in _CODEC.items()}

#: magic(4) + version(4) + crc(4): the body the CRC covers starts here
_HEADER_LEN = 12


class FrameChecksumError(RuntimeError):
    """The frame body does not match the CRC32 its envelope carries —
    the bytes were damaged between serialize (exchange wire export,
    disk-tier spill write) and deserialize (fetch decode, spill read).
    Failing loudly here is the contract: a corrupt frame must never
    decode into silently-wrong rows."""


def _start_frame() -> io.BytesIO:
    """Open a frame with a zero CRC placeholder; _seal_frame patches it."""
    out = io.BytesIO()
    out.write(MAGIC)
    out.write(struct.pack("<II", VERSION, 0))
    return out


def _seal_frame(out: io.BytesIO) -> bytes:
    """Patch the envelope CRC32 in place — no extra full-frame copy on
    the spill/wire hot path (a multi-hundred-MB frame must not
    transiently double while the process is spilling under pressure)."""
    buf = out.getbuffer()
    crc = zlib.crc32(buf[_HEADER_LEN:]) & 0xFFFFFFFF
    struct.pack_into("<I", buf, 8, crc)
    del buf          # release the memoryview before getvalue()
    return out.getvalue()


def _write_blob(out: io.BytesIO, raw,
                codec: Optional[str] = None) -> None:
    """``raw`` may be bytes or a contiguous byte memoryview into a shared
    buffer (the packed-table fast path): every codec path consumes it
    without an intermediate copy (np.frombuffer / zlib accept views)."""
    payload, codec = native.compress(raw, codec)
    if len(payload) >= len(raw):
        payload, codec = raw, "none"
    out.write(struct.pack("<qqB", len(raw), len(payload), _CODEC[codec]))
    out.write(payload)


def _read_blob(buf: memoryview, pos: int) -> Tuple[bytes, int]:
    raw_len, comp_len, codec = struct.unpack_from("<qqB", buf, pos)
    pos += 17
    payload = bytes(buf[pos: pos + comp_len])
    pos += comp_len
    return native.decompress(payload, _CODEC_R[codec], raw_len), pos


def serialize_host(arrays: Dict[str, np.ndarray], num_rows: int,
                   codec: Optional[str] = None) -> bytes:
    """Serialize named host arrays (the spill-store / shuffle-write side).
    ``codec`` overrides the process default (per-session shuffle codec)."""
    out = _start_frame()
    out.write(struct.pack("<Iq", len(arrays), num_rows))
    for name, arr in arrays.items():
        arr = np.asarray(arr)   # NOT ascontiguousarray: it promotes 0-d to 1-d
        nb = name.encode()
        dt = arr.dtype.str.encode()
        out.write(struct.pack("<I", len(nb)))
        out.write(nb)
        out.write(struct.pack("<B", len(dt)))
        out.write(dt)
        out.write(struct.pack("<B", arr.ndim))
        for s in arr.shape:
            out.write(struct.pack("<q", s))
        _write_blob(out, arr.tobytes(), codec)
    return _seal_frame(out)


def deserialize_host(data: bytes) -> Tuple[Dict[str, np.ndarray], int]:
    buf = memoryview(data)
    assert bytes(buf[:4]) == MAGIC, "bad frame magic"
    version, crc = struct.unpack_from("<II", buf, 4)
    assert version == VERSION, f"frame version {version} != {VERSION}"
    # verified on EVERY deserialize — shuffle fetch decode and disk-tier
    # spill read alike (reference: the per-buffer checksums the UCX
    # shuffle validates on receive)
    if zlib.crc32(buf[_HEADER_LEN:]) & 0xFFFFFFFF != crc:
        raise FrameChecksumError(
            f"frame body fails its envelope CRC32 "
            f"({len(data) - _HEADER_LEN} bytes)")
    ncols, num_rows = struct.unpack_from("<Iq", buf, _HEADER_LEN)
    pos = _HEADER_LEN + 12
    arrays: Dict[str, np.ndarray] = {}
    for _ in range(ncols):
        (nlen,) = struct.unpack_from("<I", buf, pos)
        pos += 4
        name = bytes(buf[pos: pos + nlen]).decode()
        pos += nlen
        (dlen,) = struct.unpack_from("<B", buf, pos)
        pos += 1
        dt = bytes(buf[pos: pos + dlen]).decode()
        pos += dlen
        (ndim,) = struct.unpack_from("<B", buf, pos)
        pos += 1
        shape = []
        for _ in range(ndim):
            (s,) = struct.unpack_from("<q", buf, pos)
            pos += 8
            shape.append(s)
        raw, pos = _read_blob(buf, pos)
        arrays[name] = np.frombuffer(raw, dtype=np.dtype(dt)).reshape(shape)
    return arrays, num_rows


def _col_to_arrays(c: DeviceColumn, key: str,
                   arrays: Dict[str, np.ndarray]) -> None:
    """Flatten one column's device lanes under path-encoded keys; struct
    children recurse as ``{key}.{j}`` (the schema drives reassembly)."""
    import jax
    arrays[f"v{key}"] = np.asarray(jax.device_get(c.validity))
    if c.is_struct:
        for j, kid in enumerate(c.struct_fields):
            _col_to_arrays(kid, f"{key}.{j}", arrays)
        return
    arrays[f"d{key}"] = np.asarray(jax.device_get(c.data))
    if c.lengths is not None:
        arrays[f"l{key}"] = np.asarray(jax.device_get(c.lengths))
    if c.data2 is not None:     # map values / string-array lengths
        arrays[f"m{key}"] = np.asarray(jax.device_get(c.data2))
    if c.dict_data is not None:
        # dict strings ship dictionary + codes (d{key} above IS the code
        # lane) instead of a padded byte matrix — the compressed wire form
        arrays[f"D{key}"] = np.asarray(jax.device_get(c.dict_data))
        arrays[f"e{key}"] = np.asarray(jax.device_get(c.dict_lengths))


def _col_from_arrays(dtype, key: str,
                     arrays: Dict[str, np.ndarray]) -> DeviceColumn:
    import jax.numpy as jnp
    from ..types import TypeKind
    validity = jnp.asarray(arrays[f"v{key}"])
    if dtype.kind is TypeKind.STRUCT:
        kids = tuple(_col_from_arrays(ct, f"{key}.{j}", arrays)
                     for j, ct in enumerate(dtype.children))
        return DeviceColumn(kids, validity, None, dtype)
    lengths = jnp.asarray(arrays[f"l{key}"]) if f"l{key}" in arrays else None
    data2 = jnp.asarray(arrays[f"m{key}"]) if f"m{key}" in arrays else None
    dict_data = jnp.asarray(arrays[f"D{key}"]) \
        if f"D{key}" in arrays else None
    dict_lengths = jnp.asarray(arrays[f"e{key}"]) \
        if f"e{key}" in arrays else None
    return DeviceColumn(jnp.asarray(arrays[f"d{key}"]), validity,
                        lengths, dtype, data2, dict_data, dict_lengths)


def batch_to_arrays(batch: ColumnarBatch) -> Dict[str, np.ndarray]:
    """D2H every lane of a device batch under its path-encoded keys."""
    arrays: Dict[str, np.ndarray] = {}
    for i, c in enumerate(batch.columns):
        _col_to_arrays(c, str(i), arrays)
    return arrays


def pack_batch(batch: ColumnarBatch):
    """One D2H staging pass: the device batch's lanes land in a single
    contiguous host PackedTable (memory/packed.py — the pinned-staging
    shape), which BOTH the spill host tier and `frame_packed` consume
    without reparsing. This is the serialize-once carrier: a batch packed
    here is never re-flattened, whether it goes to the wire, to disk, or
    back to the device."""
    from ..memory.packed import PackedTable
    return PackedTable.pack(batch_to_arrays(batch), int(batch.num_rows))


def frame_packed(packed, codec: Optional[str] = None) -> bytes:
    """PackedTable -> RTPU frame, slicing each section's payload straight
    out of the packed buffer (no per-array tobytes round-trip; the only
    remaining copy is the codec's own output). Byte-compatible with
    serialize_host — deserialize_host/deserialize_batch read both."""
    mv = memoryview(packed.buffer).cast("B")
    out = _start_frame()
    out.write(struct.pack("<Iq", len(packed.meta.sections),
                          packed.meta.num_rows))
    for s in packed.meta.sections:
        nb = s.key.encode()
        dt = s.dtype.encode()
        out.write(struct.pack("<I", len(nb)))
        out.write(nb)
        out.write(struct.pack("<B", len(dt)))
        out.write(dt)
        out.write(struct.pack("<B", len(s.shape)))
        for dim in s.shape:
            out.write(struct.pack("<q", dim))
        _write_blob(out, mv[s.offset: s.offset + s.nbytes], codec)
    return _seal_frame(out)


def serialize_batch(batch: ColumnarBatch, schema: Schema,
                    codec: Optional[str] = None) -> bytes:
    """Device batch -> framed bytes: ONE D2H staging pass into a packed
    table, then frame directly from it (reference: the serialize-once
    contiguous-split + JCudfSerialization write path,
    GpuPartitioning.scala:52)."""
    from ..trace import span as _trace_span
    with _trace_span("serializer.pack", kind="serializer") as sp:
        data = frame_packed(pack_batch(batch), codec)
        if sp is not None:
            sp.attrs["bytes"] = len(data)
        return data


def iter_framed(batches, codec: Optional[str] = None,
                depth: Optional[int] = None, metrics=None):
    """Frame a stream of device batches with the D2H stage of batch N+1
    overlapped with the framing/compression of batch N (the exchange-side
    use of the bounded pipeline; depth=0 = synchronous). Yields
    (item, frame_bytes) pairs where ``batches`` yields (item, batch)."""
    from ..pipeline import close_iterator, prefetched

    def staged():
        for item, b in batches:
            yield item, pack_batch(b)     # D2H on the producer thread

    if depth is None:
        from ..config import PREFETCH_DEPTH, PREFETCH_ENABLED, _REGISTRY
        depth = int(_REGISTRY[PREFETCH_DEPTH.key].default) \
            if _REGISTRY[PREFETCH_ENABLED.key].default else 0
    it = prefetched(staged(), depth, metrics=metrics,
                    name="exchange-serialize", stage="wire")
    try:
        for item, packed in it:
            yield item, frame_packed(packed, codec)
    finally:
        close_iterator(it)


def deserialize_batch(data: bytes, schema: Schema) -> ColumnarBatch:
    import jax.numpy as jnp

    from ..trace import span as _trace_span
    with _trace_span("serializer.unpack", kind="serializer",
                     bytes=len(data)):
        arrays, num_rows = deserialize_host(data)
        cols: List[DeviceColumn] = []
        for i, f in enumerate(schema):
            cols.append(_col_from_arrays(f.dtype, str(i), arrays))
        return ColumnarBatch(tuple(cols), jnp.asarray(num_rows, jnp.int32))
