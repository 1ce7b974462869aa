"""Snappy block-format codec: native C++ fast path + pure-Python reference.

The eth2 wire protocol frames gossip messages and Req/Resp chunks with
snappy (raw block format for gossip, framed for RPC streams — the
ssz_snappy encoding of /root/reference/beacon_node/lighthouse_network/src/
rpc/codec/, which links google/snappy natively via the `snap` crate).
Python ships no snappy and the environment is dependency-frozen, so this
module implements the block format twice:

  native/snappy.cc — the production path (built with g++ on first use,
      loaded via ctypes): where sync throughput spends its framing CPU
  pure Python below — the always-available reference implementation and
      fallback; differential tests pin the two bit-compatible on the
      decode side and round-trip-compatible on encode

Snappy block format: varint uncompressed length, then tagged elements:
  tag & 3 == 0: literal, length (tag>>2)+1 (or 1-4 extra length bytes)
  tag & 3 == 1: copy, 1-byte offset-ish (len 4-11, offset 11 bits)
  tag & 3 == 2: copy, 2-byte little-endian offset (len 1-64)
  tag & 3 == 3: copy, 4-byte offset
"""

from __future__ import annotations


class SnappyError(Exception):
    pass


# ------------------------------------------------------------ native path

_native = None
_native_tried = False


# Decompression output bound: no eth2 message (gossip max ~10 MiB) comes
# close; an attacker-controlled length varint must never size an
# allocation (the claimed length is checked against this BEFORE any
# buffer is created).
MAX_UNCOMPRESSED_LEN = 32 << 20


def _load_native():
    """Build/load the C++ codec; returns the ctypes lib or None (logged —
    a broken toolchain silently pinning production to the slow path would
    otherwise be invisible)."""
    global _native, _native_tried
    if _native_tried:
        return _native
    _native_tried = True
    try:
        import ctypes
        from pathlib import Path

        from ..utils.native_build import build_native

        lib_path = build_native(
            Path(__file__).parent / "native" / "snappy.cc", "libltsnappy.so"
        )
        lib = ctypes.CDLL(str(lib_path))
        lib.snp_uncompressed_length.restype = ctypes.c_int
        lib.snp_uncompressed_length.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)
        ]
        lib.snp_decompress.restype = ctypes.c_int64
        lib.snp_decompress.argtypes = [
            ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p, ctypes.c_uint64
        ]
        lib.snp_max_compressed_length.restype = ctypes.c_uint64
        lib.snp_max_compressed_length.argtypes = [ctypes.c_uint64]
        lib.snp_compress.restype = ctypes.c_int64
        lib.snp_compress.argtypes = [ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p]
        _native = lib
    except Exception as e:
        from ..utils.logging import get_logger

        get_logger("snappy").warn(
            "native snappy unavailable; using the pure-Python codec",
            error=f"{type(e).__name__}: {e}",
        )
        _native = None
    return _native


def _native_decompress(lib, data: bytes) -> bytes:
    import ctypes

    out_len = ctypes.c_uint64()
    if lib.snp_uncompressed_length(data, len(data), ctypes.byref(out_len)) != 0:
        raise SnappyError("truncated varint")
    if out_len.value > MAX_UNCOMPRESSED_LEN:
        raise SnappyError("uncompressed length over limit")
    buf = ctypes.create_string_buffer(out_len.value)
    written = lib.snp_decompress(data, len(data), buf, out_len.value)
    if written < 0:
        raise SnappyError("malformed snappy block")
    return buf.raw[:written]


def _native_compress(lib, data: bytes) -> bytes:
    import ctypes

    cap = lib.snp_max_compressed_length(len(data))
    buf = ctypes.create_string_buffer(cap)
    written = lib.snp_compress(data, len(data), buf)
    return buf.raw[:written]


def _read_varint(data: bytes, pos: int) -> tuple[int, int]:
    out = 0
    shift = 0
    while True:
        if pos >= len(data):
            raise SnappyError("truncated varint")
        b = data[pos]
        pos += 1
        out |= (b & 0x7F) << shift
        if not b & 0x80:
            return out, pos
        shift += 7
        if shift > 35:
            raise SnappyError("varint too long")


def _write_varint(n: int) -> bytes:
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def decompress(data: bytes) -> bytes:
    lib = _load_native()
    if lib is not None:
        return _native_decompress(lib, data)
    return _py_decompress(data)


def _py_decompress(data: bytes) -> bytes:
    expected, pos = _read_varint(data, 0)
    if expected > MAX_UNCOMPRESSED_LEN:
        raise SnappyError("uncompressed length over limit")
    out = bytearray()
    n = len(data)
    while pos < n:
        tag = data[pos]
        pos += 1
        elem_type = tag & 3
        if elem_type == 0:  # literal
            length = tag >> 2
            if length < 60:
                length += 1
            else:
                extra = length - 59
                if pos + extra > n:
                    raise SnappyError("truncated literal length")
                length = int.from_bytes(data[pos : pos + extra], "little") + 1
                pos += extra
            if pos + length > n:
                raise SnappyError("truncated literal")
            out += data[pos : pos + length]
            pos += length
            continue
        if elem_type == 1:
            length = ((tag >> 2) & 0x7) + 4
            if pos >= n:
                raise SnappyError("truncated copy1")
            offset = ((tag >> 5) << 8) | data[pos]
            pos += 1
        elif elem_type == 2:
            length = (tag >> 2) + 1
            if pos + 2 > n:
                raise SnappyError("truncated copy2")
            offset = int.from_bytes(data[pos : pos + 2], "little")
            pos += 2
        else:
            length = (tag >> 2) + 1
            if pos + 4 > n:
                raise SnappyError("truncated copy4")
            offset = int.from_bytes(data[pos : pos + 4], "little")
            pos += 4
        if offset == 0 or offset > len(out):
            raise SnappyError("bad copy offset")
        for _ in range(length):  # byte-wise: copies may overlap
            out.append(out[-offset])
    if len(out) != expected:
        raise SnappyError(f"length mismatch: {len(out)} != {expected}")
    return bytes(out)


def _emit_literal(out: bytearray, chunk: bytes) -> None:
    length = len(chunk) - 1
    if length < 60:
        out.append(length << 2)
    elif length < (1 << 8):
        out.append(60 << 2)
        out += length.to_bytes(1, "little")
    elif length < (1 << 16):
        out.append(61 << 2)
        out += length.to_bytes(2, "little")
    elif length < (1 << 24):
        out.append(62 << 2)
        out += length.to_bytes(3, "little")
    else:
        out.append(63 << 2)
        out += length.to_bytes(4, "little")
    out += chunk


def compress(data: bytes) -> bytes:
    lib = _load_native()
    if lib is not None:
        return _native_compress(lib, data)
    return _py_compress(data)


def _py_compress(data: bytes) -> bytes:
    """Greedy hash-table matcher (4-byte anchors, 64KB window)."""
    out = bytearray(_write_varint(len(data)))
    n = len(data)
    if n == 0:
        return bytes(out)
    table: dict[bytes, int] = {}
    i = 0
    lit_start = 0
    while i + 4 <= n:
        key = data[i : i + 4]
        cand = table.get(key)
        table[key] = i
        if cand is not None and i - cand <= 0xFFFF and data[cand : cand + 4] == key:
            # extend match
            length = 4
            while i + length < n and length < 64 and data[cand + length] == data[i + length]:
                length += 1
            if lit_start < i:
                _emit_literal(out, data[lit_start:i])
            offset = i - cand
            # emit copy (type 2 covers len<=64, 16-bit offsets)
            out.append(((length - 1) << 2) | 2)
            out += offset.to_bytes(2, "little")
            i += length
            lit_start = i
        else:
            i += 1
    if lit_start < n:
        _emit_literal(out, data[lit_start:])
    return bytes(out)
