"""Native runtime components (C++): the durable op-stream shuttle.

Reference parity: SURVEY.md §2.9 — the reference's server leans on native
code for its transport/storage hot paths (librdkafka for the ordering
bus, MongoDB for the durable op log, libgit2 for snapshots). Here the
equivalent is a CRC-framed append-only record log (oplog.cpp) compiled on
first use and bound via ctypes; server/durable_store.py builds the
durable bus, state store and snapshot store on top of it.

``OpLog`` picks the C++ implementation when the toolchain is available
and falls back to a pure-Python writer of the IDENTICAL file format, so
logs are portable between the two.
"""

from __future__ import annotations

import contextlib
import ctypes
import os
import struct
import zlib
from array import array
from pathlib import Path

from ._loader import build_and_load

_SRC = Path(__file__).parent / "oplog.cpp"
_configured: ctypes.CDLL | None = None


def _load_library() -> ctypes.CDLL | None:
    global _configured
    if _configured is not None:
        return _configured
    lib = build_and_load("oplog", _SRC, extra_flags=("-lz",))
    if lib is None:
        return None
    lib.oplog_open.restype = ctypes.c_void_p
    lib.oplog_open.argtypes = [ctypes.c_char_p]
    lib.oplog_count.restype = ctypes.c_long
    lib.oplog_count.argtypes = [ctypes.c_void_p]
    lib.oplog_append.restype = ctypes.c_long
    lib.oplog_append.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                 ctypes.c_uint32]
    lib.oplog_append_many.restype = ctypes.c_long
    lib.oplog_append_many.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_void_p, ctypes.c_uint32]
    lib.oplog_sync.restype = ctypes.c_int
    lib.oplog_sync.argtypes = [ctypes.c_void_p]
    lib.oplog_read_len.restype = ctypes.c_long
    lib.oplog_read_len.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.oplog_read.restype = ctypes.c_long
    lib.oplog_read.argtypes = [ctypes.c_void_p, ctypes.c_long,
                               ctypes.c_char_p, ctypes.c_uint32]
    lib.oplog_close.restype = None
    lib.oplog_close.argtypes = [ctypes.c_void_p]
    _configured = lib
    return _configured


class _NativeOpLog:
    def __init__(self, path: str) -> None:
        lib = _load_library()
        assert lib is not None
        self._lib = lib
        self._handle = lib.oplog_open(path.encode())
        if not self._handle:
            raise OSError(f"oplog_open failed: {path}")

    def __len__(self) -> int:
        return self._lib.oplog_count(self._handle)

    def append(self, data: bytes) -> int:
        idx = self._lib.oplog_append(self._handle, data, len(data))
        if idx < 0:
            raise OSError("oplog_append failed")
        return idx

    def append_many(self, payloads: list[bytes]) -> int:
        """Append ``payloads`` as ``append`` would, one by one, with one
        write; returns the index of the first."""
        if not payloads:
            return len(self)
        lens = array("I", map(len, payloads))
        idx = self._lib.oplog_append_many(
            self._handle, b"".join(payloads), lens.buffer_info()[0],
            len(lens))
        if idx < 0:
            raise OSError("oplog_append_many failed")
        return idx

    def read(self, index: int) -> bytes:
        length = self._lib.oplog_read_len(self._handle, index)
        if length < 0:
            raise IndexError(index)
        buf = ctypes.create_string_buffer(length)
        got = self._lib.oplog_read(self._handle, index, buf, length)
        if got != length:
            raise OSError("oplog_read failed")
        return buf.raw

    def sync(self) -> None:
        # A swallowed -1 here would be catastrophic: the group-commit
        # writer would advance the durability watermark (and release
        # withheld acks) over bytes that never reached disk, and the
        # WAL fsync circuit breaker could never open on a real failure.
        if self._lib.oplog_sync(self._handle) < 0:
            raise OSError("oplog_sync (fdatasync) failed")

    def close(self) -> None:
        if self._handle:
            self._lib.oplog_close(self._handle)
            self._handle = None


class _PythonOpLog:
    """Same file format as oplog.cpp ([u32 len][u32 crc32][payload] LE),
    including torn-tail truncation on open."""

    def __init__(self, path: str) -> None:
        self._path = path
        self._index: list[tuple[int, int]] = []  # (payload offset, len)
        self._fh = open(path, "a+b")
        self._fh.seek(0, os.SEEK_END)
        size = self._fh.tell()
        pos = 0
        while pos + 8 <= size:
            self._fh.seek(pos)
            header = self._fh.read(8)
            length, crc = struct.unpack("<II", header)
            if pos + 8 + length > size:
                break
            payload = self._fh.read(length)
            if len(payload) != length or zlib.crc32(payload) != crc:
                break
            self._index.append((pos + 8, length))
            pos += 8 + length
        if pos < size:
            self._fh.truncate(pos)
        self._end = pos

    def __len__(self) -> int:
        return len(self._index)

    def append(self, data: bytes) -> int:
        return self.append_many([data])

    def append_many(self, payloads: list[bytes]) -> int:
        first = len(self._index)
        if not payloads:
            return first
        frames = b"".join(struct.pack("<II", len(p), zlib.crc32(p)) + p
                          for p in payloads)
        self._fh.seek(self._end)
        try:
            self._fh.write(frames)
            self._fh.flush()
        except OSError:
            # Append mode writes at the end of the file: cut a partial
            # tail so that the next append lands at ``_end``.
            with contextlib.suppress(OSError):
                self._fh.truncate(self._end)
            raise
        pos = self._end
        for p in payloads:
            self._index.append((pos + 8, len(p)))
            pos += 8 + len(p)
        self._end = pos
        return first

    def read(self, index: int) -> bytes:
        offset, length = self._index[index]
        self._fh.seek(offset)
        return self._fh.read(length)

    def sync(self) -> None:
        self._fh.flush()
        os.fdatasync(self._fh.fileno())

    def close(self) -> None:
        self._fh.close()


def OpLog(path: str | os.PathLike):
    """Open (creating if missing) an append-only record log."""
    if _load_library() is not None:
        return _NativeOpLog(str(path))
    return _PythonOpLog(str(path))


def native_available() -> bool:
    return _load_library() is not None
