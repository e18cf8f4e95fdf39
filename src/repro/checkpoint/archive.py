"""The trainer's uncompressed snapshot blob, laid out before it is filled.

A blob is a prefix (the trainer's length-prefixed JSON header) followed by
the archive ``np.savez`` writes for the leaves: a stored zip with zip64
records forced on every entry, ``arr_<i>.npy`` in leaf order, byte for byte
what ``zipfile`` and ``np.lib.format.write_array`` produce. Stored, not
compressed: zlib shrinks float32 state by ~7% at ~24 MB/s of host CPU,
minutes per save at full width. The archive's offsets count from its own
first byte, not the blob's: ``Restore`` loads the archive alone, and zip64
records hold absolute offsets.

Every offset and size follows from the leaves' shapes and dtypes, so the
buffer is allocated once at its final size, each leaf is copied into its
place once, and its crc32 is taken over the host array. The large copies
and the crc32 release the GIL. The blob fills front to back, so a writer
can follow the fill and write each part as soon as it is final.

The buffer is a private anonymous ``mmap``: its pages are committed as they
are filled (in huge pages where the kernel gives them, which faults a fresh
buffer in about three times faster), and its slices are ``bytes``, as
``TrainerStateObject.Restore`` expects of a blob read back from the store's
memory tier.
"""
from __future__ import annotations

import io
import mmap
import struct
import zipfile
import zlib
from typing import Callable, List

import numpy as np

from ..core import spans

#: bytes copied and checksummed per call: a chunk is still in cache when
#: its crc32 reads it
_CHUNK = 8 << 20
#: the DOS date ``zipfile`` stamps on entries it names (1980-01-01 00:00)
_DOSDATE = 1 << 5 | 1
_PERMISSIONS = 0o600 << 16
_SYSTEM = zipfile.ZipInfo().create_system
_LOCAL = struct.Struct(zipfile.structFileHeader)
_ZIP64_SIZES = struct.Struct("<HHQQ")
_CENTRAL = struct.Struct(zipfile.structCentralDir)
_END64 = struct.Struct(zipfile.structEndArchive64)
_LOCATOR = struct.Struct(zipfile.structEndArchive64Locator)
_END = struct.Struct(zipfile.structEndArchive)


def _npy_header(arr: np.ndarray) -> bytes:
    out = io.BytesIO()
    np.lib.format.write_array_header_1_0(out, np.lib.format.header_data_from_array_1_0(arr))
    return out.getvalue()


def _local(name: bytes, size: int, crc: int) -> bytes:
    return _LOCAL.pack(
        zipfile.stringFileHeader, zipfile.ZIP64_VERSION, 0, 0, zipfile.ZIP_STORED, 0, _DOSDATE,
        crc, 0xFFFFFFFF, 0xFFFFFFFF, len(name), _ZIP64_SIZES.size,
    ) + name + _ZIP64_SIZES.pack(1, _ZIP64_SIZES.size - 4, size, size)


def _central(name: bytes, size: int, offset: int, crc: int) -> bytes:
    """A central directory entry; a size or offset past ``zipfile``'s limit
    moves into the zip64 extra field, as ``zipfile`` moves it."""
    wide = []
    if size > zipfile.ZIP64_LIMIT:
        wide += [size, size]
        size = 0xFFFFFFFF
    if offset > zipfile.ZIP64_LIMIT:
        wide.append(offset)
        offset = 0xFFFFFFFF
    extra = struct.pack("<HH" + "Q" * len(wide), 1, 8 * len(wide), *wide) if wide else b""
    return _CENTRAL.pack(
        zipfile.stringCentralDir, zipfile.ZIP64_VERSION, _SYSTEM, zipfile.ZIP64_VERSION, 0, 0,
        zipfile.ZIP_STORED, 0, _DOSDATE, crc, size, size, len(name), len(extra), 0, 0, 0,
        _PERMISSIONS, offset,
    ) + name + extra


def _end(count: int, cd_offset: int, cd_size: int) -> bytes:
    out = b""
    if (count > zipfile.ZIP_FILECOUNT_LIMIT or cd_offset > zipfile.ZIP64_LIMIT
            or cd_size > zipfile.ZIP64_LIMIT):
        out = _END64.pack(
            zipfile.stringEndArchive64, zipfile.sizeEndCentDir64 - 12, zipfile.ZIP64_VERSION,
            zipfile.ZIP64_VERSION, 0, 0, count, count, cd_size, cd_offset,
        ) + _LOCATOR.pack(zipfile.stringEndArchive64Locator, 0, cd_offset + cd_size, 1)
        count, cd_size, cd_offset = min(count, 0xFFFF), min(cd_size, 0xFFFFFFFF), min(cd_offset, 0xFFFFFFFF)
    return out + _END.pack(zipfile.stringEndArchive, 0, 0, count, count, cd_size, cd_offset, 0)


def _place(out: np.ndarray, at: int, data: np.ndarray, crc: int) -> int:
    """Copy ``data`` (bytes) into ``out`` at ``at``; its crc32 continued
    from ``crc``."""
    for lo in range(0, len(data), _CHUNK):
        chunk = data[lo : lo + _CHUNK]
        out[at + lo : at + lo + len(chunk)] = chunk
        crc = zlib.crc32(chunk, crc)
    return crc


def _layout(leaves: List[np.ndarray]):
    """Per leaf, its entry's name, npy header, stored size and offset in the
    archive; and where the central directory starts. Makes each leaf a
    C-ordered array, as ``write_array`` would write it."""
    entries, at = [], 0
    for i, leaf in enumerate(leaves):
        leaves[i] = arr = np.asarray(leaf, order="C")
        name, header = f"arr_{i}.npy".encode(), _npy_header(arr)
        entries.append((name, header, len(header) + arr.nbytes, at))
        at += _LOCAL.size + len(name) + _ZIP64_SIZES.size + len(header) + arr.nbytes
    return entries, at


class Archive:
    """The blob of ``prefix`` and the archive of ``leaves``, allocated at
    its final size with ``prefix`` in place; ``fill`` copies the leaves in.

    Takes ``leaves`` over: ``fill`` sets each entry to None once its leaf is
    in place, so the host snapshot shrinks as the blob fills."""

    def __init__(self, prefix: bytes, leaves: List[np.ndarray]) -> None:
        self._leaves = leaves
        self._entries, self._cd_offset = _layout(leaves)
        self._cd_size = sum(len(_central(name, size, offset, 0))
                            for name, _, size, offset in self._entries)
        self._end = _end(len(self._entries), self._cd_offset, self._cd_size)
        self._base = len(prefix)
        self.blob = mmap.mmap(-1, self._base + self._cd_offset + self._cd_size + len(self._end),
                              flags=mmap.MAP_PRIVATE | mmap.MAP_ANONYMOUS)
        if hasattr(mmap, "MADV_HUGEPAGE"):
            self.blob.madvise(mmap.MADV_HUGEPAGE)
        self.blob[: self._base] = prefix

    def fill(self, stopped: Callable[[], bool], filled: Callable[[int], None] = lambda n: None,
             parent: int = 0) -> bool:
        """Copy each leaf into place, then the records that join the entries
        into one archive; after each, ``filled(n)``: the blob's first ``n``
        bytes are final. False where ``stopped()`` turns true between two
        leaves, the blob then part filled.

        Spans (under ``parent`` where given, for a fill handed off to another
        thread): one ``trainer.encode`` (leaf) per leaf, its npy header and
        data copied into place and checksummed; ``trainer.join``, the central
        directory and end records."""
        blob, base = self.blob, self._base
        out = np.frombuffer(blob, np.uint8)
        crcs = []
        for i, (name, header, size, offset) in enumerate(self._entries):
            if stopped():
                return False
            with spans.span("trainer.encode", parent=parent, leaf=i):
                leaf, self._leaves[i] = self._leaves[i], None
                at = base + offset + _LOCAL.size + len(name) + _ZIP64_SIZES.size
                crc = _place(out, at + len(header), leaf.reshape(-1).view(np.uint8),
                             zlib.crc32(header))
                del leaf
                blob[base + offset : at + len(header)] = _local(name, size, crc) + header
                crcs.append(crc)
            filled(at + size)
        with spans.span("trainer.join", parent=parent):
            at = base + self._cd_offset
            for (name, _, size, offset), crc in zip(self._entries, crcs):
                entry = _central(name, size, offset, crc)
                blob[at : at + len(entry)] = entry
                at += len(entry)
            blob[at:] = self._end
        filled(len(blob))
        return True
