"""rec2: raw page-aligned CSR block framing — the zero-copy rec format.

The v1 rec cache stored each CSR block as an ``.npz`` member: correct,
but every read pays the zip central-directory walk plus a full memcpy of
each array out of the archive, and the bytes can never be mapped. rec2
replaces that with the layout the reference's recordio/CRB fast path
implies (src/reader/crb_parser.h:16-47, src/data/compressed_row_block.h)
minus the LZ4 (uncompressed members already won the zlib-vs-raw
trade): a fixed little-endian header,
a section table, and page-aligned raw array sections, so a reader
``mmap``s the file and wraps each section with ``np.frombuffer`` —
**zero copies until the bytes are actually consumed**, and the OS page
cache (not Python) is the read path. A producer worker can memcpy a
mapped section straight into a shm-ring slot, or skip the copy entirely
for same-host consumers.

Layout (all little-endian)::

    [0]   magic  b"DFREC2\\0\\0"                      8 bytes
    [8]   u32 version (=1) | u32 n_sections
    [16]  u32 header_crc32 (over the section table) | u32 pad
    [24]  n_sections x section entry (32 bytes each):
              name   8 bytes (ascii, NUL padded)
              dtype  8 bytes (numpy dtype str, e.g. b"<i8")
              u64    byte offset (page-aligned, from file start)
              u64    nbytes
    [..]  u32 crc32 per section (n_sections x 4, the data checksums)
    [..]  sections, each aligned to PAGE (4096)

Integrity: the header CRC covers the section table, and every section
carries its own CRC32 (zlib.crc32 — C speed, one pass). ``read_rec2``
validates structure on every open and (by default) the section CRCs,
raising a typed :class:`RecCorrupt` on truncation, bit flips, or a bad
magic — never a crash or a silent short read, mirroring the checkpoint
``CheckpointCorrupt`` contract (store/local.py). A torn write cannot be
observed at the final name: writes go through tmp + atomic rename.

Chaos: every read traverses the ``rec.read`` fault-injection point
(utils/faultinject.py): ``err`` raises RecCorrupt (what a failed disk
read becomes), ``truncate`` reads a half-length view (which the CRC then
rejects — the torn-file drill).
"""

from __future__ import annotations

import io
import mmap
import os
import struct
import zlib
from typing import Dict, Optional

import numpy as np

from ..utils import stream

MAGIC = b"DFREC2\0\0"
VERSION = 1
PAGE = 4096
SUFFIX = ".rec2"

_HEAD = struct.Struct("<8sIIII")       # magic, version, n_sections, crc, pad
_SECT = struct.Struct("<8s8sQQ")       # name, dtype, offset, nbytes

# the only arrays a rec2 member may carry (rec.py's block schema); a name
# outside this set fails loudly instead of silently round-tripping junk
SECTION_NAMES = ("offset", "label", "index", "value", "weight", "uniq")


class RecCorrupt(ValueError):
    """A rec2 member failed structural or checksum validation (torn
    write, truncation, bit flip). Typed so callers can walk to the next
    member or re-convert instead of crashing — the data-cache analog of
    store.local.CheckpointCorrupt."""


def _align(n: int) -> int:
    return (n + PAGE - 1) // PAGE * PAGE


def write_rec2(uri: str, arrays: Dict[str, np.ndarray]) -> None:
    """Atomically write ``arrays`` as one rec2 member (tmp + rename
    locally; tmp key + server-side move for remote URIs)."""
    names = list(arrays)
    for n in names:
        if n not in SECTION_NAMES:
            raise ValueError(f"unknown rec2 section {n!r} "
                             f"(one of {SECTION_NAMES})")
    header_len = _HEAD.size + len(names) * _SECT.size + len(names) * 4
    off = _align(header_len)
    entries = []
    crcs = []
    mats = []
    for n in names:
        a = np.ascontiguousarray(arrays[n])
        mats.append(a)
        entries.append((n.encode().ljust(8, b"\0"),
                        a.dtype.str.encode().ljust(8, b"\0"),
                        off, a.nbytes))
        crcs.append(zlib.crc32(a.data))
        off = _align(off + a.nbytes)
    table = b"".join(_SECT.pack(*e) for e in entries) \
        + b"".join(struct.pack("<I", c) for c in crcs)
    head = _HEAD.pack(MAGIC, VERSION, len(names), zlib.crc32(table), 0)

    def emit(f) -> None:
        f.write(head)
        f.write(table)
        pos = len(head) + len(table)
        for (_, _, o, _), a in zip(entries, mats):
            f.write(b"\0" * (o - pos))
            f.write(a.data)
            pos = o + a.nbytes

    if stream.is_remote(uri):
        buf = io.BytesIO()
        emit(buf)
        tmp = uri + ".tmp"
        with stream.open_stream(tmp, "wb") as f:
            f.write(buf.getvalue())
        fs, path = stream._fs(uri)
        _, tmp_path = stream._fs(tmp)
        try:
            fs.mv(tmp_path, path)
        except (AttributeError, NotImplementedError):  # pragma: no cover
            fs.copy(tmp_path, path)
            fs.rm(tmp_path)
        return
    path = stream._strip_file_scheme(uri)
    stream._ensure_parent(path)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        emit(f)
    os.replace(tmp, path)


def _corrupt(uri: str, why: str) -> RecCorrupt:
    return RecCorrupt(f"corrupt rec2 member {uri!r}: {why}")


def read_rec2(uri: str, verify: bool = True,
              use_mmap: bool = True) -> Dict[str, np.ndarray]:
    """Read one rec2 member -> {name: array}. Local reads mmap the file
    and return zero-copy ``np.frombuffer`` views over the mapping (the
    mapping's lifetime rides the arrays' ``base``); remote URIs read the
    bytes once and view those. Structural validation always runs;
    ``verify`` additionally checks every section CRC (one zlib.crc32
    pass per section — C speed, and the pass doubles as page-cache
    warming for the consumer that reads the bytes next)."""
    from ..utils import faultinject
    kind = faultinject.fire("rec.read")
    if kind == "err":  # pragma: no cover - fire() raises for err itself
        raise _corrupt(uri, "injected read error")
    if stream.is_remote(uri) or not use_mmap:
        with stream.open_stream(uri, "rb") as f:
            buf: memoryview = memoryview(f.read())
    else:
        path = stream._strip_file_scheme(uri)
        try:
            with open(path, "rb") as f:
                try:
                    mm = mmap.mmap(f.fileno(), 0, access=mmap.ACCESS_READ)
                except ValueError as e:  # zero-length file
                    raise _corrupt(uri, f"unmappable ({e})") from e
        except OSError as e:
            if isinstance(e, FileNotFoundError):
                raise
            raise _corrupt(uri, f"unreadable ({e})") from e
        buf = memoryview(mm)
    if kind == "truncate":
        buf = buf[:max(len(buf) // 2, 1)]
    elif kind is not None:
        faultinject.act_default(kind)
    try:
        return _parse(uri, buf, verify)
    except struct.error as e:
        raise _corrupt(uri, f"short header ({e})") from e


def _parse(uri: str, buf: memoryview, verify: bool) -> Dict[str, np.ndarray]:
    if len(buf) < _HEAD.size:
        raise _corrupt(uri, f"file too short ({len(buf)} bytes)")
    magic, version, n_sections, head_crc, _ = _HEAD.unpack_from(buf, 0)
    if magic != MAGIC:
        raise _corrupt(uri, f"bad magic {magic!r}")
    if version != VERSION:
        raise _corrupt(uri, f"unsupported version {version}")
    if not 0 < n_sections <= len(SECTION_NAMES):
        raise _corrupt(uri, f"implausible section count {n_sections}")
    table_len = n_sections * _SECT.size + n_sections * 4
    if len(buf) < _HEAD.size + table_len:
        raise _corrupt(uri, "truncated section table")
    table = bytes(buf[_HEAD.size:_HEAD.size + table_len])
    if zlib.crc32(table) != head_crc:
        raise _corrupt(uri, "section table checksum mismatch")
    crc_base = _HEAD.size + n_sections * _SECT.size
    out: Dict[str, np.ndarray] = {}
    for i in range(n_sections):
        name_b, dtype_b, off, nbytes = _SECT.unpack_from(
            buf, _HEAD.size + i * _SECT.size)
        name = name_b.rstrip(b"\0").decode("ascii", "replace")
        if name not in SECTION_NAMES:
            raise _corrupt(uri, f"unknown section {name!r}")
        try:
            dt = np.dtype(dtype_b.rstrip(b"\0").decode("ascii", "replace"))
        except TypeError as e:
            raise _corrupt(uri, f"bad dtype for {name!r} ({e})") from e
        if off % PAGE or off + nbytes > len(buf):
            raise _corrupt(
                uri, f"section {name!r} [{off}, {off + nbytes}) outside "
                f"file of {len(buf)} bytes")
        if dt.itemsize == 0 or nbytes % dt.itemsize:
            raise _corrupt(uri, f"section {name!r} nbytes {nbytes} not a "
                           f"multiple of dtype {dt.str}")
        view = buf[off:off + nbytes]
        if verify:
            want, = struct.unpack_from("<I", buf, crc_base + 4 * i)
            if zlib.crc32(view) != want:
                raise _corrupt(uri, f"section {name!r} checksum mismatch")
        out[name] = np.frombuffer(view, dtype=dt)
    return out


def is_rec2(uri: str) -> bool:
    return uri.endswith(SUFFIX)


def probe_rec2(uri: str) -> Optional[Dict[str, np.ndarray]]:
    """read_rec2 that returns None instead of raising on corruption —
    for callers that walk to the next member."""
    try:
        return read_rec2(uri)
    except RecCorrupt:
        return None
