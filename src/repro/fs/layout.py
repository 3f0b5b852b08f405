"""On-disk layout for the xv6-style file system (4 KiB blocks).

    [ 0 | superblock ]
    [ logstart .. logstart+nlog )        write-ahead journal
    [ inodestart .. bmapstart )          inode table
    [ bmapstart .. datastart )           block bitmap
    [ datastart .. size )                data blocks

Inodes carry 12 direct, 1 indirect and 1 double-indirect pointer (the
paper's 4 GB-file extension of stock xv6). Directory entries are fixed
64-byte records.
"""

from __future__ import annotations

import dataclasses
import struct
import zlib
from typing import List, Optional, Sequence, Tuple

BSIZE = 4096
FSMAGIC = 0x10203040
NDIRECT = 12
NINDIRECT = BSIZE // 4  # 1024 u32 pointers per block
MAXFILE_BLOCKS = NDIRECT + NINDIRECT + NINDIRECT * NINDIRECT  # ~4.2 GB

# inode: type u16, nlink u16, pad u32, size u64, addrs (NDIRECT+2) u32
_INODE_FMT = "<HHIQ" + "I" * (NDIRECT + 2)
INODE_SIZE = struct.calcsize(_INODE_FMT)  # 72 bytes
IPB = BSIZE // INODE_SIZE  # inodes per block

T_FREE, T_FILE, T_DIR = 0, 1, 2

DIRENT_SIZE = 64
NAME_MAX = DIRENT_SIZE - 4 - 1  # u32 ino + NUL

# Whiteout sentinel for overlay mounts (fs/overlay.py): a dirent whose ino
# field is this value records "NAME IS DELETED HERE" in a writable upper
# directory, masking a same-named entry in the immutable base below. Plain
# (non-overlay) mounts never create one; their namespace ops skip it like
# a hole but never REUSE its slot for a different name (the overlay's
# delete marker must not be silently evicted by an unrelated create).
WHITEOUT_INO = 0xFFFFFFFF  # u32 max — can never collide with a real ino

# The journal's commit record (repro.fs.journal) is ONE block: a head
# (magic, n, seq), a (home block, checksum) pair per logged block, and in
# the block's last 4 bytes a crc32 of all before it, so a header whose
# write tore is never read as a commit. One transaction therefore holds at
# most LOG_MAX_ENTRIES blocks, and a log is at most that many data blocks
# behind its header block.
LOG_MAGIC = 0x4A524E32     # "JRN2"
LOG_MAGIC_V1 = 0x4A524E4C  # "JRNL": the record before it carried a crc32
LOG_HEAD_FMT = "<III"
LOG_ENTRY_FMT = "<II"
_LOG_CRC_OFF = BSIZE - 4
LOG_MAX_ENTRIES = ((_LOG_CRC_OFF - struct.calcsize(LOG_HEAD_FMT))
                   // struct.calcsize(LOG_ENTRY_FMT))  # 510
NLOG_MAX = 1 + LOG_MAX_ENTRIES
NLOG_MIN = 64


@dataclasses.dataclass
class SuperBlock:
    magic: int
    size: int  # total blocks
    nlog: int
    logstart: int
    ninodes: int
    inodestart: int
    bmapstart: int
    datastart: int

    _FMT = "<8I"

    def pack(self) -> bytes:
        raw = struct.pack(self._FMT, self.magic, self.size, self.nlog,
                          self.logstart, self.ninodes, self.inodestart,
                          self.bmapstart, self.datastart)
        return raw + b"\0" * (BSIZE - len(raw))

    @classmethod
    def unpack(cls, raw: bytes) -> "SuperBlock":
        vals = struct.unpack_from(cls._FMT, raw)
        return cls(*vals)


def pack_log_record(seq: int, entries: Sequence[Tuple[int, int]]) -> bytes:
    """The commit record, one block, of a transaction whose logged blocks
    have these (home block, checksum) ``entries``."""
    assert 0 < len(entries) <= LOG_MAX_ENTRIES, len(entries)
    raw = bytearray(BSIZE)
    struct.pack_into(LOG_HEAD_FMT, raw, 0, LOG_MAGIC, len(entries), seq)
    off, step = struct.calcsize(LOG_HEAD_FMT), struct.calcsize(LOG_ENTRY_FMT)
    for i, (home, cks) in enumerate(entries):
        struct.pack_into(LOG_ENTRY_FMT, raw, off + step * i, home, cks)
    struct.pack_into("<I", raw, _LOG_CRC_OFF, zlib.crc32(raw[:_LOG_CRC_OFF]))
    return bytes(raw)


def unpack_log_record(raw: bytes, sb: SuperBlock
                      ) -> Optional[List[Tuple[int, int]]]:
    """The (home block, checksum) entries of the commit record in the
    header block ``raw``, or None where it holds no whole one: a cleared
    header, or one whose write tore. A record of the format before the
    crc32 (LOG_MAGIC_V1) is still read, so a log written then replays."""
    magic, n, _seq = struct.unpack_from(LOG_HEAD_FMT, raw)
    if magic == LOG_MAGIC:
        (crc,) = struct.unpack_from("<I", raw, _LOG_CRC_OFF)
        if zlib.crc32(raw[:_LOG_CRC_OFF]) != crc:
            return None
    elif magic != LOG_MAGIC_V1:
        return None
    if not 0 < n <= sb.nlog - 1:
        return None
    off, step = struct.calcsize(LOG_HEAD_FMT), struct.calcsize(LOG_ENTRY_FMT)
    entries = list(struct.iter_unpack(LOG_ENTRY_FMT,
                                      raw[off: off + step * n]))
    # the journal logs inode, bitmap and data blocks only: a home outside
    # them is a header torn over zeros, never a commit to replay
    if not all(sb.inodestart <= home < sb.size for home, _cks in entries):
        return None
    return entries


@dataclasses.dataclass
class DiskInode:
    type: int = T_FREE
    nlink: int = 0
    size: int = 0
    addrs: List[int] = dataclasses.field(
        default_factory=lambda: [0] * (NDIRECT + 2))

    def pack(self) -> bytes:
        return struct.pack(_INODE_FMT, self.type, self.nlink, 0, self.size,
                           *self.addrs)

    @classmethod
    def unpack(cls, raw: bytes, off: int = 0) -> "DiskInode":
        vals = struct.unpack_from(_INODE_FMT, raw, off)
        return cls(type=vals[0], nlink=vals[1], size=vals[3],
                   addrs=list(vals[4:]))


def pack_dirent(ino: int, name: str) -> bytes:
    nb = name.encode()
    assert 0 < len(nb) <= NAME_MAX, name
    return struct.pack("<I", ino) + nb + b"\0" * (DIRENT_SIZE - 4 - len(nb))


def unpack_dirent(raw: bytes, off: int):
    (ino,) = struct.unpack_from("<I", raw, off)
    name = raw[off + 4: off + DIRENT_SIZE].split(b"\0", 1)[0].decode()
    return ino, name


def log_blocks(n_blocks: int) -> int:
    """The log mkfs gives a device of ``n_blocks``: a 256th of the device,
    as mke2fs sizes an ext4 journal from its device, between NLOG_MIN
    (every device up to 16,384 blocks) and NLOG_MAX."""
    return min(NLOG_MAX, max(NLOG_MIN, n_blocks // 256))


def geometry(n_blocks: int, ninodes: int = 4096,
             nlog: Optional[int] = None) -> SuperBlock:
    """The layout of a device of ``n_blocks``; ``nlog`` None sizes the
    log from the device (``log_blocks``)."""
    if nlog is None:
        nlog = log_blocks(n_blocks)
    if not 2 <= nlog <= NLOG_MAX:
        raise ValueError(
            f"nlog {nlog}: a log is its header block and 1 to "
            f"{LOG_MAX_ENTRIES} data blocks, the most one commit record "
            "can name")
    logstart = 1
    inodestart = logstart + nlog
    ninodeblocks = (ninodes + IPB - 1) // IPB
    bmapstart = inodestart + ninodeblocks
    nbmap = (n_blocks + BSIZE * 8 - 1) // (BSIZE * 8)
    datastart = bmapstart + nbmap
    return SuperBlock(FSMAGIC, n_blocks, nlog, logstart, ninodes,
                      inodestart, bmapstart, datastart)
