"""Whether an acknowledged SetBit is on disk: a plain reader of the fragment
file, run after the server has been killed with SIGKILL (no clean close, so
nothing an acknowledgement ran ahead of is flushed on the way out).

The format is upstream Pilosa's (roaring.go `WriteTo` / `UnmarshalBinary`),
which the program keeps byte for byte:

    u32 cookie 12346 | u32 container count
    count x { u64 key | u32 n - 1 }     count x { u32 offset }
    blocks: n <= 4096 -> n x u32 values; else 1,024 x u64 words
    [0xF7 | u32 len | payload | u32 checksum]      the program's footer
    op log: repeated { u8 type (0 set, 1 clear) | u64 position | u32 fnv32a }

with position = row * 2**20 + column % 2**20, and a side file `<path>.wal`
of op records while a snapshot is being written. Imports struct and numpy
only; takes nothing of the program's.
"""

from __future__ import annotations

import os
import struct
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

COOKIE = 12346
ARRAY_MAX = 4096
FOOTER_TYPE = 0xF7
OP_SIZE = 13
SLICE_WIDTH = 1 << 20


def fnv32a(data: bytes) -> int:
    h = 2166136261
    for b in data:
        h = ((h ^ b) * 16777619) & 0xFFFFFFFF
    return h


def ops_in(data: bytes, off: int = 0) -> List[Tuple[int, int]]:
    """The whole op records from `off` on; stops at a torn or damaged one:
    what follows it was never acknowledged off a finished write."""
    out = []
    while off + OP_SIZE <= len(data):
        body = data[off:off + 9]
        if struct.unpack_from("<I", data, off + 9)[0] != fnv32a(body):
            break
        out.append(struct.unpack("<BQ", body))
        off += OP_SIZE
    return out


def bits_on_disk(path: str, positions: Iterable[int]) -> Dict[int, bool]:
    """Each position's bit as a reader of the file finds it: the snapshot's
    containers, then the op log, then the side log."""
    with open(path, "rb") as f:
        data = f.read()
    cookie, count = struct.unpack_from("<II", data, 0)
    if cookie != COOKIE:
        raise ValueError(f"{path}: not a roaring file")
    where, end = {}, 8 + count * 16
    for i in range(count):
        key, n1 = struct.unpack_from("<QI", data, 8 + i * 12)
        (off,) = struct.unpack_from("<I", data, 8 + count * 12 + i * 4)
        n = n1 + 1
        where[key] = (off, n)
        end = max(end, off + (n * 4 if n <= ARRAY_MAX else 8192))
    out = {}
    for pos in positions:
        key, low = pos >> 16, pos & 0xFFFF
        bit = False
        if key in where:
            off, n = where[key]
            if n <= ARRAY_MAX:
                vals = np.frombuffer(data, "<u4", n, off)
                bit = bool((vals == low).any())
            else:
                (word,) = struct.unpack_from("<Q", data, off + 8 * (low >> 6))
                bit = bool((word >> (low & 63)) & 1)
        out[pos] = bit
    if end < len(data) and data[end] == FOOTER_TYPE:
        end += 5 + struct.unpack_from("<I", data, end + 1)[0] + 4
    ops = ops_in(data, end)
    if os.path.exists(path + ".wal"):
        with open(path + ".wal", "rb") as f:
            ops += ops_in(f.read())
    for typ, pos in ops:
        if pos in out:
            out[pos] = typ == 0
    return out


def lost_writes(frag_path, acked: Sequence[Tuple[int, int]]) -> list:
    """The (row, column) of `acked` SetBits whose bit is not on disk.
    `frag_path(slice)` names a slice's fragment file."""
    by_slice: Dict[int, list] = {}
    for row, col in acked:
        by_slice.setdefault(col // SLICE_WIDTH, []).append(
            (row, col, row * SLICE_WIDTH + col % SLICE_WIDTH))
    lost = []
    for s, wanted in sorted(by_slice.items()):
        path = frag_path(s)
        found = bits_on_disk(path, [p for _, _, p in wanted]) \
            if os.path.exists(path) else {}
        lost += [(row, col) for row, col, p in wanted if not found.get(p)]
    return lost
