"""The plain reference: numpy popcounts over the generated words, with the
acknowledged writes applied. Imports numpy only; takes nothing the program
has made.

Counts. Every answer the traffic can ask of a dense frame of R rows is
tabulated at generation, per key:

    ("R", r)        |row r|
    ("I", a, b)     |a & b|,  a < b         ("U", a, b)   |a | b|,  a < b
    ("D", a, b)     |a & ~b|, ordered       ("IA",) ("UA",) all R rows
    ("DA", m)       |m & ~(every other row)|

A `SetBit(row, column)` changes one column. Every answer is a sum over columns
of a 0/1 function of that column's R row bits, so a write at a column whose R
bits were kept at generation moves every tabulated answer by -1, 0 or +1, and
writes at distinct columns add up. `CountReference` keeps the base table and
the kept bits and never looks at the words again.

Under concurrent clients a read that overlaps a write in time may or may not
see it: the expected answer is then a range. A write acknowledged before the
read was sent is always in it (the configuration's guarantee); one sent after
the reply came is never.
"""

from __future__ import annotations

import bisect
import itertools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np


def count_keys(n_rows: int) -> List[tuple]:
    rows = range(n_rows)
    keys: List[tuple] = [("R", r) for r in rows]
    pairs = list(itertools.combinations(rows, 2))
    keys += [("I", a, b) for a, b in pairs]
    keys += [("U", a, b) for a, b in pairs]
    keys += [("D", a, b) for a in rows for b in rows if a != b]
    keys += [("IA",), ("UA",)]
    keys += [("DA", m) for m in rows]
    return keys


def _pc(x) -> int:
    return int(np.bitwise_count(x).sum())


def slice_counts(words: np.ndarray, n_rows: int) -> np.ndarray:
    """One slice's share of every key, in `count_keys` order. `words` holds
    the slice's rows as (n_rows, anything) uint64."""
    w = words.reshape(n_rows, -1)
    out = []
    for key in count_keys(n_rows):
        k = key[0]
        if k == "R":
            out.append(_pc(w[key[1]]))
        elif k == "I":
            out.append(_pc(w[key[1]] & w[key[2]]))
        elif k == "U":
            out.append(_pc(w[key[1]] | w[key[2]]))
        elif k == "D":
            out.append(_pc(w[key[1]] & ~w[key[2]]))
        elif k == "IA":
            out.append(_pc(np.bitwise_and.reduce(w, axis=0)))
        elif k == "UA":
            out.append(_pc(np.bitwise_or.reduce(w, axis=0)))
        else:  # "DA"
            m = key[1]
            others = np.bitwise_or.reduce(np.delete(w, m, axis=0), axis=0)
            out.append(_pc(w[m] & ~others))
    return np.asarray(out, dtype=np.int64)


def bit_eval(key: tuple, bits: Sequence[int]) -> int:
    """The key's 0/1 function of one column's row bits."""
    k = key[0]
    if k == "R":
        return int(bits[key[1]])
    if k == "I":
        return int(bits[key[1]] and bits[key[2]])
    if k == "U":
        return int(bits[key[1]] or bits[key[2]])
    if k == "D":
        return int(bits[key[1]] and not bits[key[2]])
    if k == "IA":
        return int(all(bits))
    if k == "UA":
        return int(any(bits))
    m = key[1]  # "DA"
    return int(bits[m] and not any(b for i, b in enumerate(bits) if i != m))


def column_bits(words: np.ndarray, n_rows: int, local: int) -> List[int]:
    """The R row bits of column `local` (0 .. 2**20 - 1) of one slice whose
    rows are (n_rows * 16, 1024) uint64 container words."""
    block, within = local >> 16, local & 0xFFFF
    word, bit = within >> 6, within & 63
    return [int((int(words[r * 16 + block, word]) >> bit) & 1)
            for r in range(n_rows)]


class CountReference:
    """Base table + kept column bits; answers with writes applied."""

    def __init__(self, n_rows: int, base: np.ndarray,
                 kept: Dict[int, Sequence[int]]):
        self.n_rows = n_rows
        self.keys = count_keys(n_rows)
        self.index = {k: i for i, k in enumerate(self.keys)}
        self.base = np.asarray(base, dtype=np.int64)
        self.kept = kept  # column -> its R bits as generated

    def delta(self, row: int, column: int) -> np.ndarray:
        """What SetBit(row, column) adds to every key. Columns are written
        once, so the bits before are the generated ones."""
        before = list(self.kept[column])
        after = list(before)
        after[row] = 1
        return np.asarray([bit_eval(k, after) - bit_eval(k, before)
                           for k in self.keys], dtype=np.int64)

    def judge(self, reads: Sequence[tuple], writes: Sequence[tuple]) -> list:
        """reads: (key, t_send, t_done, answer); writes: (row, column, t_send,
        t_ack) of acknowledged SetBits. Returns one (lo, hi) per read: the
        answers a linearizable index could have given."""
        ws = sorted(writes, key=lambda w: w[3])
        acks = [w[3] for w in ws]
        deltas = [self.delta(w[0], w[1]) for w in ws]
        prefix = np.zeros((len(ws) + 1, len(self.keys)), dtype=np.int64)
        if ws:
            prefix[1:] = np.cumsum(np.stack(deltas), axis=0)
        out = []
        for key, t_send, t_done, _ in reads:
            i = self.index[key]
            k = bisect.bisect_right(acks, t_send)  # acked before the send
            lo = hi = int(self.base[i] + prefix[k, i])
            for j in range(k, len(ws)):
                if ws[j][2] < t_done:  # sent before the reply: may be seen
                    d = int(deltas[j][i])
                    lo, hi = lo + min(d, 0), hi + max(d, 0)
            out.append((lo, hi))
        return out


# -- TopN --------------------------------------------------------------------


def container_words(values: Optional[np.ndarray],
                    bitmap: Optional[np.ndarray]) -> np.ndarray:
    """A container as 1,024 uint64 words, bit i at word i >> 6, bit i & 63."""
    if bitmap is not None:
        return bitmap
    bits = np.zeros(65536, dtype=np.uint8)
    bits[values] = 1
    return np.packbits(bits, bitorder="little").view(np.uint64)


def rank_top(counts: Dict[int, int], n: int) -> List[Tuple[int, int]]:
    """Exact TopN: rows whose count is at least 1, by count descending and
    row id ascending, the first n."""
    pairs = sorted(((r, c) for r, c in counts.items() if c >= 1),
                   key=lambda rc: (-rc[1], rc[0]))
    return pairs[:n] if n else pairs


class TopNReference:
    def __init__(self, totals: Dict[int, int],
                 by_src: Dict[int, Dict[int, int]]):
        self.totals = totals    # row -> |row|
        self.by_src = by_src    # src row -> {row -> |row & src|}

    def answer(self, key: tuple) -> List[Tuple[int, int]]:
        _, src, n = key
        counts = self.totals if src is None else self.by_src.get(src, {})
        return rank_top(counts, n)
