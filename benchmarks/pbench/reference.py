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

TopN. `TopNReference` tabulates |row| and, for every src row the traffic
names, |row & src|, and keeps for each column a run may write which rows are
set there as generated; a `SetBit` then moves those tables exactly. A ranking
has no range: a TopN read that overlaps k writes is right if it equals the
exact ranking with some subset of the k applied.

What the harness asks of a reference, whichever it is (the module
`pbench/refs/<name>.py` of a configuration's `correctness.reference`):

    slice_part(frame, rows, words, locals_, src_rows)   one slice's share of
        the tables, in a worker: `words` is (len(rows), W) uint64, row i of it
        the bits of row id rows[i] over the slice's first W * 64 columns
    assemble(frame, parts, candidates, weight=1)  -> the reference
    ref.candidates()        columns a run may write, in the order it takes them
    ref.can_write(row, column)   whether SetBit(row, column) changes a bit and
                                 creates no container
    ref.judge(reads, writes)     one verdict per read: None (right), or
                                 (WRONG | NOT_JUDGED, what was expected)
    ref.live()              the tables with set_bit(row, column) and
                            answer(key): what the control serves from
    ref.max_overlap         only on a reference that can answer NOT_JUDGED:
                            the run then compares `not_judged`, limit 0
    ref.bytes_needed(key)   HBM bytes a device-answered read of that key has
                            to move (the rooflines' numerator)
    ref.memo_account(key)   (/debug/vars counter, "hits" | "misses"): the
                            program's count that the harness's account of
                            whole-query memo hits among such reads is held to
"""

from __future__ import annotations

import bisect
import itertools
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .layers import container_bytes_needed, read_bytes_needed

WRONG, NOT_JUDGED = "wrong", "not_judged"


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


def bits_at(words: np.ndarray, local: int) -> np.ndarray:
    """Column `local`'s bit in each row of `words`, (rows, W) uint64 with
    column c at word c >> 6, bit c & 63."""
    return ((words[:, local >> 6] >> np.uint64(local & 63))
            & np.uint64(1)).astype(np.int64)


def _verdict(got, lo: int, hi: int) -> Optional[tuple]:
    if isinstance(got, int) and lo <= got <= hi:
        return None
    return (WRONG, f"reference [{lo}, {hi}]")


class CountReference:
    """Base table + kept column bits; answers with writes applied."""

    def __init__(self, n_rows: int, base: np.ndarray,
                 kept: Dict[int, Sequence[int]], candidates=(),
                 slices: int = 0):
        self.n_rows, self.slices = n_rows, slices
        self.keys = count_keys(n_rows)
        self.index = {k: i for i, k in enumerate(self.keys)}
        self.base = np.asarray(base, dtype=np.int64)
        self.kept = kept  # column -> its R bits as generated
        self._candidates = [int(c) for c in candidates]

    @staticmethod
    def slice_part(frame: dict, rows, words: np.ndarray,
                   locals_: Sequence[int], src_rows=()) -> dict:
        """Needs every row of the frame in `words`, in row order."""
        n_rows = int(frame["rows"])
        return {"counts": slice_counts(words, n_rows),
                "kept": {int(c): [int(b) for b in bits_at(words, int(c))]
                         for c in locals_}}

    @classmethod
    def assemble(cls, frame: dict, parts: Sequence[dict], candidates,
                 weight: int = 1) -> "CountReference":
        kept: dict = {}
        for s, p in parts:
            kept.update({(s << 20) + c: bits for c, bits in p["kept"].items()})
        base = weight * np.sum([p["counts"] for _, p in parts], axis=0)
        return cls(int(frame["rows"]), base, kept, candidates,
                   slices=weight * len(parts))

    def candidates(self) -> List[int]:
        return self._candidates

    def can_write(self, row: int, column: int) -> bool:
        """A dense row holds every container: any clear bit will do."""
        return not self.kept[column][row]

    def delta(self, row: int, column: int) -> np.ndarray:
        """What SetBit(row, column) adds to every key. Columns are written
        once, so the bits before are the generated ones."""
        before = list(self.kept[column])
        after = list(before)
        after[row] = 1
        return np.asarray([bit_eval(k, after) - bit_eval(k, before)
                           for k in self.keys], dtype=np.int64)

    def ranges(self, reads: Sequence[tuple], writes: Sequence[tuple]) -> list:
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

    def judge(self, reads: Sequence[tuple], writes: Sequence[tuple]) -> list:
        return [_verdict(r[3], lo, hi)
                for r, (lo, hi) in zip(reads, self.ranges(reads, writes))]

    def live(self) -> "_LiveCounts":
        return _LiveCounts(self)

    def bytes_needed(self, key: tuple) -> int:
        return read_bytes_needed(key, self.n_rows, self.slices)

    def memo_account(self, key: tuple) -> Tuple[str, str]:
        """A Count asks the host's query cache, which counts its hits."""
        return "host_cache.query_hit", "hits"


class _LiveCounts:
    def __init__(self, ref: CountReference):
        self.ref = ref
        self.applied = np.zeros(len(ref.keys), dtype=np.int64)

    def set_bit(self, row: int, column: int) -> None:
        self.applied += self.ref.delta(row, column)

    def answer(self, key: tuple) -> int:
        i = self.ref.index[key]
        return int(self.ref.base[i] + self.applied[i])


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
    """|row| and |row & src| with the acknowledged writes applied; also
    ("R", r), an update's read-back Count(Bitmap(r)), as a range."""

    max_overlap = 10  # a TopN read overlapping more writes is not judged

    def __init__(self, totals: Dict[int, int],
                 by_src: Dict[int, Dict[int, int]],
                 kept: Optional[Dict[int, frozenset]] = None,
                 present: Optional[Dict[int, frozenset]] = None,
                 candidates=(), row_bytes: Optional[Dict[int, int]] = None):
        self.totals = totals    # row -> |row|
        self.by_src = by_src    # src row -> {row -> |row & src|}
        self.kept = kept or {}        # column -> rows set there as generated
        self.present = present or {}  # slice -> rows with a container there
        self._candidates = [int(c) for c in candidates]
        self.row_bytes = row_bytes or {}  # row -> bytes of its containers

    @staticmethod
    def slice_part(frame: dict, rows, words: np.ndarray,
                   locals_: Sequence[int], src_rows=()) -> dict:
        rows = np.asarray(rows, dtype=np.int64)
        src = np.flatnonzero(np.isin(rows, np.asarray(src_rows, np.int64)))
        inter = np.zeros((len(src), len(rows)), dtype=np.int32)
        for k, i in enumerate(src):
            inter[k] = np.bitwise_count(words & words[i]).sum(axis=1)
        return {"rows": rows,
                "counts": np.bitwise_count(words).sum(axis=1).astype(np.int64),
                "src": rows[src], "inter": inter,
                "kept": {int(c): rows[bits_at(words, int(c)) > 0].tolist()
                         for c in locals_}}

    @classmethod
    def assemble(cls, frame: dict, parts: Sequence[tuple], candidates,
                 weight: int = 1) -> "TopNReference":
        n_rows = int(frame["rows"])
        totals = np.zeros(n_rows, dtype=np.int64)
        srcs = sorted({int(x) for _, p in parts for x in p["src"]})
        at = {x: k for k, x in enumerate(srcs)}
        acc = np.zeros((len(srcs), n_rows), dtype=np.int64)
        kept, present = {}, {}
        row_bytes = np.zeros(n_rows, dtype=np.int64)
        for s, p in parts:
            totals[p["rows"]] += weight * p["counts"]
            # One container a (row, slice): its cardinality is the row's count.
            np.add.at(row_bytes, p["rows"], [
                weight * container_bytes_needed(c) for c in p["counts"]])
            if len(p["src"]):
                acc[np.ix_([at[int(x)] for x in p["src"]],
                           p["rows"])] += weight * p["inter"]
            present[s] = frozenset(p["rows"].tolist())
            kept.update({(s << 20) + c: frozenset(r)
                         for c, r in p["kept"].items()})
        return cls({r: int(c) for r, c in enumerate(totals) if c},
                   {x: {r: int(c) for r, c in enumerate(acc[k]) if c}
                    for x, k in at.items()},
                   kept, present, candidates,
                   {r: int(b) for r, b in enumerate(row_bytes) if b})

    def candidates(self) -> List[int]:
        return self._candidates

    def can_write(self, row: int, column: int) -> bool:
        """The bit is clear and the row has a container in that slice
        already: an update of an existing record, never an insert."""
        return (row in self.present.get(column >> 20, ())
                and row not in self.kept[column])

    def answer(self, key: tuple):
        """The answer as generated, no write applied."""
        return _answer(self.totals, self.by_src, key)

    def bytes_needed(self, key: tuple) -> int:
        """A TopN counts every row: each generated container once, as roaring
        holds it, and the src row's again for the src form; a read-back
        Count(Bitmap(r)) reads its row's."""
        one = self.row_bytes.get(key[1], 0) if key[1] is not None else 0
        return one if key[0] == "R" else sum(self.row_bytes.values()) + one

    def memo_account(self, key: tuple) -> Tuple[str, str]:
        """A Count asks the host's query cache (hits counted); a TopN asks the
        mesh's limb memo, twice a query, so what is counted there is the
        stores: the TopNs that went to the device."""
        if key[0] == "R":
            return "host_cache.query_hit", "hits"
        return "mesh.memo_store", "misses"

    def live(self) -> "_LiveTopN":
        return _LiveTopN(self)

    def delta(self, key: tuple, row: int, column: int) -> Dict[int, int]:
        """What SetBit(row, column) on a clear bit adds to the counts `key`
        ranks (for ("R", r): to that one count). Columns are written once,
        so the rows set at the column are the generated ones."""
        kind, src = key[0], key[1]
        if kind == "R":
            return {row: 1} if row == src else {}
        if src is None or (src != row and src in self.kept[column]):
            return {row: 1}
        if src == row:
            return {x: 1 for x in (*self.kept[column], row)}
        return {}

    def judge(self, reads: Sequence[tuple], writes: Sequence[tuple]) -> list:
        """reads: (key, t_send, t_done, answer); writes: (row, column, t_send,
        t_ack). Writes acknowledged before a read was sent are applied, writes
        sent after its reply are not; of the k that can move the answer in
        between, some subset has to give it exactly."""
        ws = sorted(writes, key=lambda w: w[3])
        live, applied = self.live(), 0
        out: List[Optional[tuple]] = [None] * len(reads)
        for i in sorted(range(len(reads)), key=lambda i: reads[i][1]):
            key, t_send, t_done, got = reads[i]
            while applied < len(ws) and ws[applied][3] <= t_send:
                live.set_bit(ws[applied][0], ws[applied][1])
                applied += 1
            maybe = [d for d in (self.delta(key, w[0], w[1])
                                 for w in ws[applied:] if w[2] < t_done) if d]
            counts = live.counts(key)
            if key[0] == "R":
                lo = counts.get(key[1], 0)
                out[i] = _verdict(got, lo, lo + len(maybe))
                continue
            try:
                got = [(int(p["id"]), int(p["count"])) for p in got]
            except (TypeError, KeyError, ValueError):
                out[i] = (WRONG, f"no ranking: {str(got)[:60]}")
                continue
            if len(maybe) > self.max_overlap:
                out[i] = (NOT_JUDGED, f"overlaps {len(maybe)} writes")
            elif not any(got == rank_top(_plus(counts, sub), key[2])
                         for n in range(len(maybe) + 1)
                         for sub in itertools.combinations(maybe, n)):
                out[i] = (WRONG, f"reference {rank_top(counts, key[2])[:3]}"
                                 f" with any of {len(maybe)} writes")
        return out


def _counts(totals: dict, by_src: dict, key: tuple) -> Dict[int, int]:
    """The counts `key` reads: |row| for ("R", r) and the plain ranking,
    |row & src| for the src form."""
    if key[0] == "R" or key[1] is None:
        return totals
    return by_src.get(key[1], {})


def _answer(totals: dict, by_src: dict, key: tuple):
    counts = _counts(totals, by_src, key)
    return counts.get(key[1], 0) if key[0] == "R" else rank_top(counts,
                                                               key[2])


def _plus(counts: Dict[int, int], deltas: Iterable[Dict[int, int]]) -> dict:
    if not deltas:
        return counts
    out = dict(counts)
    for d in deltas:
        for r, n in d.items():
            out[r] = out.get(r, 0) + n
    return out


class _LiveTopN:
    def __init__(self, ref: TopNReference):
        self.ref = ref
        self.totals = dict(ref.totals)
        self.by_src = {x: dict(row) for x, row in ref.by_src.items()}

    def counts(self, key: tuple) -> Dict[int, int]:
        return _counts(self.totals, self.by_src, key)

    def set_bit(self, row: int, column: int) -> None:
        for src, counts in ((None, self.totals), *self.by_src.items()):
            for r, n in self.ref.delta(("T", src), row, column).items():
                counts[r] = counts.get(r, 0) + n

    def answer(self, key: tuple):
        return _answer(self.totals, self.by_src, key)

