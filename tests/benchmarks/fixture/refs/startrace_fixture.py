"""TEST FIXTURE, the reference of `startrace-fixture`: numpy and sets, nothing
of the program. It keeps each user's starred columns, each column's language
where a star can fall, |language row|, and M[user, language] = the user's
stars on repositories of that language, which both directions of the
cross-frame src form read: a row of M ranks `language` filtered by a
`stargazer` row, a column of M ranks `stargazer` filtered by a `language` row.
A `SetBit` into `stargazer` (the harness hands a write's frame as its fifth
field; nothing writes `language`) adds a column to a user's set and 1 to one
cell of M. Columns are written once, so what a write moves is read off the
data as generated.

Keys: ("T", ranked frame, src frame | None, src row | None, n),
("I", (frame, row), ...) for a Count of intersected rows (one of them a
`stargazer` row), ("R", user) for a star's read-back Count.
"""

import itertools
import re
from typing import Dict, List, Optional, Sequence

import numpy as np

from pbench.reference import NOT_JUDGED, WRONG, _plus, _verdict, rank_top

LANGUAGE, STARGAZER = "language", "stargazer"
_LEAF = re.compile(r'Bitmap\(rowID=(\d+), frame="([^"]*)"\)')
_RANKED = re.compile(r'frame="([^"]*)", n=(\d+)\)$')


def key_of(pql: str) -> tuple:
    """The key of a read as the fixture kind binds it: the control's way from
    the PQL it is sent to the tables."""
    leaves = [(f, int(r)) for r, f in _LEAF.findall(pql)]
    if pql.startswith("TopN("):
        ranked, n = _RANKED.search(pql).groups()
        src, row = leaves[0] if leaves else (None, None)
        return ("T", ranked, src, row, int(n))
    if pql.startswith("Count(Intersect("):
        return ("I", *leaves)
    if pql.startswith("Count(Bitmap(") and leaves[0][0] == STARGAZER:
        return ("R", leaves[0][1])
    raise ValueError(f"the fixture reference does not speak {pql[:40]!r}")


class StarTraceReference:
    max_overlap = 10  # a TopN read overlapping more stars is not judged

    def __init__(self, n_languages: int, language_totals: Dict[int, int],
                 stars: Dict[int, frozenset], language_of: Dict[int, int],
                 candidates: Dict[str, Sequence[int]]):
        self.language_totals = language_totals   # language -> |row|
        self.stars = stars                        # user -> columns starred
        self.language_of = language_of   # column -> language, where known
        self._candidates = {f: [int(c) for c in cs]
                            for f, cs in candidates.items()}
        self.m = np.zeros((len(stars), n_languages), dtype=np.int64)
        for u, cols in stars.items():
            for c in cols:
                self.m[u, language_of[c]] += 1

    def candidates(self) -> Dict[str, List[int]]:
        """By written frame: the harness takes an update's from its own."""
        return self._candidates

    def can_write(self, row: int, column: int, frame: str) -> bool:
        return frame == STARGAZER and column not in self.stars[row]

    def live(self) -> "_Live":
        return _Live(self)

    def answer(self, key: tuple):
        return self.live().answer(key)

    def judge(self, reads: Sequence[tuple], writes: Sequence[tuple]) -> list:
        """reads: (key, t_send, t_done, answer); writes: (row, column, t_send,
        t_ack, frame). As `TopNReference.judge`: stars acknowledged before a
        read was sent are applied, stars sent after its reply are not, and of
        those between a count may hold any number, a ranking some subset."""
        ws = sorted(writes, key=lambda w: w[3])
        live, applied = self.live(), 0
        out: List[Optional[tuple]] = [None] * len(reads)
        for i in sorted(range(len(reads)), key=lambda i: reads[i][1]):
            key, t_send, t_done, got = reads[i]
            while applied < len(ws) and ws[applied][3] <= t_send:
                live.set_bit(ws[applied][0], ws[applied][1], ws[applied][4])
                applied += 1
            maybe = [d for d in (live.delta(key, w[0], w[1], w[4])
                                 for w in ws[applied:] if w[2] < t_done) if d]
            counts = live.counts(key)
            if key[0] != "T":
                out[i] = _verdict(got, counts[0], counts[0] + len(maybe))
                continue
            try:
                got = [(int(p["id"]), int(p["count"])) for p in got]
            except (TypeError, KeyError, ValueError):
                out[i] = (WRONG, f"no ranking: {str(got)[:60]}")
                continue
            if len(maybe) > self.max_overlap:
                out[i] = (NOT_JUDGED, f"overlaps {len(maybe)} writes")
            elif not any(got == rank_top(_plus(counts, sub), key[4])
                         for n in range(len(maybe) + 1)
                         for sub in itertools.combinations(maybe, n)):
                out[i] = (WRONG, f"reference {rank_top(counts, key[4])[:3]}"
                                 f" with any of {len(maybe)} writes")
        return out


class _Live:
    """The tables with the stars applied so far; what the control serves."""

    def __init__(self, ref: StarTraceReference):
        self.ref = ref
        self.m = ref.m.copy()
        self.added: Dict[int, set] = {}  # user -> columns starred since

    def _stars(self, u: int):
        return self.ref.stars[u] | self.added.get(u, frozenset())

    def counts(self, key: tuple) -> Dict[int, int]:
        """What the key reads, id -> count: a ranking's rows, or {0: the
        count} of a Count."""
        if key[0] == "R":
            return {0: len(self._stars(key[1]))}
        if key[0] == "I":
            users = [r for f, r in key[1:] if f == STARGAZER]
            langs = {r for f, r in key[1:] if f == LANGUAGE}
            both = frozenset.intersection(*(frozenset(self._stars(u))
                                            for u in users))
            return {0: sum(1 for c in both if len(langs) == 0 or langs ==
                           {self.ref.language_of[c]})}
        _, ranked, src_frame, src, _n = key
        if src is None:
            return (self.ref.language_totals if ranked == LANGUAGE else
                    {u: len(self._stars(u)) for u in self.ref.stars})
        if (src_frame, ranked) == (STARGAZER, LANGUAGE):
            line = self.m[src]
        elif (src_frame, ranked) == (LANGUAGE, STARGAZER):
            line = self.m[:, src]
        else:
            raise KeyError(key)
        return {int(i): int(line[i]) for i in np.flatnonzero(line)}

    def delta(self, key: tuple, row: int, column: int,
              frame: str) -> Dict[int, int]:
        """What SetBit(row, column, frame) on a clear bit adds to `counts(key)`
        as they stand."""
        if frame != STARGAZER:
            raise ValueError(f"nothing writes {frame!r}")
        lang = self.ref.language_of[column]
        if key[0] == "R":
            return {0: 1} if key[1] == row else {}
        if key[0] == "I":
            users = [r for f, r in key[1:] if f == STARGAZER]
            langs = {r for f, r in key[1:] if f == LANGUAGE}
            hit = row in users and langs <= {lang} and all(
                column in self._stars(u) for u in users if u != row)
            return {0: 1} if hit else {}
        _, ranked, src_frame, src, _n = key
        if src is None:
            return {row: 1} if ranked == STARGAZER else {}
        if ranked == LANGUAGE:
            return {lang: 1} if src == row else {}
        return {row: 1} if src == lang else {}

    def set_bit(self, row: int, column: int, frame: str) -> None:
        if frame != STARGAZER:
            raise ValueError(f"nothing writes {frame!r}")
        self.added.setdefault(row, set()).add(column)
        self.m[row, self.ref.language_of[column]] += 1

    def answer(self, key: tuple):
        counts = self.counts(key)
        return rank_top(counts, key[4]) if key[0] == "T" else counts[0]

    def answer_pql(self, pql: str):
        return self.answer(key_of(pql))


def assemble(config: dict, language: np.ndarray,
             stars: Dict[int, np.ndarray],
             candidates: Dict[str, Sequence[int]]) -> StarTraceReference:
    """`language` is every column's language row. Kept of it: each row's
    count, and the language of each column a star lies on or may fall on."""
    n = max(int(f["rows"]) for f in config["frames"]
            if f["name"] == LANGUAGE)
    totals = np.bincount(language, minlength=n)
    known = {int(c) for cols in stars.values() for c in cols}
    known.update(int(c) for cs in candidates.values() for c in cs)
    return StarTraceReference(
        n, {r: int(c) for r, c in enumerate(totals) if c},
        {u: frozenset(int(c) for c in cols) for u, cols in stars.items()},
        {c: int(language[c]) for c in known}, candidates)
