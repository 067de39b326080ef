"""`topn`'s reference with `can_write` turned round: a write is an insert. It
is granted where the row holds no container in the column's 65,536-column
block of its slice (in a slice's first block: the row is absent from the
slice; in the other fifteen the recipe puts nothing), and each such (row,
slice, block) once, so every bound SetBit creates a container. Everything
else is `TopNReference`'s: a SetBit on a clear bit adds 1 to |row|."""

import collections
from typing import Sequence

import numpy as np

from ..reference import TopNReference


class TopNIngestReference(TopNReference):
    def __init__(self, *args, **kw):
        super().__init__(*args, **kw)
        self._granted = set()  # (row, slice, block): a container each
        self._made = collections.Counter()  # row -> containers granted it

    @staticmethod
    def slice_part(frame: dict, rows, words: np.ndarray,
                   locals_: Sequence[int], src_rows=()) -> dict:
        """`words` holds the slice's first block, the only one with bits: a
        column past it has no row set as generated."""
        held = words.shape[1] * 64
        part = TopNReference.slice_part(
            frame, rows, words, [c for c in locals_ if c < held], src_rows)
        part["kept"].update({int(c): [] for c in locals_ if c >= held})
        return part

    def can_write(self, row: int, column: int) -> bool:
        """Answers true once for a container: `Plan.assign_columns` takes the
        column whenever it does."""
        s, block = column >> 20, (column >> 16) & 15
        made = (row, s, block)
        if made in self._granted or (block == 0
                                     and row in self.present.get(s, ())):
            return False
        self._granted.add(made)
        self._made[row] += 1
        return True

    def bytes_needed(self, key: tuple) -> int:
        """`TopNReference`'s, and each created container as roaring holds it:
        an array of one value, 2 B. Every insert the plan bound is counted,
        sent or not: under 5 KB beside the 1.18 GB a TopN reads."""
        made = self._made[key[1]] if key[0] == "R" else len(self._granted)
        return super().bytes_needed(key) + 2 * made


slice_part, assemble = (TopNIngestReference.slice_part,
                        TopNIngestReference.assemble)
