"""View: orientation/time variant of a frame, owning fragments by slice.

Parity with /root/reference/view.go: "standard" and "inverse" base views
plus time-quantum views ("standard_2017", ...); fragments are created
lazily, and creating a fragment at a new max slice notifies the cluster
(CreateSliceMessage broadcast, view.go:236-246).
"""

from __future__ import annotations

import os
import re
import threading
from typing import Dict, Optional

from .. import SLICE_WIDTH
from .cache import CACHE_TYPE_RANKED, DEFAULT_CACHE_SIZE
from .fragment import Fragment, MUTATION_EPOCH, WriteCounter

VIEW_STANDARD = "standard"
VIEW_INVERSE = "inverse"

_FRAGMENT_FILE_RE = re.compile(r"^\d+$")


def is_inverse_view(name: str) -> bool:
    return name.startswith(VIEW_INVERSE)


class View:
    def __init__(self, path: str, index: str, frame: str, name: str,
                 cache_type: str = CACHE_TYPE_RANKED,
                 cache_size: int = DEFAULT_CACHE_SIZE,
                 row_attr_store=None, stats=None, broadcaster=None,
                 wal=None, integrity=None):
        self.path = path
        self.index = index
        self.frame = frame
        self.name = name
        self.cache_type = cache_type
        self.cache_size = cache_size
        self.row_attr_store = row_attr_store
        self.stats = stats
        self.broadcaster = broadcaster
        self.wal = wal
        self.integrity = integrity
        self.fragments: Dict[int, Fragment] = {}
        # One count of the writes to any fragment of this view: what a
        # whole-query memo records in place of every fragment's
        # generation (Executor._query_token).
        self.writes = WriteCounter()
        self._create_mu = threading.RLock()

    @property
    def fragments_path(self) -> str:
        return os.path.join(self.path, "fragments")

    def open(self):
        os.makedirs(self.fragments_path, exist_ok=True)
        for fname in sorted(os.listdir(self.fragments_path)):
            if not _FRAGMENT_FILE_RE.match(fname):
                continue
            # Lazy: the scan takes each fragment's flock but defers the
            # parse to first touch, so a cold server open is O(schema)
            # (the reference's mmap-attach analog, fragment.go:211-229).
            self._open_fragment(int(fname), lazy=True)

    def close(self):
        for f in self.fragments.values():
            f.close()
        self.fragments.clear()

    def _open_fragment(self, slice_: int, lazy: bool = False) -> Fragment:
        frag = Fragment(
            path=os.path.join(self.fragments_path, str(slice_)),
            index=self.index,
            frame=self.frame,
            view=self.name,
            slice_=slice_,
            cache_type=self.cache_type,
            cache_size=self.cache_size,
            row_attr_store=self.row_attr_store,
            stats=self.stats.with_tags(f"slice:{slice_}") if self.stats else None,
            wal=self.wal,
            integrity=self.integrity,
            view_writes=self.writes,
        )
        frag.open(lazy=lazy)
        # Copy-on-write: readers (max_slice, query fan-out) iterate
        # fragments without the lock.
        self.fragments = {**self.fragments, slice_: frag}
        # A new fragment changes the SET a query could touch: memos
        # that recorded generations of then-existing fragments can't
        # see it, so their structural token must stop validating.
        MUTATION_EPOCH.bump_structural()
        return frag

    def fragment(self, slice_: int) -> Optional[Fragment]:
        return self.fragments.get(slice_)

    def max_slice(self) -> int:
        return max(self.fragments, default=0)

    def create_fragment_if_not_exists(self, slice_: int) -> Fragment:
        with self._create_mu:
            frag = self.fragments.get(slice_)
            if frag is not None:
                return frag
            is_new_max = (self.fragments and slice_ > self.max_slice()
                          or not self.fragments and slice_ > 0)
            frag = self._open_fragment(slice_)
        if is_new_max and self.broadcaster is not None:
            from ..wire import pb
            self.broadcaster.send_async(pb.CreateSliceMessage(
                index=self.index, slice=slice_,
                is_inverse=is_inverse_view(self.name)))
        return frag

    def set_bit(self, row_id: int, column_id: int,
                deadline: Optional[float] = None) -> bool:
        frag = self.create_fragment_if_not_exists(column_id // SLICE_WIDTH)
        return frag.set_bit(row_id, column_id, deadline=deadline)

    def clear_bit(self, row_id: int, column_id: int,
                  deadline: Optional[float] = None) -> bool:
        frag = self.fragments.get(column_id // SLICE_WIDTH)
        if frag is None:
            return False
        return frag.clear_bit(row_id, column_id, deadline=deadline)
