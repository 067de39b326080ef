"""Data from --seed: fragments on disk for the server, reference tables for
the harness, one slice per job in a pool of workers, in two passes.

The first pass is set-up: the fragments go through the repo's own roaring
serializer (footer and all), so the server's ordinary open path loads and
verifies them: that is loading the data, as a user's import would. The second
pass is the reference's and numpy alone: each slice's words are made again
from the seed and the reference the configuration names (`names.reference`)
tabulates its share from them. It is timed by itself (`ref.tabulated_s`) and
the harness leaves those seconds out of `setup_s`.

A kind (a module `pbench/kinds/<kind>.py`; protocol: that package's
docstring) gives the harness `generate(config, seed, data_dir, plan) ->
reference` and `stage_query(frame_name)`, the query that stages the kind's
view; `Kind` here builds both from how one slice of one frame is made. A kind
of several frames writes its own `generate` from the pieces here
(`create_schema`, `frag_path`, `_write_fragment`).
"""

from __future__ import annotations

import multiprocessing
import os
import time
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from . import names, reference

VIEW = "standard"


def frag_path(data_dir: str, index: str, frame: str, slice_: int) -> str:
    return os.path.join(data_dir, index, frame, VIEW, "fragments",
                        str(slice_))


def _write_fragment(path: str, keys, containers) -> None:
    from pilosa_tpu.roaring.bitmap import Bitmap

    bm = Bitmap()
    bm.keys = list(keys)
    bm.containers = list(containers)
    with open(path, "wb") as f:
        bm.write_to(f, footer=True)


def dense_words(seed: int, slice_: int, n_rows: int) -> np.ndarray:
    rng = np.random.default_rng([seed, slice_, 0])
    return rng.integers(0, 2**64, size=(n_rows * 16, 1024), dtype=np.uint64)


def _first_distinct(cols: np.ndarray, n: int) -> np.ndarray:
    _, first = np.unique(cols, return_index=True)
    return cols[np.sort(first)][:n]


def write_candidates(seed: int, n_columns: int, n: int) -> np.ndarray:
    """Columns a run may write, in the order it takes them: distinct,
    uniform over the index."""
    rng = np.random.default_rng([seed, 102])
    return _first_distinct(
        rng.integers(0, n_columns, size=2 * n + 16, dtype=np.int64), n)


def block0_candidates(seed: int, n_columns: int, n: int) -> np.ndarray:
    """The same over the columns a mixed frame populates: the first 65,536
    of every slice, where the recipe puts its one container a row."""
    rng = np.random.default_rng([seed, 102])
    size = 2 * n + 16
    return _first_distinct(
        (rng.integers(0, n_columns >> 20, size=size, dtype=np.int64) << 20)
        + rng.integers(0, 65536, size=size, dtype=np.int64), n)


def _dense_slice(seed: int, s: int, data_dir: Optional[str], index: str,
                 frame: dict) -> Tuple[Sequence[int], Optional[np.ndarray]]:
    n_rows = int(frame["rows"])
    words = dense_words(seed, s, n_rows)
    if data_dir is None:
        return range(n_rows), words.reshape(n_rows, -1)
    from pilosa_tpu.roaring.bitmap import Container

    _write_fragment(
        frag_path(data_dir, index, frame["name"], s),
        [r * 16 + b for r in range(n_rows) for b in range(16)],
        [Container(bitmap=words[i]) for i in range(len(words))])
    return range(n_rows), None


def mixed_containers(seed: int, s: int, frame: dict):
    """bench.build_mixed_holder's containers as chip_smoke.py cuts them:
    `rows_per_slice` of `rows` rows present in a slice, each one container in
    the slice's first block; 30% bitmaps (AND of two random words, ~25%
    full), 70% arrays of U[1, 4096] values, windows of one permutation.
    Returns (rows, [(values | None, bitmap | None)])."""
    rng = np.random.default_rng([seed, s, 1])
    n_rows = int(frame["rows"])
    per = min(int(frame["rows_per_slice"]), n_rows)
    rows = np.sort(rng.choice(n_rows, size=per, replace=False))
    perm = rng.permutation(65536).astype(np.uint32)
    out = []
    for _ in rows:
        if rng.random() < float(frame["bitmap_share"]):
            bits = rng.integers(0, 2**64, size=1024, dtype=np.uint64)
            bits &= rng.integers(0, 2**64, size=1024, dtype=np.uint64)
            out.append((None, bits))
        else:
            n = int(rng.integers(1, int(frame["array_max_values"]) + 1))
            start = int(rng.integers(0, 65536 - n))
            out.append((np.sort(perm[start:start + n]), None))
    return rows, out


def _mixed_slice(seed: int, s: int, data_dir: Optional[str], index: str,
                 frame: dict) -> Tuple[Sequence[int], Optional[np.ndarray]]:
    rows, conts = mixed_containers(seed, s, frame)
    if data_dir is None:
        return rows, np.stack([reference.container_words(v, b)
                               for v, b in conts])
    from pilosa_tpu.roaring.bitmap import Container

    _write_fragment(
        frag_path(data_dir, index, frame["name"], s),
        [int(r) * 16 for r in rows],
        [Container(array=v) if b is None else Container(bitmap=b)
         for v, b in conts])
    return rows, None


def _job(args) -> Tuple[int, Optional[dict]]:
    """One slice in a worker. With a data directory, the fragment on disk;
    without, the reference's share of the slice, from the same words made
    again from the seed."""
    slice_words, config, seed, s, data_dir, locals_, src_rows = args
    frame = names.frames(config)[0]
    rows, words = slice_words(seed, s, data_dir, config["index"], frame)
    if data_dir is not None:
        return s, None
    return s, names.reference(config).slice_part(frame, rows, words, locals_,
                                                 src_rows)


def create_schema(data_dir: str, index: str, *frames) -> None:
    """The index and its frames, each a name or a configuration's frame: its
    `name`, and under `options` what the schema needs of it, by the names
    `Index.create_frame` takes (cache_type, time_quantum, inverse_enabled)."""
    from pilosa_tpu.core import Holder

    h = Holder(data_dir)
    h.open()
    idx = h.create_index_if_not_exists(index)
    for frame in frames:
        frame = {"name": frame} if isinstance(frame, str) else frame
        idx.create_frame_if_not_exists(
            frame["name"], **frame.get("options", {})) \
            .create_view_if_not_exists(VIEW)
    h.close()


class Kind:
    """A frame kind of this module: how one slice is made
    (`slice_words(seed, slice, data_dir, index, frame)` writes the fragment,
    or with no `data_dir` returns the slice's row ids and their words instead;
    a module-level function, since it goes to the workers), which columns a
    run may write, and the query that stages the view. A module under `kinds/`
    may build its own from these."""

    def __init__(self, slice_words: Callable, candidates: Callable,
                 stage_query: Callable):
        self.slice_words = slice_words
        self.candidates = candidates
        self.stage_query = stage_query

    def generate(self, config: dict, seed: int, data_dir: str, plan, *,
                 approx: bool = False):
        """Write the configuration's frame and return its reference, with the
        seconds its tabulation took as `tabulated_s`. With `approx` (the
        control's) a pair: the exact reference and one counted over every
        other slice and doubled."""
        index, frame, slices = config["index"], names.frames(config)[0], \
            int(config["slices"])
        create_schema(data_dir, index, frame)
        updates = len(plan.updates())
        candidates = self.candidates(seed, slices << 20, 3 * updates + 64) \
            if updates else ()
        by_slice: Dict[int, List[int]] = {}
        for c in candidates:
            by_slice.setdefault(int(c) >> 20, []).append(int(c) & 0xFFFFF)
        src = tuple(plan.src_rows())
        jobs = [(self.slice_words, config, seed, s, where,
                 by_slice.get(s, ()), src)
                for where in (data_dir, None) for s in range(slices)]
        n = max(1, min(slices, (os.cpu_count() or 2) - 1, 12))
        with multiprocessing.get_context("spawn").Pool(n) as pool:
            pool.map(_job, jobs[:slices], chunksize=4)
            t0 = time.monotonic()  # from here on it is the reference's time
            parts = pool.map(_job, jobs[slices:], chunksize=4)
        ref = names.reference(config)
        exact = ref.assemble(frame, parts, candidates)
        also = ref.assemble(frame, parts[::2], candidates, weight=2) \
            if approx else None
        exact.tabulated_s = time.monotonic() - t0
        return (exact, also) if approx else exact


def _count_row0(frame: str):
    return f'Count(Bitmap(rowID=0, frame="{frame}"))', ("R", 0), "count"


def _topn5(frame: str):
    return f'TopN(frame="{frame}", n=5)', ("T", None, 5), "topn"

