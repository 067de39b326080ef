"""Data from --seed: fragments on disk for the server, reference tables for
the harness, one slice per job in a pool of workers.

The fragments go through the repo's own roaring serializer (footer and all),
so the server's ordinary open path loads and verifies them: that is loading
the data, as a user's import would. The reference side of each job is numpy
alone (`reference.py`).
"""

from __future__ import annotations

import multiprocessing
import os
from typing import Dict, List, Optional, Sequence

import numpy as np

from . import reference

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


def write_candidates(seed: int, n_columns: int, n: int) -> np.ndarray:
    """Columns a run may write, in the order it takes them: distinct,
    uniform over the index."""
    rng = np.random.default_rng([seed, 102])
    cols = rng.integers(0, n_columns, size=2 * n + 16, dtype=np.int64)
    _, first = np.unique(cols, return_index=True)
    return cols[np.sort(first)][:n]


def _dense_job(seed: int, s: int, data_dir: str, index: str, frame: dict,
               locals_: Sequence[int]) -> dict:
    from pilosa_tpu.roaring.bitmap import Container

    n_rows = int(frame["rows"])
    words = dense_words(seed, s, n_rows)
    _write_fragment(
        frag_path(data_dir, index, frame["name"], s),
        [r * 16 + b for r in range(n_rows) for b in range(16)],
        [Container(bitmap=words[i]) for i in range(len(words))])
    return {
        "counts": reference.slice_counts(
            words.reshape(n_rows, -1), n_rows),
        "kept": {(s << 20) + int(c): reference.column_bits(words, n_rows,
                                                          int(c))
                 for c in locals_},
    }


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


def _mixed_job(seed: int, s: int, data_dir: str, index: str, frame: dict,
               src_rows: Sequence[int]) -> dict:
    from pilosa_tpu.roaring.bitmap import Container

    rows, conts = mixed_containers(seed, s, frame)
    _write_fragment(
        frag_path(data_dir, index, frame["name"], s),
        [int(r) * 16 for r in rows],
        [Container(array=v) if b is None else Container(bitmap=b)
         for v, b in conts])
    counts = [len(v) if b is None else int(np.bitwise_count(b).sum())
              for v, b in conts]
    by_src: Dict[int, Dict[int, int]] = {}
    present = sorted(set(int(r) for r in rows) & set(src_rows))
    if present:
        words = np.stack([reference.container_words(v, b)
                          for v, b in conts])
        where = {int(r): i for i, r in enumerate(rows)}
        for x in present:
            inter = np.bitwise_count(words & words[where[x]]).sum(axis=1)
            by_src[x] = {int(r): int(c) for r, c in zip(rows, inter) if c}
    return {"rows": [int(r) for r in rows], "counts": counts,
            "by_src": by_src}


def _job(args) -> dict:
    kind = args[0]
    return (_dense_job if kind == "dense" else _mixed_job)(*args[1:])


def create_schema(data_dir: str, index: str, frame: str) -> None:
    from pilosa_tpu.core import Holder

    h = Holder(data_dir)
    h.open()
    h.create_index_if_not_exists(index) \
        .create_frame_if_not_exists(frame) \
        .create_view_if_not_exists(VIEW)
    h.close()


def generate(config: dict, seed: int, data_dir: str, *,
             write_columns: Optional[np.ndarray] = None,
             src_rows: Sequence[int] = (), approx: bool = False):
    """Write the configuration's frame and return its reference:
    a `CountReference` for a dense frame, a `TopNReference` for a mixed.
    With `approx` (the control's) a mixed frame returns a pair: the exact
    reference and one counted over every other slice and doubled."""
    index, frame, slices = config["index"], config["frame"], \
        int(config["slices"])
    create_schema(data_dir, index, frame["name"])
    kind = frame["kind"]
    if kind == "dense":
        by_slice: Dict[int, List[int]] = {}
        for c in (write_columns if write_columns is not None else ()):
            by_slice.setdefault(int(c) >> 20, []).append(int(c) & 0xFFFFF)
        jobs = [("dense", seed, s, data_dir, index, frame,
                 by_slice.get(s, ())) for s in range(slices)]
    elif kind == "mixed":
        src = tuple(sorted(set(int(r) for r in src_rows)))
        jobs = [("mixed", seed, s, data_dir, index, frame, src)
                for s in range(slices)]
    else:
        raise ValueError(f"unknown frame kind {kind!r}")
    n = max(1, min(len(jobs), (os.cpu_count() or 2) - 1, 12))
    with multiprocessing.get_context("spawn").Pool(n) as pool:
        parts = pool.map(_job, jobs, chunksize=4)
    if kind == "dense":
        base = np.sum([p["counts"] for p in parts], axis=0)
        kept: dict = {}
        for p in parts:
            kept.update(p["kept"])
        return reference.CountReference(int(frame["rows"]), base, kept)
    exact = _topn_reference(parts, 1)
    return (exact, _topn_reference(parts[::2], 2)) if approx else exact


def _topn_reference(parts, weight: int) -> reference.TopNReference:
    totals: Dict[int, int] = {}
    by_src: Dict[int, Dict[int, int]] = {}
    for p in parts:
        for r, c in zip(p["rows"], p["counts"]):
            totals[r] = totals.get(r, 0) + weight * c
        for x, row in p["by_src"].items():
            acc = by_src.setdefault(x, {})
            for r, c in row.items():
                acc[r] = acc.get(r, 0) + weight * c
    return reference.TopNReference(totals, by_src)
