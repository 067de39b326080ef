"""`mixed`'s data to the byte (`datagen._mixed_slice`), for inserts: the
columns a run may write are `mixed`'s (`block0_candidates`: the slice's first
block, where a row absent from the slice holds no container), and after them
as many again in the slice's other fifteen blocks, where no row holds one. The
harness takes the first candidate the reference lets a row write, so a row is
inserted into slices it is absent from while there are such, and a row that
has used them up (the hottest of 256 is absent from ~97 of 960 slices and
draws a sixth of the inserts) still creates a container with every SetBit.

The configuration asks of the program that it patch a created container into
the staged pool (`topn-ingest-1b.json`, `requires`). `generate` looks for the
counter the cell's own metrics read, `container_patches`, in the source of
`pilosa_tpu/parallel/serve.py` (this process stays off JAX and imports none of
it) and, where it is not there, ends the run at once with exit code 1: a
program that restages the whole view for every insert answers ~3 ops a
second, and is not measured in this cell (PERF.md, section 6)."""

import os

import numpy as np

from ..datagen import (Kind, _first_distinct, _mixed_slice, _topn5,
                       block0_candidates)
from ..server import REPO


def ingest_candidates(seed: int, n_columns: int, n: int) -> np.ndarray:
    rng = np.random.default_rng([seed, 103])
    size = 2 * n + 16
    rest = _first_distinct(
        (rng.integers(0, n_columns >> 20, size=size, dtype=np.int64) << 20)
        + rng.integers(65536, 1 << 20, size=size, dtype=np.int64), n)
    return np.concatenate([block0_candidates(seed, n_columns, n), rest])


def program_patches(repo: str) -> bool:
    try:
        with open(os.path.join(repo, "pilosa_tpu", "parallel",
                               "serve.py")) as f:
            return "container_patches" in f.read()
    except OSError:
        return False


_KIND = Kind(_mixed_slice, ingest_candidates, _topn5)
stage_query = _KIND.stage_query


def generate(config: dict, seed: int, data_dir: str, plan, **kw):
    if not program_patches(REPO):
        raise SystemExit(
            f"benchmarks: {config['name']} requires a program that patches a "
            "created container into the staged pool (no counter "
            "`container_patches` in pilosa_tpu/parallel/serve.py): this one "
            "restages the whole view for every insert; not run")
    return _KIND.generate(config, seed, data_dir, plan, **kw)
