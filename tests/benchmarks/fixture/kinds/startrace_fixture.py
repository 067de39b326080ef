"""TEST FIXTURE, the kind of `startrace-fixture`: upstream's getting-started
example (Star Trace) in miniature, as a later PR would add a deployment of two
frames: this file, a reference, a configuration and a traffic mix, and no
edit to the harness. A column is a repository. Frame `language` holds every
column in exactly one of its rows (bitmap containers, sixteen a row and
slice); frame `stargazer` holds a row a user, a few stars each (array
containers of a value or two), most of them on a few hot repositories so that
two users' rows meet.

What the harness's defaults cannot say and this kind does: `bind` writes PQL
over two frames (a TopN whose src row lies in the other frame, in both
directions; a Count of three leaves from two frames; a SetBit into
`stargazer`, which names the frame it wrote), `stage_queries` stages both
views, and `generate` makes both frames and hands the reference candidates
per written frame.
"""

import time

import numpy as np

from pbench import datagen, names
from pbench.schedule import BoundOp, bitmap

LANGUAGE, STARGAZER = "language", "stargazer"
SLICE = 1 << 20


def frames_of(config: dict) -> dict:
    return {f["name"]: f for f in names.frames(config)}


def language_of(seed: int, s: int, frame: dict) -> np.ndarray:
    """The language row of each of slice s's 2**20 columns: skewed, so the
    ranking has an order."""
    rng = np.random.default_rng([seed, s, 2])
    p = 1.0 / np.arange(1, int(frame["rows"]) + 1) ** float(frame["skew"])
    return rng.choice(int(frame["rows"]), size=SLICE,
                      p=p / p.sum()).astype(np.uint8)


def hot_repositories(seed: int, n_columns: int, frame: dict) -> np.ndarray:
    rng = np.random.default_rng([seed, 3])
    return rng.choice(n_columns, size=int(frame["hot_repositories"]),
                      replace=False).astype(np.int64)


def stars_of(seed: int, n_columns: int, frame: dict) -> dict:
    """user -> the sorted columns the user has starred, over the whole
    index."""
    hot = hot_repositories(seed, n_columns, frame)
    rng = np.random.default_rng([seed, 4])
    out = {}
    for u in range(int(frame["rows"])):
        k = int(rng.integers(int(frame["stars_min"]),
                             int(frame["stars_max"]) + 1))
        picks = np.where(rng.random(k) < float(frame["hot_share"]),
                         hot[rng.integers(len(hot), size=k)],
                         rng.integers(0, n_columns, size=k, dtype=np.int64))
        out[u] = np.unique(picks)
    return out


def star_candidates(seed: int, n_columns: int, frame: dict,
                    n: int) -> np.ndarray:
    """Columns a run may star, in the order it takes them, distinct: hot
    repositories (a star there can move an intersection) and any other."""
    hot = hot_repositories(seed, n_columns, frame)
    rng = np.random.default_rng([seed, 104])
    size = 2 * n + 16
    return datagen._first_distinct(np.where(
        rng.random(size) < 0.5, hot[rng.integers(len(hot), size=size)],
        rng.integers(0, n_columns, size=size, dtype=np.int64)), n)


def _write_language(data_dir, index, s, lang, n_rows) -> None:
    from pilosa_tpu.roaring.bitmap import Container

    keys, conts = [], []
    for r in range(n_rows):
        words = np.packbits(lang == r, bitorder="little").view(np.uint64)
        for block, w in enumerate(words.reshape(16, 1024)):
            if w.any():
                keys.append(r * 16 + block)
                conts.append(Container(bitmap=w.copy()).normalize())
    datagen._write_fragment(datagen.frag_path(data_dir, index, LANGUAGE, s),
                            keys, conts)


def _write_stargazer(data_dir, index, s, stars) -> None:
    from pilosa_tpu.roaring.bitmap import Container

    pos = np.sort(np.asarray(
        [u * SLICE + (int(c) & (SLICE - 1)) for u, cols in stars.items()
         for c in cols if int(c) >> 20 == s], dtype=np.int64))
    keys, first = np.unique(pos >> 16, return_index=True)
    datagen._write_fragment(
        datagen.frag_path(data_dir, index, STARGAZER, s),
        [int(k) for k in keys],
        [Container(array=(chunk & 0xFFFF).astype(np.uint32))
         for chunk in np.split(pos, first[1:])])


def generate(config: dict, seed: int, data_dir: str, plan):
    """Both frames on disk through the repo's roaring serializer, and the
    reference (`refs/startrace_fixture.py`) from the same arrays."""
    frames, slices = frames_of(config), int(config["slices"])
    index, n_columns = config["index"], slices << 20
    datagen.create_schema(data_dir, index, *names.frames(config))
    stars = stars_of(seed, n_columns, frames[STARGAZER])
    langs = []
    for s in range(slices):
        langs.append(language_of(seed, s, frames[LANGUAGE]))
        _write_language(data_dir, index, s, langs[-1],
                        int(frames[LANGUAGE]["rows"]))
        _write_stargazer(data_dir, index, s, stars)
    t0 = time.monotonic()  # from here on it is the reference's time
    updates = len(plan.updates())
    candidates = star_candidates(seed, n_columns, frames[STARGAZER],
                                 3 * updates + 64) if updates else ()
    ref = names.reference(config).assemble(
        config, np.concatenate(langs), stars, {STARGAZER: candidates})
    ref.tabulated_s = time.monotonic() - t0
    return ref


def stage_queries(config: dict):
    """Both views, `stargazer` in the layout the src form reads: a view of a
    few bits a container stages sparse, and the first TopN over it restages
    it dense (`MeshManager._demote_to_dense`). That happens here, one request
    at a time, and not under the warm-up's first four at once."""
    return [(f'TopN(frame="{LANGUAGE}", n=5)',
             ("T", LANGUAGE, None, None, 5), "topn"),
            (f"Count({bitmap(0, STARGAZER)})", ("R", 0), "count"),
            (f'TopN({bitmap(0, LANGUAGE)}, frame="{STARGAZER}", n=5)',
             ("T", STARGAZER, LANGUAGE, 0, 5), "topn")]


def bind(op, plan, column) -> BoundOp:
    """An update stars a repository for the user its rank names. A TopN ranks
    the last frame the op names, filtered by a row of the first where it
    draws one. A Count intersects its leaves, each a row of the frame named
    for it."""
    rows = plan.rows(op)
    if op.kind == "update":
        (frame, u), = rows
        return BoundOp("update",
                       (f'SetBit(rowID={u}, frame="{frame}", '
                        f"columnID={column})", f"Count({bitmap(u, frame)})"),
                       ("R", u), (u, int(column)), frame)
    if op.kind == "topn":
        ranked = op.frames[-1]
        if rows:
            (src, r), = rows
            return BoundOp(
                "topn",
                (f'TopN({bitmap(r, src)}, frame="{ranked}", n={op.n})',),
                ("T", ranked, src, r, op.n), None)
        return BoundOp("topn", (f'TopN(frame="{ranked}", n={op.n})',),
                       ("T", ranked, None, None, op.n), None)
    if op.kind == "count" and op.op == "Intersect":
        inner = ", ".join(bitmap(r, f) for f, r in rows)
        return BoundOp("count", (f"Count(Intersect({inner}))",),
                       ("I", *rows), None)
    raise ValueError(f"the fixture kind binds no {op.kind} {op.op}")
