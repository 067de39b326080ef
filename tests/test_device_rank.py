"""TopN's ranking where the row counts lie (serve.MeshManager.
_rank_on_device over mesh.compile_serve_topn_rank and
compile_serve_topn_gather) gives the pairs the host form gives:
combine_limbs over every row read back, then rank_pairs. Seeded counts
over 2^20 rows and over 5,000 (padded to 8,192), on one device and on
four of the suite's eight virtual ones, the limbs replicated over the
mesh as a row-count program leaves them."""

import zlib

import numpy as np
import pytest

from pilosa_tpu.parallel import serve
from pilosa_tpu.parallel.serve import (MeshManager, RANK_EXACT_SLICES,
                                       combine_limbs, rank_k, rank_pairs)

SIZES = {"2^20": (1 << 20, 1 << 20), "5000": (1 << 13, 5000)}


@pytest.fixture(scope="module", params=[1, 4])
def mgr(request):
    from pilosa_tpu.parallel.mesh import default_mesh

    return MeshManager(None, mesh=default_mesh(request.param))


def on_mesh(mgr, limbs):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    return jax.device_put(limbs, NamedSharding(mgr.mesh, P()))


def split(counts, rng, carry=0.3):
    """(2, R) int32 [lo, hi] limbs of int64 counts, a share of the rows
    with 2^16 moved from hi into lo, as a psum of per-device lo limbs
    leaves them (lo past 16 bits)."""
    lo, hi = counts & 0xFFFF, counts >> 16
    move = (rng.random(len(counts)) < carry) & (hi > 0)
    return np.stack([lo + (move << 16), hi - move]).astype(np.int32)


def row_ids_of(rng, live):
    return np.sort(rng.choice(1 << 40, size=live, replace=False)) \
        .astype(np.uint64)


def counts_of(kind, rng, padded, live):
    out = np.zeros(padded, dtype=np.int64)
    if kind == "pareto":
        out[:live] = np.where(rng.random(live) < 0.3,
                              (rng.pareto(1.25, live) + 1).astype(np.int64),
                              0)
    elif kind == "few_values":  # many rows tied at the n-th place
        out[:live] = rng.integers(0, 4, size=live)
    elif kind == "all_tied":
        out[:live] = 7
    elif kind == "large":  # up to 2,047 slices of full rows
        out[:live] = rng.integers(0, (RANK_EXACT_SLICES - 1) << 20,
                                  size=live)
    elif kind == "few_qualify":
        out[:live] = rng.integers(0, 3, size=live)
        out[rng.choice(live, size=4, replace=False)] = 50
    return out


def both_forms(mgr, rng, counts, live, n, threshold, pick_ids=None):
    """(device pairs, host pairs) over the same limbs; `pick_ids(
    all_rows)` gives phase 2's candidate ids."""
    limbs = split(counts, rng)
    all_rows = row_ids_of(rng, live)
    row_ids = pick_ids(all_rows) if pick_ids else ()
    host = rank_pairs(all_rows, combine_limbs(limbs, live), n, row_ids,
                      threshold)
    dev = mgr._rank_on_device(all_rows, on_mesh(mgr, limbs), n, row_ids,
                              threshold)
    return dev, host


def seed_of(*parts):
    return zlib.crc32(repr(parts).encode())


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("kind,n,threshold", [
    ("pareto", 5, 1), ("pareto", 100, 1), ("pareto", 5, 3),
    ("few_values", 5, 1), ("few_values", 9, 2), ("all_tied", 5, 1),
    ("large", 5, 1), ("few_qualify", 100, 10)])
def test_phase_one_keeps_what_rank_pairs_keeps(mgr, size, kind, n,
                                               threshold):
    padded, live = SIZES[size]
    rng = np.random.default_rng(seed_of(size, kind, n, threshold))
    counts = counts_of(kind, rng, padded, live)
    dev, host = both_forms(mgr, rng, counts, live, n, threshold)
    assert dev == host
    qualify = int((counts >= threshold).sum())
    assert len(dev) == min(n, qualify)
    if kind in ("few_values", "all_tied"):  # ties settled by row id
        last = dev[-1][1]
        assert int((counts[:live] == last).sum()) > sum(
            1 for _r, c in dev if c == last)


@pytest.mark.parametrize("size", sorted(SIZES))
def test_every_count_zero_keeps_nothing(mgr, size):
    padded, live = SIZES[size]
    rng = np.random.default_rng(3)
    dev, host = both_forms(mgr, rng, np.zeros(padded, dtype=np.int64),
                           live, 5, 1)
    assert dev == host == []


@pytest.mark.parametrize("size", sorted(SIZES))
@pytest.mark.parametrize("threshold", [1, 3])
def test_phase_two_recounts_the_ids_that_are_there(mgr, size, threshold):
    padded, live = SIZES[size]
    rng = np.random.default_rng(11 + threshold)
    counts = counts_of("few_values", rng, padded, live)
    above = np.flatnonzero(counts[:live] >= threshold)

    def some_there_some_not(all_rows):  # unsorted, one id twice
        there = [int(all_rows[i]) for i in np.concatenate([
            rng.choice(above, 3, replace=False),
            rng.choice(live, 3, replace=False)])]
        return there + [int(all_rows[-1]) + 1, 3, there[0]]

    dev, host = both_forms(mgr, rng, counts, live, 0, threshold,
                           pick_ids=some_there_some_not)
    assert dev == host
    assert 0 < len(dev) <= 7


def test_phase_two_of_ids_none_of_which_is_there(mgr):
    padded, live = SIZES["5000"]
    rng = np.random.default_rng(5)
    counts = counts_of("pareto", rng, padded, live)
    all_rows = row_ids_of(rng, live)
    absent = [int(all_rows[-1]) + 1, int(all_rows[-1]) + 7]
    assert mgr._rank_on_device(all_rows,
                               on_mesh(mgr, split(counts, rng)), 0,
                               absent, 1) == []


@pytest.mark.parametrize("num_slices,n,row_ids,device", [
    (RANK_EXACT_SLICES - 1, 5, (), True),
    (RANK_EXACT_SLICES, 5, (), False),   # int32 counts could overflow
    (RANK_EXACT_SLICES, 0, (1, 2), True),  # phase 2 combines on the host
    (8, 0, (), False),                   # every row asked for
    (8, (1 << 20) + 1, (), False),       # k past the rows
])
def test_the_slice_count_bound_and_n_send_phase_one_to_the_host(
        num_slices, n, row_ids, device):
    assert MeshManager._ranks_on_device(1 << 20, num_slices, n, row_ids,
                                        None) is device
    # Below the cut, or with an attribute filter, always the host.
    assert not MeshManager._ranks_on_device(
        serve.RANK_DEVICE_MIN_ROWS // 2, 8, 5, (), None)
    assert not MeshManager._ranks_on_device(1 << 20, 8, 5, (),
                                            lambda r: True)


def test_k_is_n_rounded_up_to_a_power_of_two_at_least_eight():
    assert [rank_k(n) for n in (1, 5, 8, 9, 100, 128, 129)] == \
        [8, 8, 8, 16, 128, 128, 256]


def test_what_came_back_answers_the_same_array_again(mgr, monkeypatch):
    """Phase 1 asked again of the same limbs (a memo hit) and phase 2
    of phase 1's own rows answer from what came back: one launch in
    all. Another n, or an id phase 1 did not return, launches again;
    the record goes with the array."""
    import gc

    padded, live = SIZES["5000"]
    rng = np.random.default_rng(17)
    counts = counts_of("pareto", rng, padded, live)
    limbs_host = split(counts, rng)
    limbs = on_mesh(mgr, limbs_host)
    all_rows = row_ids_of(rng, live)
    launches = []
    run = mgr._run_rank
    monkeypatch.setattr(mgr, "_run_rank",
                        lambda *a: launches.append(1) or run(*a))

    def host(n, ids=()):
        return rank_pairs(all_rows, combine_limbs(limbs_host, live), n, ids,
                          1)

    first = mgr._rank_on_device(all_rows, limbs, 5, (), 1)
    ids = [r for r, _c in first]
    assert first == host(5) and len(launches) == 1
    assert mgr._rank_on_device(all_rows, limbs, 5, (), 1) == first
    assert mgr._rank_on_device(all_rows, limbs, 0, ids, 1) == host(0, ids)
    assert len(launches) == 1
    assert mgr._rank_on_device(all_rows, limbs, 7, (), 1) == host(7)
    zero = [int(all_rows[np.flatnonzero(counts[:live] == 0)[0]])]
    assert mgr._rank_on_device(all_rows, limbs, 0, ids + zero, 1) \
        == host(0, ids + zero)
    assert len(launches) == 3
    key = id(limbs)
    assert key in mgr._ranked
    del limbs
    gc.collect()
    assert key not in mgr._ranked
