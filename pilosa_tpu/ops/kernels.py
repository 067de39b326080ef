"""Pallas TPU kernels for the fused roaring set-op + popcount path.

TPU re-design of the reference's POPCNT assembly kernels
(/root/reference/roaring/assembly_amd64.s:25-115: popcntAndSlice etc.):
the pairwise bitwise op and the population-count reduction run in one
kernel over VMEM-resident blocks, streaming from HBM via the grid, with a
scalar accumulator in SMEM. Backend dispatch (Pallas on TPU, fused XLA
elsewhere) is the analog of the reference's hasAsm runtime dispatch
(roaring/assembly_asm.go:20).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .bitops import BINARY_OPS, count_pair, fold_tree
from .pool import CONTAINER_WORDS, ROW_SPAN

# Max rows of 2048-word containers per pairwise grid step: two operand
# blocks, each Mosaic-double-buffered, at 256 rows bill 8 MB of the
# 16 MB VMEM window (same budget note as _uniform_pick_t). Bigger
# blocks mean fewer grid steps, so less per-step DMA issue overhead on
# large inputs; _pair_pick_block shrinks the block (and the padding
# waste) for small ones.
_BLOCK_M = 256


def _pair_pick_block(m: int) -> int:
    """Rows per grid step for the pairwise kernel: the full _BLOCK_M
    when the input fills it, else the input rounded up to the 8-sublane
    tile so a small pair runs as ONE grid step with < 8 rows of
    zero-padding (the old fixed 64-row block padded a 1-row pair to
    64)."""
    if m >= _BLOCK_M:
        return _BLOCK_M
    return max(8, -(-m // 8) * 8)


# -- carry-save (Harley-Seal) popcount accumulation --------------------------
#
# Every count kernel's epilogue is "popcount each word, sum to a
# scalar". The carry-save-adder ladder (Faster Population Counts Using
# AVX2 Instructions, arXiv:1611.07612 §2; blocked positional scheme in
# arXiv:2412.16370) folds EIGHT word slabs into four accumulator slabs
# (ones/twos/fours/eights) with 16 cheap bitwise VPU ops, then
# popcounts only the accumulators — half the popcount volume at
# one-eighth-volume bitwise cost. That wins exactly when the backend
# lowers lax.population_count as a multi-op SWAR sequence rather than
# one native instruction, which is hardware-dependent — so the backend
# *choice* is measured (ops/calibrate.py), and the ladder itself can be
# pinned off with PILOSA_TPU_CSA=0 (read at trace time; compiled
# programs keep whichever epilogue they were traced with).


def _csa_enabled() -> bool:
    return os.environ.get("PILOSA_TPU_CSA", "1").lower() not in (
        "0", "false", "no", "off")


def _csa(a, b, c):
    """One carry-save adder: (sum, carry) bit-planes of a + b + c."""
    u = a ^ b
    return u ^ c, (a & b) | (u & c)


def csa_popcount_sum(v, *, force: bool | None = None):
    """Scalar int32 popcount-sum of a uint-word array.

    The leading dims collapse and split into eight contiguous row
    slabs — both are leading-dim reshapes, which are layout-preserving
    on Mosaic (no lane retiling; see _runs_view) — and one seven-CSA
    ladder reduces them. Exact: sum-of-bits = pc(ones) + 2*pc(twos) +
    4*pc(fours) + 8*pc(eights) by the carry-save invariant. Falls back
    to the naive popcount-everything epilogue when the row count is
    not a multiple of 8 or the ladder is disabled (`force` overrides
    the env gate for differential tests; works outside kernels too,
    so tests exercise the ladder directly)."""
    def naive(x):
        return jnp.sum(lax.population_count(x).astype(jnp.int32))

    lanes = v.shape[-1]
    rows = 1
    for d in v.shape[:-1]:
        rows *= d
    use = _csa_enabled() if force is None else force
    if not use or rows < 8 or rows % 8 != 0:
        return naive(v)
    w = v.reshape(8, rows // 8, lanes)
    ones = w[0] ^ w[1]
    twos_a = w[0] & w[1]
    ones, twos_b = _csa(ones, w[2], w[3])
    twos = twos_a ^ twos_b
    fours_a = twos_a & twos_b
    ones, twos_a = _csa(ones, w[4], w[5])
    ones, twos_b = _csa(ones, w[6], w[7])
    twos, fours_b = _csa(twos, twos_a, twos_b)
    fours = fours_a ^ fours_b
    eights = fours_a & fours_b
    return (naive(ones) + 2 * naive(twos) + 4 * naive(fours)
            + 8 * naive(eights))


def pallas_probe_ok() -> bool:
    """Compile + run ONE trivial Pallas kernel and check the result —
    the canary for 'can this backend compile Pallas at all'. Blocks
    for the compile; callers own their hang policy (ops/calibrate.py:
    daemon thread with a bounded wait and a cached verdict)."""
    try:
        import numpy as np

        out = pl.pallas_call(
            lambda x_ref, o_ref: o_ref.__setitem__(..., x_ref[...] + 1),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.int32))(
            jnp.zeros((8, 128), jnp.int32))
        return bool((np.asarray(out) == 1).all())
    except Exception:  # noqa: BLE001 — any failure means "no pallas"
        from ..obs import get_logger

        get_logger("kernels").warning("Pallas probe kernel failed",
                                      exc_info=True)
        return False


def use_pallas() -> bool:
    """True when the Pallas TPU path should be used.

    Non-TPU backends always answer False (Pallas interpret mode is a
    test vehicle, never a serving dispatch). On TPU the verdict is no
    longer a comment-driven constant: PILOSA_TPU_COUNT_BACKEND=pallas
    or =xla pins it, and the default ("auto") asks ops/calibrate.py,
    which measures both backends once per process on a representative
    shape — under the same probe watchdog the serving layer uses — and
    caches (optionally persists) the winner: which backend is faster
    depends on the kernel shape and the chip, so a measurement, not a
    comment, owns this dispatch."""
    if jax.default_backend() != "tpu":
        return False
    from .calibrate import resolve_backend

    return resolve_backend() == "pallas"


def _pair_count_kernel(op_name: str, a_ref, b_ref, o_ref):
    op = BINARY_OPS[op_name]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        o_ref[0, 0] = jnp.int32(0)

    o_ref[0, 0] += csa_popcount_sum(op(a_ref[:], b_ref[:]))


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def _pallas_pair_count(a, b, op: str = "and", interpret: bool = False):
    m = a.shape[0]
    block = _pair_pick_block(m)
    grid = (max(1, (m + block - 1) // block),)
    # Zero-pad to a block multiple: padding contributes no set bits for
    # any of the four ops (0 op 0 == 0). Each operand streams HBM->VMEM
    # exactly once — the grid blocks are disjoint row slabs and Mosaic
    # double-buffers them, so block i+1 prefetches under block i's
    # fold+popcount.
    padded = grid[0] * block
    if padded != m:
        pad = ((0, padded - m), (0, 0))
        a = jnp.pad(a, pad)
        b = jnp.pad(b, pad)
    out = pl.pallas_call(
        functools.partial(_pair_count_kernel, op),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, CONTAINER_WORDS), lambda i: (i, 0)),
            pl.BlockSpec((block, CONTAINER_WORDS), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        interpret=interpret,
    )(a, b)
    return out[0, 0]


def fused_pair_count(a, b, op: str = "and", *, force_pallas: bool | None = None,
                     interpret: bool = False):
    """popcount(op(a, b)) over (M, 2048) uint32 blocks, fused on device.

    Dispatches to the Pallas TPU kernel on TPU backends, fused XLA
    elsewhere. On a cpu backend, host numpy inputs short-circuit to the
    native C++ popcount-pair kernels (a Python int result) — JAX-on-CPU
    pays a dispatch plus a device round-trip for what is one fused
    memory pass. `force_pallas`/`interpret` exist for differential
    tests and always take the device paths.
    """
    if (force_pallas is None and not interpret
            and isinstance(a, np.ndarray) and isinstance(b, np.ndarray)
            and jax.default_backend() == "cpu"):
        from . import native

        if native.has_native() and a.shape == b.shape:
            av = np.ascontiguousarray(a).reshape(-1).view(np.uint64)
            bv = np.ascontiguousarray(b).reshape(-1).view(np.uint64)
            fn = getattr(native, f"popcnt_{op}_slice", None)
            if fn is not None:
                return fn(av, bv)
    a = a.reshape(-1, CONTAINER_WORDS)
    b = b.reshape(-1, CONTAINER_WORDS)
    if force_pallas or (force_pallas is None and use_pallas()):
        return _pallas_pair_count(a, b, op=op, interpret=interpret)
    return count_pair(a, b, op)


# -- fused call-tree count with in-kernel container gather -------------------
#
# The XLA mesh path gathers each leaf row into a fresh (16, 2048) block
# before combining (parallel/plan.py eval_tree over pool.words[idx]),
# which materializes the gathered copies in HBM: for the 1B-column
# Intersect+Count that triples the memory traffic. This kernel instead
# streams the EXACT containers straight from the pool into VMEM via
# scalar-prefetched index maps (the Pallas block-sparse pattern), so
# each container is read once and nothing intermediate is written.

# Container words viewed as (sublanes, lanes) for the TPU tiling rules:
# a Pallas block's minor two dims must be (8k, 128k)-aligned, so a
# 2048-word container streams as a (16, 128) tile.
_SUBLANES = 16
_LANES = 128


def _tree_count_kernel(tree, num_leaves, idx_ref, hit_ref, *refs):
    o_ref = refs[num_leaves]
    s = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when((s == 0) & (j == 0))
    def _init():
        o_ref[0, 0] = jnp.int32(0)

    def leaf(i):
        blk = refs[i][0, 0, :, :]
        keep = hit_ref[i, s, j] != 0
        return jnp.where(keep, blk, jnp.uint32(0))

    o_ref[0, 0] += csa_popcount_sum(fold_tree(tree, leaf))


# SMEM budget for one pallas_call's scalar-prefetch tables: the
# (L, S, 16) idx+hit tables live in SMEM (1 MB/core) — at 960 slices
# and 2 leaves they overflow it (observed: "Used 1.88M of 1.00M smem"),
# so larger shards run slice slabs, each its own kernel launch. A
# 2-leaf/256-slice slab (128 KB of tables) compiles with headroom; the
# slab size scales down with leaf count to hold that table budget.
_PREFETCH_SLICES_PER_LEAF = 512


def _tree_count_call(words4, idx, hit, tree, num_leaves, interpret):
    """One pallas_call over (S, cap, 16, 128) words with (L, S, 16)
    prefetch tables."""
    s_n, r_n = idx.shape[1], idx.shape[2]

    def leaf_spec(leaf):
        return pl.BlockSpec(
            (1, 1, _SUBLANES, _LANES),
            lambda s, j, idx_ref, hit_ref, leaf=leaf: (
                s, idx_ref[leaf, s, j], 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s_n, r_n),
        in_specs=[leaf_spec(leaf) for leaf in range(num_leaves)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    out = pl.pallas_call(
        functools.partial(_tree_count_kernel, tree, num_leaves),
        out_shape=jax.ShapeDtypeStruct((1, 1), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(idx, hit, *([words4] * num_leaves))
    return out[0, 0]


def _coarse_count_kernel(tree, num_leaves, starts_ref, *refs):
    o_ref = refs[num_leaves]
    s = pl.program_id(0)

    def leaf(i):
        blk = refs[i][0, :, :]
        keep = starts_ref[i, s] >= 0
        return jnp.where(keep, blk, jnp.uint32(0))

    o_ref[0, s] = csa_popcount_sum(fold_tree(tree, leaf))


def coarse_count_per_slice(views, starts, tree, *,
                           interpret: bool = False):
    """ONE pallas_call producing per-slice coarse counts.

    The shared engine under both coarse count surfaces — the
    mesh-level scalar kernel below and the serving-layer program
    (mesh.compile_serve_count_coarse_pallas), which differ only in
    whether leaves share one pool and how the per-slice counts are
    reduced (scalar sum vs 16-bit limb psum).

    views:  tuple per leaf of the NATIVE (S, cap_i, 2048) uint32 pool
            (cap_i % 16 == 0; leaves may share one pool object). A
            whole-row run is the (1, 16, 2048) block at row-run index
            starts[leaf, s] — 16 sublanes x 2048 lanes satisfies the
            (8k, 128k) tiling rule DIRECTLY, so no reshape of the pool
            is needed. (The previous (S, cap/16, 256, 128) view was
            NOT a bitcast: splitting the 2048-lane rows retiles the
            physical T(8,128) layout, and XLA materialized a whole
            POOL-SIZED copy per kernel operand — 960 MB per leaf at
            headline scale, OOM at batch width 16.)
    starts: (L, S) int32 signed row-run index; negative = absent or
            masked out (the block is read clipped and zeroed).
    Returns (1, S) int32 per-slice counts (each <= 2^20, exact)."""
    num_leaves, s_n = starts.shape

    def leaf_spec(leaf):
        return pl.BlockSpec(
            (1, ROW_SPAN, 16 * _LANES),
            lambda s, starts_ref, leaf=leaf: (
                s, jnp.maximum(starts_ref[leaf, s], 0), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_n,),
        in_specs=[leaf_spec(leaf) for leaf in range(num_leaves)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_coarse_count_kernel, tree, num_leaves),
        out_shape=jax.ShapeDtypeStruct((1, s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *views)


def _identity_batch_kernel(tree, num_leaves, starts_ref, *refs):
    o_ref = refs[num_leaves]
    b = pl.program_id(0)
    s = pl.program_id(1)

    def leaf(i):
        blk = refs[i][0, :, :]
        keep = starts_ref[b * num_leaves + i, s] >= 0
        return jnp.where(keep, blk, jnp.uint32(0))

    o_ref[b, s] = csa_popcount_sum(fold_tree(tree, leaf))


def coarse_count_identity_batch(pools, starts, tree, *,
                                interpret: bool = False):
    """ONE pallas_call producing per-(query, slice) counts for a PLAIN
    (no leaf sharing assumed) coarse batch — grid (B, S), each step
    computing one query's fold for one slice from the L leaf-position
    pools.

    Why not the shared-read kernel with an identity leaf map: a B*L
    operand list repeating one pool makes the AOT compiler budget HBM
    for EVERY alias (arguments: 30 GB at batch 16 over the 1 GB
    headline pool — a compile-time OOM even though the runtime buffers
    alias). Here the operand list is the L DISTINCT leaf-position
    pools — the same worst-case accounting the XLA batch programs
    already pay — and the (b, s) grid picks each slot's row-run via
    the scalar-prefetched starts table. Traffic matches the plain XLA
    batch (each query reads its own rows) minus the gathered-copy
    amplification, and ONE compile serves every width-B herd of this
    tree shape regardless of which rows the queries name.

    pools:  tuple per LEAF POSITION of the NATIVE (S, cap_l, 2048)
            uint32 pool (cap_l % 16 == 0).
    starts: (B*L, S) int32 signed row-run indices, slot-major
            (slot = b*L + l); negative = absent or masked out.
    tree:   nested op list with numbered leaf POSITIONS.

    Returns (B, S) int32 per-(query, slice) counts."""
    slots, s_n = starts.shape
    num_leaves = len(pools)
    batch = slots // num_leaves
    assert batch * num_leaves == slots, (slots, num_leaves)

    def leaf_spec(leaf):
        return pl.BlockSpec(
            (1, ROW_SPAN, 16 * _LANES),
            lambda b, s, starts_ref, leaf=leaf: (
                s, jnp.maximum(starts_ref[b * num_leaves + leaf, s], 0), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, s_n),
        in_specs=[leaf_spec(leaf) for leaf in range(num_leaves)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_identity_batch_kernel, tree, num_leaves),
        out_shape=jax.ShapeDtypeStruct((batch, s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *pools)


# What the uniform kernels may plan to use of the chip's 16 MB scoped
# VMEM window: the SMEM output and scalar tables bill into the same
# scoped allocation (the compiler's message for a (28, 960) int32
# output counts it), and the fold keeps temporaries the pickers below
# do not itemize.
_VMEM_BUDGET = 12 << 20
_ROW_BYTES = ROW_SPAN * 16 * _LANES * 4  # one whole-row run: 128 KB


def _largest_t(s_n: int, cap: int) -> int:
    """The largest convenient slices-per-step that divides S and does
    not exceed `cap` (1 when nothing else fits)."""
    for t in (32, 16, 8, 4, 2):
        if t <= cap and s_n % t == 0:
            return t
    return 1


def _uniform_pick_t(s_n: int, num_operands: int = 2) -> int:
    """Slices fetched per grid step: the largest convenient divisor of
    S that fits the scoped-VMEM budget. Bigger blocks amortize
    per-step DMA issue cost. Each operand's block is t * 128 KB and
    Mosaic double-buffers it, so an 8-operand batch at t=32 bills
    64 MB and is rejected at compile time — the budget caps t by
    operand count instead."""
    return _largest_t(s_n, _VMEM_BUDGET // (2 * num_operands * _ROW_BYTES))


def _runs_view(v):
    """(S, cap, 2048) -> (S, cap/16, 16, 2048): a leading-dim split is
    layout-preserving (no lane retiling — contrast the (256, 128) view
    coarse_count_per_slice's docstring warns about), and makes each
    whole-row run a full trailing (16, 2048) block Mosaic can tile
    into a multi-slice fetch."""
    return v.reshape(v.shape[0], v.shape[1] // ROW_SPAN,
                     ROW_SPAN, 16 * _LANES)


def _uniform_kernel(tree, num_leaves, t, starts_ref, *refs):
    o_ref = refs[num_leaves]
    base = pl.program_id(0) * t

    def leaf(i):
        blk = refs[i][...]  # (t, 1, 16, 2048)
        keep = starts_ref[i] >= 0
        return jnp.where(keep, blk, jnp.uint32(0))

    folded = fold_tree(tree, leaf)
    # One full reduce per sub-slice: Mosaic lowers scalar full-reduces
    # into SMEM, but not vector-element extracts (a partial
    # axis=(1,2,3) reduce + per[j] store fails "Invalid input layout").
    for j in range(t):
        o_ref[0, base + j] = csa_popcount_sum(folded[j])


def coarse_count_uniform(views, starts, tree, *,
                         interpret: bool = False):
    """ONE pallas_call of per-slice coarse counts for the UNIFORM
    layout: every slice stores each leaf at the SAME row-run index —
    true for any densely staged pool, detected host-side from the
    keys (serve._leaf_arrays). The per-(leaf, slice) starts table
    collapses to ONE scalar per leaf, so a grid step can fetch t
    CONSECUTIVE slices as one (t, 1, 16, 2048) block: per-step DMA
    issue cost amortizes t-fold (not measured on the attached chip).

    views:  tuple per leaf of the NATIVE (S, cap_i, 2048) uint32 pool.
    starts: (L,) int32 — one signed row-run index per leaf; negative =
            leaf absent everywhere (counts all-zero).
    Returns (1, S) int32 per-slice counts (slice ownership masks apply
    AFTER, at the serving layer)."""
    num_leaves = len(views)
    s_n = views[0].shape[0]
    t = _uniform_pick_t(s_n, num_leaves)
    views = tuple(_runs_view(v) for v in views)

    def leaf_spec(leaf):
        return pl.BlockSpec(
            (t, 1, ROW_SPAN, 16 * _LANES),
            lambda i, starts_ref, leaf=leaf: (
                i, jnp.maximum(starts_ref[leaf], 0), 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_n // t,),
        in_specs=[leaf_spec(leaf) for leaf in range(num_leaves)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_uniform_kernel, tree, num_leaves, t),
        out_shape=jax.ShapeDtypeStruct((1, s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *views)


def _uniform_batch_kernel(tree, num_leaves, t, starts_ref, *refs):
    o_ref = refs[num_leaves]
    b = pl.program_id(0)
    base = pl.program_id(1) * t

    def leaf(i):
        blk = refs[i][...]
        keep = starts_ref[b * num_leaves + i] >= 0
        return jnp.where(keep, blk, jnp.uint32(0))

    folded = fold_tree(tree, leaf)
    for j in range(t):
        o_ref[b, base + j] = csa_popcount_sum(folded[j])


def coarse_count_uniform_batch(pools, starts, tree, *,
                               interpret: bool = False):
    """Uniform-layout twin of coarse_count_identity_batch: grid
    (B, S/t), each step fetching t consecutive slices of each leaf
    position's row as one block (see coarse_count_uniform).

    pools:  tuple per LEAF POSITION of the NATIVE (S, cap_l, 2048)
            uint32 pool.
    starts: (B*L,) int32 scalar row-run index per slot (slot =
            b*L + l); negative = absent.
    Returns (B, S) int32 per-(query, slice) counts."""
    slots = int(starts.shape[0])
    num_leaves = len(pools)
    batch = slots // num_leaves
    assert batch * num_leaves == slots, (slots, num_leaves)
    s_n = pools[0].shape[0]
    t = _uniform_pick_t(s_n, num_leaves)
    pools = tuple(_runs_view(v) for v in pools)

    def leaf_spec(leaf):
        return pl.BlockSpec(
            (t, 1, ROW_SPAN, 16 * _LANES),
            lambda b, i, starts_ref, leaf=leaf: (
                i, jnp.maximum(starts_ref[b * num_leaves + leaf], 0),
                0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, s_n // t),
        in_specs=[leaf_spec(leaf) for leaf in range(num_leaves)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_uniform_batch_kernel, tree, num_leaves, t),
        out_shape=jax.ShapeDtypeStruct((batch, s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *pools)


def _coarse_batch_kernel(tree, leaf_map, num_unique, starts_ref, *refs):
    o_ref = refs[num_unique]
    s = pl.program_id(0)
    blocks = []
    for u in range(num_unique):
        blk = refs[u][0, :, :]
        keep = starts_ref[u, s] >= 0
        blocks.append(jnp.where(keep, blk, jnp.uint32(0)))
    for b, lm in enumerate(leaf_map):
        o_ref[b, s] = csa_popcount_sum(
            fold_tree(tree, lambda i, lm=lm: blocks[lm[i]]))


def coarse_count_batch_per_slice(views, starts, tree, leaf_map, *,
                                 interpret: bool = False):
    """ONE pallas_call producing per-(query, slice) counts for a
    SHARED-READ coarse batch: B queries of one tree shape over U
    unique whole-row leaves.

    The device analog of the reference's per-fragment row cache
    serving many queries from one materialized row (fragment.go:
    332-367 + BitmapCache) — same sharing the XLA scan program
    (mesh.compile_serve_count_batch_shared) expresses, but as a
    PIPELINED GRID instead of a lax.scan: the scan's 960 sequential
    steps of tiny compute are latency-bound, while a grid step's DMA
    prefetch overlaps the previous step's compute. Each step streams
    the U unique 128 KB row runs HBM->VMEM
    exactly once (U * 128 KB resident, e.g. 1 MB for the headline's 8
    rows) and computes all B folds from VMEM, so HBM traffic scales
    with UNIQUE leaves — the 28-pair headline reads 8 rows/slice, not
    56 — and no gathered intermediate is ever written back.

    views:    tuple per UNIQUE leaf of the NATIVE (S, cap_u, 2048)
              uint32 pool (cap_u % 16 == 0; leaves may share one pool
              object — see coarse_count_per_slice on why the native
              shape, not a (256, 128) view, is load-bearing).
    starts:   (U, S) int32 signed row-run index; negative = absent or
              masked out (block read clipped and zeroed).
    tree:     nested op list with numbered leaf POSITIONS
              (plan._tree_signature).
    leaf_map: STATIC tuple per query: leaf position -> unique index.

    Returns (B, S) int32 per-(query, slice) counts (each <= 2^20).
    SMEM budget: the (B, S) output + (U, S) prefetch table — at the
     28-query/960-slice headline that is ~215 KB, well inside the
    1 MB/core the general kernel's tables overflowed."""
    num_unique, s_n = starts.shape

    def leaf_spec(u):
        return pl.BlockSpec(
            (1, ROW_SPAN, 16 * _LANES),
            lambda s, starts_ref, u=u: (
                s, jnp.maximum(starts_ref[u, s], 0), 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_n,),
        in_specs=[leaf_spec(u) for u in range(num_unique)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_coarse_batch_kernel, tree, tuple(leaf_map),
                          num_unique),
        out_shape=jax.ShapeDtypeStruct((len(leaf_map), s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *views)


def _shared_uniform_kernel(tree, leaf_map, num_unique, t,
                           starts_ref, *refs):
    o_ref = refs[num_unique]
    base = pl.program_id(0) * t

    # One slice of the fetched block per loop iteration: the body is
    # compiled once, so what stays live in VMEM beside the operand
    # buffers is ONE iteration's U masked rows and B fold temporaries
    # (_shared_uniform_pick_t bills exactly that). Folding the whole
    # (t, 1, 16, 2048) block per query instead kept t times as much
    # live, and the 28-pair/8-row composition overflowed the window.
    def one_slice(j, carry):
        blocks = []
        for u in range(num_unique):
            blk = refs[u][j, 0]  # (16, 2048)
            keep = starts_ref[u] >= 0
            blocks.append(jnp.where(keep, blk, jnp.uint32(0)))
        for b, lm in enumerate(leaf_map):
            o_ref[b, base + j] = csa_popcount_sum(
                fold_tree(tree, lambda i, lm=lm: blocks[lm[i]]))
        return carry

    lax.fori_loop(0, t, one_slice, 0)


def _shared_uniform_pick_t(s_n: int, num_unique: int, batch: int) -> int:
    """_uniform_pick_t for the shared-read kernel, whose live set is
    not only its operands: the double-buffered operand blocks
    (2 * U * t rows of 128 KB) plus one loop iteration's masked rows
    and fold results (U + B rows), all inside the same scoped window."""
    room = _VMEM_BUDGET - (num_unique + batch) * _ROW_BYTES
    return _largest_t(s_n, room // (2 * num_unique * _ROW_BYTES))


def coarse_count_shared_uniform(views, starts, tree, leaf_map, *,
                                interpret: bool = False):
    """Uniform-layout twin of coarse_count_batch_per_slice: the U
    unique rows stream as (t, 1, 16, 2048) multi-slice blocks (see
    coarse_count_uniform) and all B folds for those t slices evaluate
    from VMEM. Combines both traffic savings: unique leaves read
    once per slice AND per-step DMA issue cost amortized t-fold.

    views:  tuple per UNIQUE leaf of the NATIVE (S, cap_u, 2048)
            uint32 pool.
    starts: (U,) int32 scalar row-run index per unique; negative =
            absent everywhere.
    Returns (B, S) int32."""
    num_unique = len(views)
    s_n = views[0].shape[0]
    t = _shared_uniform_pick_t(s_n, num_unique, len(leaf_map))
    views = tuple(_runs_view(v) for v in views)

    def leaf_spec(u):
        return pl.BlockSpec(
            (t, 1, ROW_SPAN, 16 * _LANES),
            lambda i, starts_ref, u=u: (
                i, jnp.maximum(starts_ref[u], 0), 0, 0))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s_n // t,),
        in_specs=[leaf_spec(u) for u in range(num_unique)],
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
    )
    return pl.pallas_call(
        functools.partial(_shared_uniform_kernel, tree, tuple(leaf_map),
                          num_unique, t),
        out_shape=jax.ShapeDtypeStruct((len(leaf_map), s_n), jnp.int32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(starts, *views)


def tree_count_pallas_coarse(words, starts, tree, *,
                             interpret: bool = False):
    """Fused popcount(eval_tree) over COARSE whole-row runs — ONE
    pallas_call for ANY slice count.

    The general kernel above needs (L, S, 16) idx+hit prefetch tables;
    at headline scale they overflow the 1 MB SMEM budget and force a
    lax.scan of slab launches, each paying a dispatch. When every leaf
    row is staged as one contiguous 16-aligned container run
    (mesh.coarse_row_starts — true for dense rows, which staging
    sorts and pads), the per-slice
    address state collapses to ONE signed int per (leaf, slice): the
    row-run index, negative where the slice holds no part of the row.
    That is 1/48th the SMEM (4 bytes vs 2x16x4), so even a 3072-slice
    x 8-leaf TABLE fits one launch with headroom, and each grid step
    streams each leaf's whole 128 KB row run from HBM exactly once —
    no gathered intermediate is ever written back (the XLA path's ~3x
    traffic overhead, kernels.py header note).

    Count range: the scalar accumulator is int32, exact to 2^31-1 set
    bits per SHARD (~2048 fully-dense slices) — the same bound as the
    general kernel above and the XLA mesh path. >2^31-bit shards are
    the SERVING layer's regime, whose programs split per-slice counts
    into 16-bit limbs before the psum (compile_serve_count*,
    combine_limbs) precisely for that.

    words:  (S, cap, 2048) uint32 pool, cap % 16 == 0.
    starts: (L, S) int32 signed row-run index (pos // 16, or any
            negative where absent/masked out).
    tree:   nested op list with numbered leaves (plan._tree_signature).

    Returns the shard's total count as a scalar int32.
    """
    num_leaves, s_n = starts.shape
    cap = words.shape[1]
    assert cap % 16 == 0, cap
    # The pool streams in its NATIVE shape — one block = one whole row
    # run, the (1, 16, 2048) tile at row-run index starts[l, s].
    per_slice = coarse_count_per_slice(
        (words,) * num_leaves, starts, tree, interpret=interpret)
    return per_slice.sum(dtype=jnp.int32)


def tree_count_pallas(words, idx, hit, tree, *, interpret: bool = False):
    """Fused popcount(eval_tree) over one shard's container pool.

    words: (S, cap, 2048) uint32 — the local slices' pools.
    idx:   (L, S, 16) int32 — per leaf/slice/sub-key container index
           into `cap` (clipped; garbage where hit == 0).
    hit:   (L, S, 16) int32 — 1 where the container is really present.
    tree:  nested op list with numbered leaves (plan._tree_signature).

    Returns the shard's total count as a scalar int32. Shards whose
    prefetch tables exceed the SMEM budget run fixed-size slice slabs
    via lax.scan plus one remainder call — a fixed slab (not a divisor
    of S) so a prime slice count can't degrade to per-slice launches.
    """
    num_leaves, s_n, r_n = idx.shape
    cap = words.shape[1]
    # (S, cap, 16, 128): per-container blocks whose minor dims satisfy
    # the TPU (8, 128) tiling constraint — (1, 1, 2048) blocks do not.
    words4 = words.reshape(s_n, cap, _SUBLANES, _LANES)

    chunk = max(1, _PREFETCH_SLICES_PER_LEAF // num_leaves)
    if s_n <= chunk:
        return _tree_count_call(words4, idx, hit, tree, num_leaves, interpret)

    c, rem = divmod(s_n, chunk)
    main = c * chunk
    words_r = words4[:main].reshape(c, chunk, cap, _SUBLANES, _LANES)
    idx_r = idx[:, :main].reshape(num_leaves, c, chunk, r_n).transpose(
        1, 0, 2, 3)
    hit_r = hit[:, :main].reshape(num_leaves, c, chunk, r_n).transpose(
        1, 0, 2, 3)

    def body(acc, xs):
        w, ix, ht = xs
        return acc + _tree_count_call(w, ix, ht, tree, num_leaves,
                                      interpret), None

    acc, _ = lax.scan(body, jnp.int32(0), (words_r, idx_r, hit_r))
    if rem:
        acc = acc + _tree_count_call(words4[main:], idx[:, main:],
                                     hit[:, main:], tree, num_leaves,
                                     interpret)
    return acc


# -- sorted-array (sparse container) intersect-count ---------------------------
#
# Pallas variant of bitops.sparse_pair_intersect_counts — the device
# array×array kernel class (reference roaring.go:1270-1351) for
# containers staged as sorted value lists. The TPU has no per-lane
# dynamic gather, so instead of the XLA path's binary-search ladder this
# kernel brute-forces membership with lane-parallel broadcast compares:
# each grid step loads a block of containers and, per 128-value a-slab,
# tests all K b-values at once. That is O(K^2/lanes) VPU work vs the
# gather ladder's O(K log K) HBM round-trips — which of the two wins is
# hardware-dependent (gathers are expensive on TPU, compares are nearly
# free), so ops/calibrate.py races them and the winner earns the
# dispatch, same contract as the dense count backends.

_SPARSE_BM = 8      # containers per grid step
_SPARSE_AK = 128    # a-values per fori step: one full lane tile
_SPARSE_BK = 1024   # b-lane slab per static inner step (VMEM bound)


def _sparse_pair_kernel(bm, k, a_ref, al_ref, b_ref, bl_ref, o_ref):
    al = al_ref[...]
    bl = bl_ref[...]
    bk = min(k, _SPARSE_BK)

    def body(c, acc):
        a = a_ref[:, pl.ds(c * _SPARSE_AK, _SPARSE_AK)]
        hit = jnp.zeros((bm, _SPARSE_AK), jnp.bool_)
        # Static b-slab loop: container values are duplicate-free, so
        # membership (any-match) equals match count and slabs OR. Each
        # slab is a static slice of the REF: slicing a loaded value
        # together with a new axis (b[:, None, lo:hi]) traces to a
        # gather, which Mosaic refuses ("Shape mismatch in input,
        # indices and output") for every K above one slab.
        for lo in range(0, k, bk):
            hi = min(k, lo + bk)
            b = b_ref[:, lo:hi]
            valid_b = (lax.broadcasted_iota(jnp.int32, (bm, hi - lo), 1)
                       + lo) < bl
            eq = (a[:, :, None] == b[:, None, :]) & valid_b[:, None, :]
            hit = hit | eq.any(axis=-1)
        a_pos = (lax.broadcasted_iota(jnp.int32, (bm, _SPARSE_AK), 1)
                 + c * _SPARSE_AK)
        hits = hit & (a_pos < al)
        return acc + hits.sum(axis=-1, keepdims=True).astype(jnp.int32)

    o_ref[...] = lax.fori_loop(0, k // _SPARSE_AK, body,
                               jnp.zeros((bm, 1), jnp.int32))


@functools.partial(jax.jit, static_argnames=("interpret",))
def pallas_sparse_pair_counts(a_vals, a_len, b_vals, b_len, *,
                              interpret: bool = False):
    """Per-container |a ∩ b| over batched sorted-array containers —
    same contract as bitops.sparse_pair_intersect_counts (values
    padded with 0xFFFF, lens give real cardinality; exact for every
    u16 value including 65535, because validity comes from the len
    masks, never the pad value).

    a_vals/b_vals: (..., K) integer values; a_len/b_len: (...,).
    Returns (...,) int32."""
    shape = a_vals.shape[:-1]
    ka = a_vals.shape[-1]
    kb = b_vals.shape[-1]  # operands may come from different pools
    n = 1
    for d in shape:
        n *= d
    a = a_vals.reshape(n, ka).astype(jnp.int32)
    b = b_vals.reshape(n, kb).astype(jnp.int32)
    al = a_len.reshape(n, 1).astype(jnp.int32)
    bl = b_len.reshape(n, 1).astype(jnp.int32)
    kp = max(_SPARSE_AK,
             -(-max(ka, kb) // _SPARSE_AK) * _SPARSE_AK)
    if kp != ka:
        # Value padding is arbitrary (zeros): the len masks reject it.
        a = jnp.pad(a, ((0, 0), (0, kp - ka)))
    if kp != kb:
        b = jnp.pad(b, ((0, 0), (0, kp - kb)))
    n_p = -(-n // _SPARSE_BM) * _SPARSE_BM
    if n_p != n:
        a = jnp.pad(a, ((0, n_p - n), (0, 0)))
        b = jnp.pad(b, ((0, n_p - n), (0, 0)))
        al = jnp.pad(al, ((0, n_p - n), (0, 0)))
        bl = jnp.pad(bl, ((0, n_p - n), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_sparse_pair_kernel, _SPARSE_BM, kp),
        out_shape=jax.ShapeDtypeStruct((n_p, 1), jnp.int32),
        grid=(n_p // _SPARSE_BM,),
        in_specs=[
            pl.BlockSpec((_SPARSE_BM, kp), lambda i: (i, 0)),
            pl.BlockSpec((_SPARSE_BM, 1), lambda i: (i, 0)),
            pl.BlockSpec((_SPARSE_BM, kp), lambda i: (i, 0)),
            pl.BlockSpec((_SPARSE_BM, 1), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((_SPARSE_BM, 1), lambda i: (i, 0)),
        interpret=interpret,
    )(a, al, b, bl)
    return out[:n, 0].reshape(shape)


def use_sparse_pallas() -> bool:
    """Dispatch switch for the sorted-array intersect kernel — the
    sparse twin of use_pallas(): never on non-TPU backends, else the
    PILOSA_TPU_SPARSE_BACKEND pin or the calibrated race winner."""
    if jax.default_backend() != "tpu":
        return False
    from .calibrate import resolve_sparse_backend

    return resolve_sparse_backend() == "pallas"
