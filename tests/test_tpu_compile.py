"""The main path's kernels and serve programs, compiled for the chip.

Interpret mode and the CPU mesh say a kernel computes the right thing;
they do not say the chip's compiler will take it. The TPU compiler is
installed here and compiles for a chip that is described, not attached
(`jax.experimental.topologies`), so these tests hold every program the
serving dispatch can select at the headline shape (960 slices, an
8-row dense pool of (960, 128, 2048) uint32, `_MAX_BATCH` = 16, 2-8
leaves, shared groups up to the 28 pairs of 8 rows) to what that
compiler accepts: VMEM and SMEM budgets, tiling, compile-time HBM
accounting. Nothing runs, so nothing here is a result or a time.

The topology is described inside a module-scoped fixture, never while
a module is imported, in a skipif or in parametrize: only one process
may load the TPU library, and under pytest-xdist every worker imports
every test file. Keep these tests in this one file (a second file
could land on a worker that cannot load the library, and skip).
"""

import itertools
import re

import numpy as np
import pytest

S, CAP = 960, 128  # the BASELINE.json "1B-col Intersect+Count" pool
AND2 = ["and", ["leaf", 0], ["leaf", 1]]
PAIRS28 = tuple(itertools.combinations(range(8), 2))


def nary(op, n):
    tree = ["leaf", 0]
    for i in range(1, n):
        tree = [op, tree, ["leaf", i]]
    return tree


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        t = topologies.get_topology_desc(platform="tpu",
                                         topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip is written to the persistent
    # cache but cannot be read back without one (it warns and compiles
    # again), so the cache is off around these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield t
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


class Chip:
    """Shapes on a mesh of described devices (1 chip, or the 2x2)."""

    def __init__(self, devices):
        from jax.sharding import Mesh

        from pilosa_tpu.parallel.mesh import SLICE_AXIS

        self.axis = SLICE_AXIS
        self.mesh = Mesh(np.array(devices), (SLICE_AXIS,))

    def _sds(self, shape, dtype, spec):
        import jax
        from jax.sharding import NamedSharding

        return jax.ShapeDtypeStruct(
            shape, dtype, sharding=NamedSharding(self.mesh, spec))

    def sliced(self, dtype, *extra, s=S):
        from jax.sharding import PartitionSpec as P

        return self._sds((s,) + extra, dtype, P(self.axis))

    def repl(self, dtype, *shape):
        from jax.sharding import PartitionSpec as P

        return self._sds(shape, dtype, P())

    def pool(self, cap=CAP):
        return self.sliced(np.uint32, cap, 2048)

    def starts_valid(self, n, s=S):
        return (tuple(self.sliced(np.int32, s=s) for _ in range(n)),
                tuple(self.sliced(np.uint32, s=s) for _ in range(n)))

    def write_batch(self, width, s=S):
        """pack_mutation_batches' (S, B) arrays and its scalar."""
        return (*(self.sliced(t, width, s=s) for t in (
            np.int32, np.int32, np.uint32, np.uint32)),
            self.repl(np.int32))

    def idx_hit(self, n):
        return (tuple(self.sliced(np.int32, 16) for _ in range(n)),
                tuple(self.sliced(np.uint32, 16) for _ in range(n)))


@pytest.fixture(scope="module")
def chip(topo):
    return Chip(topo.devices[:1])


@pytest.fixture(scope="module")
def four(topo):
    return Chip(topo.devices)


def compiled(fn, *args):
    """Lower and compile; what the chip's compiler would raise, this
    raises. Returns (HLO text, memory analysis)."""
    import jax

    fn = fn if hasattr(fn, "lower") else jax.jit(fn)
    c = fn.lower(*args).compile()
    return c.as_text(), c.memory_analysis()


def kernels_in(text):
    return text.count("tpu_custom_call")


# -- raw kernels -------------------------------------------------------------

def test_pair_count_kernel(chip):
    """fused_pair_count's kernel over a whole 960-slice row pair."""
    from pilosa_tpu.ops.kernels import _pallas_pair_count

    a = chip.repl(np.uint32, S * 16, 2048)
    text, _ = compiled(_pallas_pair_count, a, a)
    assert kernels_in(text) == 1


@pytest.mark.parametrize("k", [512, 2048, 4096])
def test_sparse_pair_kernel(chip, k):
    """The sorted-array intersect kernel at real value capacities: one
    b-slab (512, the calibrator's shape) and several (a 3%-dense
    container pads to 2048; 4096 is the array break-even). Above one
    slab it did not lower before PR 21."""
    from pilosa_tpu.ops.kernels import pallas_sparse_pair_counts

    n = S * 16
    text, _ = compiled(pallas_sparse_pair_counts,
                       chip.repl(np.uint16, n, k), chip.repl(np.int32, n),
                       chip.repl(np.uint16, n, k), chip.repl(np.int32, n))
    assert kernels_in(text) == 1


def test_general_tree_kernels(chip):
    """tree_count_pallas (slab scan) and its coarse twin, the kernels
    behind compile_mesh_count's Pallas backend."""
    from pilosa_tpu.ops.kernels import (tree_count_pallas,
                                        tree_count_pallas_coarse)

    w = chip.repl(np.uint32, S, CAP, 2048)
    text, _ = compiled(
        lambda w, i, h: tree_count_pallas(w, i, h, AND2), w,
        chip.repl(np.int32, 2, S, 16), chip.repl(np.int32, 2, S, 16))
    assert kernels_in(text) >= 1
    text, _ = compiled(
        lambda w, st: tree_count_pallas_coarse(w, st, AND2), w,
        chip.repl(np.int32, 2, S))
    assert kernels_in(text) == 1


# -- what _pick_count and _count_program can select --------------------------

@pytest.mark.parametrize("op,leaves", [("and", 1), ("and", 2), ("or", 2),
                                       ("andnot", 2), ("and", 8),
                                       ("andnot", 8)])
def test_lone_coarse_pallas(chip, op, leaves):
    """A group of one: the per-slice kernel and, for a uniformly
    staged pool, the multi-slice-fetch kernel."""
    from pilosa_tpu.parallel.mesh import (
        compile_serve_count_coarse_pallas,
        compile_serve_count_coarse_pallas_uniform)

    tree = nary(op, leaves)
    w = (chip.pool(),) * leaves
    mask = chip.sliced(np.int32)
    text, _ = compiled(
        compile_serve_count_coarse_pallas(chip.mesh, tree, leaves),
        w, *chip.starts_valid(leaves), mask)
    assert kernels_in(text) == 1
    text, _ = compiled(
        compile_serve_count_coarse_pallas_uniform(chip.mesh, tree, leaves, 1),
        w, chip.repl(np.int32, leaves), mask)
    assert kernels_in(text) == 1


@pytest.mark.parametrize("op,leaves", [("and", 2), ("or", 2), ("and", 8)])
def test_padded_batch_pallas(chip, op, leaves):
    """Every multi-request group runs at _MAX_BATCH: the identity-batch
    kernel and its uniform twin."""
    from pilosa_tpu.parallel.mesh import (
        compile_serve_count_coarse_pallas_batch,
        compile_serve_count_coarse_pallas_uniform)
    from pilosa_tpu.parallel.serve import MeshManager

    b = MeshManager._MAX_BATCH
    tree = nary(op, leaves)
    w = (chip.pool(),) * leaves
    mask = chip.sliced(np.int32)
    text, _ = compiled(
        compile_serve_count_coarse_pallas_batch(chip.mesh, tree, leaves, b),
        w, *chip.starts_valid(leaves * b), mask)
    assert kernels_in(text) == 1
    text, _ = compiled(
        compile_serve_count_coarse_pallas_uniform(chip.mesh, tree, leaves, b),
        w, chip.repl(np.int32, leaves * b), mask)
    assert kernels_in(text) == 1


@pytest.mark.parametrize("name,leaf_map,uniques", [
    ("28-of-8", PAIRS28, 8),          # every pair of the 8 headline rows
    ("16-of-8", PAIRS28[:16], 8),     # the widest group the batcher forms
    ("16-of-11", tuple((i, i + 1) for i in range(10))
     + tuple((i, i + 2) for i in range(6)), 11),  # most uniques the
    #                                   aliased-argument budget admits
])
def test_shared_read_pallas(chip, name, leaf_map, uniques):
    """The shared-read programs. The uniform one was refused at 28-of-8
    before PR 21 (17.63 MB of a 16 MB scoped VMEM window): its budget
    counted the operand blocks and not the fold temporaries."""
    from pilosa_tpu.parallel.mesh import (
        compile_serve_count_batch_shared_pallas,
        compile_serve_count_batch_shared_pallas_uniform)

    w = (chip.pool(),) * uniques
    mask = chip.sliced(np.int32)
    text, _ = compiled(
        compile_serve_count_batch_shared_pallas(
            chip.mesh, AND2, leaf_map, uniques),
        w, *chip.starts_valid(uniques), mask)
    assert kernels_in(text) == 1
    text, _ = compiled(
        compile_serve_count_batch_shared_pallas_uniform(
            chip.mesh, AND2, leaf_map, uniques),
        w, chip.repl(np.int32, uniques), mask)
    assert kernels_in(text) == 1


@pytest.mark.parametrize("program", ["coarse_b16", "shared_28_of_8",
                                     "general_b16", "fused_8", "general_8"])
def test_xla_count_programs(chip, program):
    """The XLA twins and the programs with no Pallas form (the fused
    lone count, the general container gather): what `auto` serves when
    the calibrator picks XLA. Their refusal would be HBM, at compile
    time, where every aliased leaf operand is billed as its own pool."""
    from pilosa_tpu.parallel import mesh as M

    mask = chip.sliced(np.int32)
    w = chip.pool()
    if program == "coarse_b16":
        fn = M.compile_serve_count(chip.mesh, AND2, 2, 16, runs=True)
        args = ((w, w), *chip.starts_valid(32), mask)
    elif program == "shared_28_of_8":
        fn = M.compile_serve_count_batch_shared(chip.mesh, AND2, PAIRS28, 8)
        args = ((w,) * 8, *chip.starts_valid(8), mask)
    elif program == "general_b16":
        fn = M.compile_serve_count(chip.mesh, AND2, 2, 16)
        args = ((w, w), *chip.idx_hit(32), mask)
    elif program == "fused_8":
        fn = M.compile_serve_count(chip.mesh, nary("andnot", 8), 8,
                                   host_meta=True)
        args = ((w,) * 8, chip.repl(np.int32, 8, S, 16),
                chip.repl(np.uint32, 8, S, 16), chip.repl(np.int32, S))
    else:
        fn = M.compile_serve_count(chip.mesh, nary("or", 8), 8, 1)
        args = ((w,) * 8, *chip.idx_hit(8), mask)
    text, mem = compiled(fn, *args)
    assert kernels_in(text) == 0
    # "Used ... of 15.75G hbm" in the compiler's words, G = 2**30.
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 15.75 * 2**30


# -- the sparse path, TopN, BSI, writes --------------------------------------

@pytest.mark.parametrize("kind,backend", [("ss", "pallas"), ("ss", "xla"),
                                          ("sd", "xla"), ("ds", "xla")])
def test_sparse_pair_programs(chip, kind, backend):
    """_sparse_pair_fn's programs over a 3%-dense pool (32 containers a
    slice, values padded to 2048) against itself and a dense pool."""
    from pilosa_tpu.parallel.mesh import compile_serve_count_sparse_pair

    sparse = (chip.sliced(np.uint16, 32, 2048), chip.sliced(np.int32, 32))
    dense = (chip.pool(cap=32),)
    meta = (chip.repl(np.int32, S, 16), chip.repl(np.uint32, S, 16))
    text, _ = compiled(
        compile_serve_count_sparse_pair(chip.mesh, "and", kind,
                                        backend=backend),
        dense if kind == "ds" else sparse,
        dense if kind == "sd" else sparse,
        *meta, *meta, chip.repl(np.int32, S))
    assert kernels_in(text) == (1 if backend == "pallas" else 0)


@pytest.mark.parametrize("program", ["topn_4096_rows", "topn_src",
                                     "tanimoto", "bsi_sum_planes",
                                     "apply_writes", "patch_containers"])
def test_row_count_and_write_programs(chip, program):
    """TopN over 4,096 rows (plain, with a src tree, tanimoto), the
    BSI Sum's per-plane counts (a 64-row space), the incremental
    write scatter and the write of created containers' keys into free
    slots (the keys alone: no pool passes through it), all over the
    960-slice pool."""
    from pilosa_tpu.parallel import mesh as M

    keys, w, mask = chip.sliced(np.int32, CAP), chip.pool(), \
        chip.sliced(np.int32)
    index = M.ShardedIndex(keys=keys, words=w)
    if program == "topn_4096_rows":
        fn, args = M.compile_serve_row_counts(chip.mesh, 4096), (index, mask)
    elif program == "bsi_sum_planes":
        fn, args = M.compile_serve_row_counts(chip.mesh, 64), (index, mask)
    elif program == "topn_src":
        fn = M.compile_serve_row_counts_src(chip.mesh, AND2, 2, 4096)
        args = (keys, w, (w, w), *chip.idx_hit(2), mask)
    elif program == "tanimoto":
        fn = M.compile_serve_row_counts_tanimoto(chip.mesh, ["leaf", 0],
                                                 1, 4096)
        args = (keys, w, (w,), *chip.idx_hit(1), mask)
    elif program == "apply_writes":
        fn = M.compile_serve_apply_writes(chip.mesh)
        args = (w, *chip.write_batch(8))
    else:
        fn = M.compile_serve_patch_containers(chip.mesh)
        args = (keys, chip.sliced(np.int32, 8), chip.sliced(np.int32, 8))
    _, mem = compiled(fn, *args)
    if program == "patch_containers":
        assert mem.argument_size_in_bytes + mem.temp_size_in_bytes \
            + mem.output_size_in_bytes < 4 * S * CAP * 4


def test_bsi_range_ladder_through_the_count_kernels(chip):
    """A BSI comparison is lowered to the and/or/andnot tree language
    (bsi/lower.py) and counted by the same programs: `v >= 45` over a
    7-plane field is 13 leaves, through the uniform Pallas kernel and
    the fused XLA program a lone Count(Range(...)) takes. (Every leaf
    is billed a pool of its own at compile time, so on this pool a
    ladder of 17 leaves or more is refused by any program: see
    test_a_refusal_is_recognised_as_one.)"""
    from pilosa_tpu.bsi import lower as L
    from pilosa_tpu.bsi.field import FieldSchema
    from pilosa_tpu.parallel.mesh import (
        compile_serve_count, compile_serve_count_coarse_pallas_uniform)
    from pilosa_tpu.parallel.plan import _tree_signature

    leaves: list = []
    shape = L.to_shape(L.cond_tree(FieldSchema("v", -100, 100), ">=", 45),
                       "f", "bsi.v", leaves)
    n = len(leaves)
    assert n == 13, n
    sig = _tree_signature(shape)
    w, mask = (chip.pool(),) * n, chip.sliced(np.int32)
    text, _ = compiled(
        compile_serve_count_coarse_pallas_uniform(chip.mesh, sig, n, 1),
        w, chip.repl(np.int32, n), mask)
    assert kernels_in(text) == 1
    compiled(compile_serve_count(chip.mesh, sig, n, host_meta=True), w,
             chip.repl(np.int32, n, S, 16), chip.repl(np.uint32, n, S, 16),
             chip.repl(np.int32, S))


# -- four chips --------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_four_chip_program_reduces_over_the_interconnect(chip, four,
                                                         backend):
    """The same serve program on the 2x2: an all-reduce joins the
    shards' limbs, and each device is billed a quarter of the pool."""
    from pilosa_tpu.parallel import mesh as M

    def build(c):
        mask = c.sliced(np.int32)
        if backend == "pallas":
            return (M.compile_serve_count_coarse_pallas_uniform(
                c.mesh, AND2, 2, 16),
                (c.pool(), c.pool()), c.repl(np.int32, 32), mask)
        return (M.compile_serve_count(c.mesh, AND2, 2, 16, runs=True),
                (c.pool(), c.pool()), *c.starts_valid(32), mask)

    one_text, one_mem = compiled(*build(chip))
    text, mem = compiled(*build(four))
    assert "all-reduce" in text
    assert kernels_in(text) == kernels_in(one_text)
    pool_bytes = 2 * S * CAP * 2048 * 4
    assert abs(one_mem.argument_size_in_bytes - pool_bytes) < 0.01 * pool_bytes
    assert abs(4 * mem.argument_size_in_bytes - pool_bytes) \
        < 0.01 * pool_bytes


@pytest.mark.parametrize("program,n", [("coarse", 2), ("coarse", 8),
                                       ("fused", 8), ("apply_writes", 1),
                                       ("patch_containers", 0)])
def test_seg_2b_x4_programs_over_the_sharded_pool(four, program, n):
    """What benchmarks/configs/seg-2b-x4 serves, at its size: 1,920
    slices sharded over the 2x2, 480 a device. The herd's count_coarse
    at batch 1 (xla, 2 and 8 leaves), the lone fused count, the write
    scatter: a device is billed its quarter of the 2 GB pool once for
    each of the program's n pool operands, and the counts join in
    all-reduces."""
    from pilosa_tpu.parallel import mesh as M

    s2 = 1920
    w, mask = four.sliced(np.uint32, CAP, 2048, s=s2), \
        four.sliced(np.int32, s=s2)
    if program == "coarse":
        fn = M.compile_serve_count(four.mesh, nary("or", n), n, 1,
                                   runs=True)
        args = ((w,) * n, tuple(four.sliced(np.int32, s=s2)
                                for _ in range(n)),
                tuple(four.sliced(np.uint32, s=s2) for _ in range(n)), mask)
    elif program == "fused":
        fn = M.compile_serve_count(four.mesh, nary("andnot", n), n,
                                   host_meta=True)
        args = ((w,) * n, four.repl(np.int32, n, s2, 16),
                four.repl(np.uint32, n, s2, 16), four.repl(np.int32, s2))
    elif program == "apply_writes":
        fn = M.compile_serve_apply_writes(four.mesh)
        args = (w, *four.write_batch(8, s=s2))
    else:  # the created containers' keys: no pool operand at all
        fn = M.compile_serve_patch_containers(four.mesh)
        args = (four.sliced(np.int32, CAP, s=s2),
                four.sliced(np.int32, 8, s=s2),
                four.sliced(np.int32, 8, s=s2))
    text, mem = compiled(fn, *args)
    quarter = s2 // 4 * CAP * 2048 * 4
    assert quarter == 503_316_480
    # The compiler bills every aliased leaf operand as a pool of its own.
    assert n * quarter <= mem.argument_size_in_bytes \
        < 1.01 * n * quarter + 2**20
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes \
        < 15.75 * 2**30
    assert ("all-reduce" in text) == (program in ("coarse", "fused"))


# -- the xla coarse programs read the pool where it lies ----------------------

_DTYPE_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
                "f64": 8}
# Instructions that hand a buffer on without moving a byte of it.
_NO_BYTES_MOVE = {"parameter", "bitcast", "tuple", "get-tuple-element",
                  "while"}


def instructions(text):
    """(name, opcode, bytes of the largest array in the result) of every
    instruction of an HLO module's text, fused computations included."""
    for line in text.splitlines():
        head, eq, rest = line.partition(" = ")
        if not eq or not head.strip().startswith(("%", "ROOT ")):
            continue
        if rest.startswith("("):                 # a tuple-typed result
            depth = 0
            for end, ch in enumerate(rest):
                depth += (ch == "(") - (ch == ")")
                if depth == 0:
                    break
            typ, rest = rest[:end + 1], rest[end + 1:].lstrip()
        else:
            typ, _, rest = rest.partition(" ")
        sizes = [_DTYPE_BYTES[dt] * int(np.prod([int(d) for d in
                                                 dims.split(",") if d] or [1]))
                 for dt, dims in re.findall(r"\b([a-z]+\d*)\[([\d,]*)\]", typ)
                 if dt in _DTYPE_BYTES]
        yield head.split()[-1], rest.partition("(")[0], max(sizes, default=0)


@pytest.mark.parametrize("program,leaves,batch,devices", [
    ("coarse", 2, 1, 1), ("coarse", 8, 1, 1), ("coarse", 2, 16, 1),
    ("shared_28_of_8", 8, 28, 1), ("coarse", 2, 1, 4), ("coarse", 8, 1, 4)])
def test_xla_coarse_programs_do_not_copy_the_pool(chip, four, program,
                                                  leaves, batch, devices):
    """Until PR 30 both programs viewed the pool as (S, cap/16, 16*W) to
    pick a row with one index; the pool's two minor dimensions are
    tiled T(8,128) on the chip, so XLA made that view a copy of the
    whole pool for every leaf operand of every launch (`reshape.N
    u32[960,8,32768]`, 1 GB and 3.06 ms each: 79% of seg-1b.herd64's
    device time in the ledger's PR 29 line). Held here: no instruction
    that moves bytes has a result as large as one device's share of a
    pool operand, and the program's temporaries are the gathered rows
    (leaves x S_local x 128 KB) at batch 1, under one pool at batch 16
    and under 16 MB in the shared-read scan, which slices the pool in
    place. One chip at 960 slices; the 2x2 at seg-2b-x4's 1,920."""
    from pilosa_tpu.parallel import mesh as M

    c = chip if devices == 1 else four
    s = S * 2 if devices == 4 else S
    w, mask = c.sliced(np.uint32, CAP, 2048, s=s), c.sliced(np.int32, s=s)
    slots = leaves if program != "coarse" else leaves * batch
    args = ((w,) * leaves, *c.starts_valid(slots, s=s), mask)
    if program == "coarse":
        fn = M.compile_serve_count(c.mesh, nary("and", leaves), leaves,
                                   batch, runs=True)
    else:
        fn = M.compile_serve_count_batch_shared(c.mesh, AND2, PAIRS28, leaves)
    text, mem = compiled(fn, *args)
    shard = s // devices * CAP * 2048 * 4
    copies = [(name, op, size) for name, op, size in instructions(text)
              if size >= shard and op not in _NO_BYTES_MOVE]
    assert not copies, copies
    rows = leaves * (s // devices) * 16 * 2048 * 4
    limit = (16 * 2**20 if program != "coarse"
             else 1.1 * rows if batch == 1 else shard)
    assert mem.temp_size_in_bytes < limit, (mem.temp_size_in_bytes, limit)


@pytest.mark.parametrize("width", [8, 16])
@pytest.mark.parametrize("donate", [True, False],
                         ids=["in_place", "copied"])
@pytest.mark.parametrize("slices,cap,devices", [
    (960, 240, 1), (960, 128, 1), (1920, 128, 4)],
    ids=["topn-1b", "seg-1b", "seg-2b-x4"])
def test_write_program_scatters_where_the_pool_lies(chip, four, slices, cap,
                                                    devices, donate, width):
    """Until PR 35 the write program was one scatter of the batch's width
    (8 or more updates a slice), which the chip's compiler runs in
    another layout: `copy.11 u32[28800,16,8,128]{3,1,2,0}` in, `reshape.3
    u32[960,240,2048]` out, two passes over the whole pool and a
    pool-sized temporary for one bit, donated or not (12.2 ms of every
    SetBit over topn-1b's 1.89 GB; two thirds of that cell's device
    time in the ledger's PR 34 line). Held here, at the three staged
    pools of benchmarks/configs and both batch widths a refresh sends:
    the donated form aliases the pool, needs no temporary to speak of
    and has no instruction that moves a pool's worth of bytes but the
    scatter itself, in place inside the loop; the undonated form is the
    same program after exactly one copy."""
    from pilosa_tpu.parallel import mesh as M

    c = chip if devices == 1 else four
    text, mem = compiled(
        M.compile_serve_apply_writes(c.mesh, donate=donate),
        c.sliced(np.uint32, cap, 2048, s=slices),
        *c.write_batch(width, s=slices))
    shard = slices // devices * cap * 2048 * 4
    pool_sized = [(name, op) for name, op, size in instructions(text)
                  if size >= shard and op not in _NO_BYTES_MOVE]
    assert mem.temp_size_in_bytes < 2**20, mem.temp_size_in_bytes
    assert mem.alias_size_in_bytes == (shard if donate else 0)
    # The scatter itself, in place (its fusion inside the loop), and in
    # the undonated form the one copy it starts from: nothing else.
    assert sorted(op for _, op in pool_sized) == \
        ([] if donate else ["copy"]) + ["fusion", "scatter"], pool_sized
    assert not re.search(r"= u32\[\d+,16,8,128\]", text)  # the relayout


def _scan_step_ops(text):
    """The instructions of the program's while body that the chip runs as
    device ops of their own, and a device trace records as events, each
    step: fusions and dynamic slices outside scalar memory (the scalar
    core's adds, compares and selects are neither)."""
    body = re.search(r"body=%?([\w.\-]+)", text).group(1)
    start = text.index("\n%" + body + " (")
    lines = text[start:text.index("\n}", start)].splitlines()[1:]
    ops = []
    for line in lines:
        m = re.match(r"\s*(?:ROOT )?%?([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(", line)
        if m and m.group(3) in ("fusion", "dynamic-slice", "concatenate",
                                "copy") and "S(6)" not in m.group(2):
            ops.append(m.group(1))
    return ops


@pytest.mark.parametrize("leaf_map,uniques,devices,most", [
    (((0, 1), (1, 2)), 3, 1, 2), (((0, 1), (1, 2)), 3, 4, 2),
    (((0, 1), (1, 2), (2, 3), (3, 4)), 5, 1, 2),
    (PAIRS28[:16], 8, 1, 7 + 8)])
def test_shared_scan_step_is_few_device_ops(chip, four, leaf_map, uniques,
                                            devices, most):
    """The shared-read scan pays for every device op of its step 960 times
    a launch, in time and in the events of a device trace (a traced herd
    run: 470,000 of 492,000 events, and 70 s to stop the trace; PR 32).
    Up to _SHARED_NARROW_MAX queries a step is two: the fetch of its
    scalars and the fusion that gathers, folds and counts. The wide body
    is held to what it has."""
    from pilosa_tpu.parallel import mesh as M

    c = chip if devices == 1 else four
    s = S * 2 if devices == 4 else S
    w, mask = c.sliced(np.uint32, CAP, 2048, s=s), c.sliced(np.int32, s=s)
    text, _ = compiled(
        M.compile_serve_count_batch_shared(c.mesh, AND2, leaf_map, uniques),
        (w,) * uniques, *c.starts_valid(uniques, s=s), mask)
    ops = _scan_step_ops(text)
    assert 0 < len(ops) <= most, ops


# -- Star Trace: a sparse view read and written where it lies -----------------

ST_S, ST_C, ST_K, ST_LANG = 480, 10480, 128, 512  # startrace-500m, seed 3700000311


@pytest.mark.parametrize("program", ["src_leaf_sparse", "ranked_sparse",
                                     "write_donated", "write_copied",
                                     "topn_rank", "topn_gather"])
def test_star_trace_programs(chip, program):
    """startrace-500m's programs at its size: `language` dense, (480,
    512, 2048) words; `stargazer` sparse, (480, 10,480) keys and cards
    and (480, 10,480, 128) u16 values. The user-src TopN expands the
    sparse leaf into words over the dense pool; the language-src TopN
    ranks 2^20 users over the sorted values with no (S, R) temporary
    (2 GB at this size: the largest temporary is the gathered src rows
    of the containers' first values); a star's write
    runs in the pool's own buffers when donated (no temporary), and
    from one copy of them when not. The ranking of those 2^20 users
    runs where their (2, 2^20) limbs lie and leaves (2, 8) int32:
    phase 1's rows, phase 2's candidates' columns."""
    from pilosa_tpu.parallel import mesh as M

    keys, vals, cards = (chip.sliced(np.int32, ST_C, s=ST_S),
                         chip.sliced(np.uint16, ST_C, ST_K, s=ST_S),
                         chip.sliced(np.int32, ST_C, s=ST_S))
    lang_k, lang_w = (chip.sliced(np.int32, ST_LANG, s=ST_S),
                      chip.sliced(np.uint32, ST_LANG, 2048, s=ST_S))
    mask = chip.sliced(np.int32, s=ST_S)
    meta = ((chip.sliced(np.int32, 16, s=ST_S),),
            (chip.sliced(np.uint32, 16, s=ST_S),))
    pool = ST_S * ST_C * (ST_K * 2 + 8)
    if program == "src_leaf_sparse":
        fn = M.compile_serve_row_counts_src(chip.mesh, ["leaf", 0], 1, 32,
                                            leaf_formats=("s",))
        _, mem = compiled(fn, lang_k, lang_w, ((vals, cards),), *meta, mask)
        # The per-container src block of the dense program, as for a
        # dense src leaf: the language pool's size once.
        assert mem.temp_size_in_bytes < 1.01 * ST_S * ST_LANG * 2048 * 4
    elif program == "ranked_sparse":
        fn = M.compile_serve_row_counts_src_sparse(chip.mesh, ["leaf", 0],
                                                   1, 1 << 20)
        text, mem = compiled(fn, keys, vals, cards,
                             chip.sliced(np.int32, 256, s=ST_S),
                             chip.repl(np.int32), (lang_w,), *meta, mask)
        # The first value of every container gathers one 512-B src row:
        # (480, 10,480, 128) u32, 2.58 GB, and no (S, R) table anywhere
        # (2,690,210,816 B in all: PERF.md section 5, ROADMAP W7 (a)).
        assert mem.temp_size_in_bytes < 1.2 * ST_S * ST_C * 128 * 4
        assert not re.search(r"\[480,104857[67]\]", text)
    elif program.startswith("topn_"):
        limbs = chip.repl(np.int32, 2, 1 << 20)
        if program == "topn_rank":
            fn = M.compile_serve_topn_rank(chip.mesh, 1 << 20, 8)
            _, mem = compiled(fn, limbs, *(chip.repl(np.int32),) * 3)
        else:
            fn = M.compile_serve_topn_gather(chip.mesh)
            _, mem = compiled(fn, limbs, chip.repl(np.int32, 8))
        # A few (2^20,) vectors at most, nothing pool-sized; and (2, 8)
        # int32 out, one padded tile, not the 8 MB of limbs.
        assert mem.temp_size_in_bytes < 64 << 20
        assert mem.output_size_in_bytes <= 4096
    else:
        donate = program == "write_donated"
        fn = M.compile_serve_sparse_write(chip.mesh, donate=donate)
        b = chip.sliced(np.int32, 1, s=ST_S)
        _, mem = compiled(fn, keys, vals, cards, b, b, b,
                          chip.sliced(np.uint16, 1, ST_K, s=ST_S),
                          chip.repl(np.int32))
        assert mem.temp_size_in_bytes < 0.01 * pool
        assert (mem.alias_size_in_bytes > 0.99 * pool) is donate


# -- what a refusal looks like ------------------------------------------------

@pytest.mark.parametrize("leaves,slices,space", [(64, 64, "vmem"),
                                                 (24, 960, "hbm")])
def test_a_refusal_is_recognised_as_one(chip, leaves, slices, space):
    """Two programs the chip's compiler does refuse, to hold the serve
    layer's classifier to the installed compiler's real words: 64
    operand blocks overflow scoped VMEM, and 24 aliased leaves of the
    1 GB pool overflow HBM in XLA's compile-time accounting (every
    tree of 17 leaves or more over this pool does, in any program).
    Both say RESOURCE_EXHAUSTED; neither is a device OOM."""
    import jax

    from pilosa_tpu.ops.kernels import coarse_count_per_slice
    from pilosa_tpu.parallel.serve import (_is_compile_refusal,
                                           _is_resource_exhausted)

    tree = nary("and", leaves)
    w = chip.repl(np.uint32, slices, CAP, 2048)
    with pytest.raises(jax.errors.JaxRuntimeError) as err:
        compiled(lambda ws, st: coarse_count_per_slice(ws, st, tree),
                 (w,) * leaves, chip.repl(np.int32, leaves, slices))
    msg = str(err.value)
    assert "RESOURCE_EXHAUSTED" in msg and f"memory space {space}" in msg
    assert _is_compile_refusal(err.value)
    assert not _is_resource_exhausted(err.value)
