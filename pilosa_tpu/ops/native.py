"""ctypes loader + dispatch for the native host kernels.

The analog of the reference's runtime assembly dispatch
(roaring/assembly_asm.go:20,40-80 hasAsm + function-pointer selection):
on first use, build (if needed) and load the library under
native/build/; every kernel has a numpy fallback so the package works
without a C++ toolchain. `has_native()` reports which path is live;
`PILOSA_TPU_NO_NATIVE=1` forces the fallback (the reference's
`go build -tags noasm` escape hatch).

The library is compiled with -march=native, so it belongs to the CPU
that built it: its file name carries a digest of that CPU's flags. A
build directory that travelled here from another machine (it is not in
git, but a copy of the tree brings it along) then holds a library this
host does not look for, and the right one is built beside it.
"""

from __future__ import annotations

import ctypes
import functools
import hashlib
import os
import platform
import subprocess
from typing import Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")


@functools.lru_cache(maxsize=None)
def _lib_name() -> str:
    """The library's file name on this host: it carries a digest of
    what -march=native selects here, the machine type and the CPU's
    feature flags (first `flags`/`Features` line of /proc/cpuinfo; the
    machine type alone where there is no such file)."""
    flags = ""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    flags = " ".join(sorted(line.split(":", 1)[1].split()))
                    break
    except OSError:
        pass
    key = hashlib.sha1(
        f"{platform.machine()} {flags}".encode()).hexdigest()[:12]
    return f"libpilosa_native-{key}.so"


def _lib_path() -> str:
    return os.path.join(_NATIVE_DIR, "build", _lib_name())


_U64P = ctypes.POINTER(ctypes.c_uint64)
_U32P = ctypes.POINTER(ctypes.c_uint32)
_U8P = ctypes.POINTER(ctypes.c_uint8)

_lib: Optional[ctypes.CDLL] = None
_load_attempted = False


def _src_mtime() -> float:
    try:
        return os.path.getmtime(os.path.join(_NATIVE_DIR,
                                             "pilosa_native.cpp"))
    except OSError:
        return 0.0


def _build() -> bool:
    if not os.path.isdir(_NATIVE_DIR):
        return False
    # A previously failed build is cached on disk and only retried when
    # the source changes, so toolchain-less machines pay the failed
    # compile once, not per process.
    fail_stamp = _lib_path() + ".build_failed"
    try:
        with open(fail_stamp) as f:
            if float(f.read() or 0) == _src_mtime():
                return False
    except (OSError, ValueError):
        pass
    try:
        subprocess.run(["make", "-C", _NATIVE_DIR, "-s",
                        f"LIB_NAME={_lib_name()}"], check=True,
                       capture_output=True, timeout=120)
        return os.path.exists(_lib_path())
    except Exception:  # noqa: BLE001 — no toolchain: numpy fallback
        try:
            os.makedirs(os.path.dirname(fail_stamp), exist_ok=True)
            with open(fail_stamp, "w") as f:
                f.write(str(_src_mtime()))
        except OSError:
            pass
        return False


def _load() -> Optional[ctypes.CDLL]:
    if os.environ.get("PILOSA_TPU_NO_NATIVE"):
        return None
    # Always run make: it is a cheap no-op when the .so is newer than the
    # source, and rebuilds a stale .so after source edits. A failed build
    # (no toolchain) still loads a previously built library if present.
    _build()
    if not os.path.exists(_lib_path()):
        return None
    try:
        lib = ctypes.CDLL(_lib_path())
    except OSError:
        return None
    lib.pilosa_popcnt_slice.restype = ctypes.c_uint64
    lib.pilosa_popcnt_slice.argtypes = [_U64P, ctypes.c_size_t]
    for name in ("and", "or", "xor", "andnot"):
        fn = getattr(lib, f"pilosa_popcnt_{name}_slice")
        fn.restype = ctypes.c_uint64
        fn.argtypes = [_U64P, _U64P, ctypes.c_size_t]
    for name, args in [
        ("intersect_sorted_u32", [_U32P, ctypes.c_size_t, _U32P,
                                  ctypes.c_size_t, _U32P]),
        ("intersection_count_sorted_u32", [_U32P, ctypes.c_size_t, _U32P,
                                           ctypes.c_size_t]),
        ("union_sorted_u32", [_U32P, ctypes.c_size_t, _U32P,
                              ctypes.c_size_t, _U32P]),
        ("difference_sorted_u32", [_U32P, ctypes.c_size_t, _U32P,
                                   ctypes.c_size_t, _U32P]),
        ("xor_sorted_u32", [_U32P, ctypes.c_size_t, _U32P,
                            ctypes.c_size_t, _U32P]),
        ("bitmap_to_values_u32", [_U64P, ctypes.c_size_t, _U32P]),
    ]:
        fn = getattr(lib, f"pilosa_{name}")
        fn.restype = ctypes.c_size_t
        fn.argtypes = args
    lib.pilosa_bitmap_contains_u32.restype = None
    lib.pilosa_bitmap_contains_u32.argtypes = [_U64P, _U32P,
                                               ctypes.c_size_t, _U8P]
    lib.pilosa_popcnt_blocks.restype = None
    lib.pilosa_popcnt_blocks.argtypes = [_U64P, ctypes.c_size_t,
                                         ctypes.c_size_t, _U64P]
    lib.pilosa_fold_blocks.restype = None
    lib.pilosa_fold_blocks.argtypes = [ctypes.POINTER(_U64P),
                                       ctypes.c_size_t, ctypes.c_int,
                                       ctypes.c_size_t, ctypes.c_size_t,
                                       _U64P, _U64P]
    return lib


def _get_lib() -> Optional[ctypes.CDLL]:
    """Deferred load: the (possibly blocking) build+dlopen happens on
    the first kernel call, not at import (roaring imports this module
    at its own import time)."""
    global _lib, _load_attempted
    if not _load_attempted:
        _load_attempted = True
        _lib = _load()
    return _lib

# ctypes call overhead beats the kernel below these sizes — numpy's SIMD
# handles small inputs better (measured: numpy wins at 1024-word
# containers, native wins >=8K words by 2-4x and 10x on value extraction).
POPCNT_NATIVE_MIN = 8192      # uint64 words
SORTED_NATIVE_MIN = 2048      # combined array elements


def has_native() -> bool:
    return _get_lib() is not None


def _p64(a: np.ndarray):
    return a.ctypes.data_as(_U64P)


def _p32(a: np.ndarray):
    return a.ctypes.data_as(_U32P)


# ---- popcount slices -------------------------------------------------------

def popcnt_slice(s: np.ndarray) -> int:
    lib = _get_lib()
    if (lib is not None and s.dtype == np.uint64 and s.flags.c_contiguous
            and len(s) >= POPCNT_NATIVE_MIN):
        return int(lib.pilosa_popcnt_slice(_p64(s), len(s)))
    return int(np.bitwise_count(s).sum())


def popcnt_blocks(s: np.ndarray, block_words: int = 1024) -> np.ndarray:
    """Per-block popcounts: (len(s)/block_words,) uint64 — ONE pass,
    one call, for per-container counts on the materializing path."""
    nblocks = len(s) // block_words
    lib = _get_lib()
    if (lib is not None and s.dtype == np.uint64 and s.flags.c_contiguous
            and len(s) >= POPCNT_NATIVE_MIN):
        out = np.empty(nblocks, dtype=np.uint64)
        lib.pilosa_popcnt_blocks(_p64(s), nblocks, block_words, _p64(out))
        return out
    return np.bitwise_count(s).reshape(nblocks, block_words) \
        .sum(axis=1, dtype=np.uint64)


_FOLD_OPS = {"and": 0, "or": 1, "andnot": 2}


def fold_blocks(leaves, op: str, block_words: int = 1024):
    """Fused flat fold + per-block popcount: (out, counts) for
    out = leaves[0] op leaves[1] op ... (left fold), or None when the
    native library is unavailable or inputs don't qualify — callers
    fall back to a numpy fold + popcnt_blocks (one extra result pass)."""
    lib = _get_lib()
    code = _FOLD_OPS.get(op)
    if (lib is None or code is None or len(leaves) < 2
            or any(a.dtype != np.uint64 or not a.flags.c_contiguous
                   or a.shape != leaves[0].shape for a in leaves)):
        return None
    n = leaves[0].size
    if n % block_words or n < POPCNT_NATIVE_MIN:
        return None
    nblocks = n // block_words
    out = np.empty(n, dtype=np.uint64)
    counts = np.empty(nblocks, dtype=np.uint64)
    ptrs = (_U64P * len(leaves))(*[
        a.ctypes.data_as(_U64P) for a in leaves])
    lib.pilosa_fold_blocks(ptrs, len(leaves), code, nblocks, block_words,
                           _p64(out), _p64(counts))
    return out, counts


def fold_count(blocks, tree) -> int:
    """Total popcount of a numbered op-tree (plan._tree_signature)
    folded over numpy uint64 blocks. Flat trees — one op over leaves in
    index order, the common Intersect/Union count — run through the
    fused C++ fold+per-block-popcount kernel in a single pass; nested
    or non-qualifying trees fall back to a numpy fold plus
    popcnt_slice (one extra materialized intermediate per op level)."""
    # Deferred import: bitops pulls in jax, and this module must stay
    # importable (and fast) in jax-free host tooling.
    from .bitops import flat_fold_op, fold_tree

    op = flat_fold_op(tree)
    if op is not None:
        r = fold_blocks(list(blocks), op)
        if r is not None:
            return int(r[1].sum())
    acc = fold_tree(tree, lambda i: blocks[i])
    return popcnt_slice(np.ascontiguousarray(acc))


def _popcnt_pair(name: str, np_op, s: np.ndarray, m: np.ndarray) -> int:
    lib = _get_lib()
    if (lib is not None and s.dtype == np.uint64 and m.dtype == np.uint64
            and s.flags.c_contiguous and m.flags.c_contiguous
            and len(s) == len(m) and len(s) >= POPCNT_NATIVE_MIN):
        return int(getattr(lib, f"pilosa_popcnt_{name}_slice")(
            _p64(s), _p64(m), len(s)))
    return int(np.bitwise_count(np_op(s, m)).sum())


def popcnt_and_slice(s, m) -> int:
    return _popcnt_pair("and", np.bitwise_and, s, m)


def popcnt_or_slice(s, m) -> int:
    return _popcnt_pair("or", np.bitwise_or, s, m)


def popcnt_xor_slice(s, m) -> int:
    return _popcnt_pair("xor", np.bitwise_xor, s, m)


def popcnt_andnot_slice(s, m) -> int:
    return _popcnt_pair("andnot", lambda a, b: a & ~b, s, m)


# ---- sorted-array kernels --------------------------------------------------

def intersect_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _get_lib()
    if lib is not None and len(a) + len(b) >= SORTED_NATIVE_MIN:
        a = np.ascontiguousarray(a, dtype=np.uint32)
        b = np.ascontiguousarray(b, dtype=np.uint32)
        out = np.empty(min(len(a), len(b)), dtype=np.uint32)
        k = lib.pilosa_intersect_sorted_u32(_p32(a), len(a), _p32(b),
                                             len(b), _p32(out))
        return out[:k]
    return np.intersect1d(a, b, assume_unique=True).astype(np.uint32)


def intersection_count_sorted(a: np.ndarray, b: np.ndarray) -> int:
    lib = _get_lib()
    if lib is not None and len(a) + len(b) >= SORTED_NATIVE_MIN:
        a = np.ascontiguousarray(a, dtype=np.uint32)
        b = np.ascontiguousarray(b, dtype=np.uint32)
        return int(lib.pilosa_intersection_count_sorted_u32(
            _p32(a), len(a), _p32(b), len(b)))
    return len(np.intersect1d(a, b, assume_unique=True))


def union_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _get_lib()
    if lib is not None and len(a) + len(b) >= SORTED_NATIVE_MIN:
        a = np.ascontiguousarray(a, dtype=np.uint32)
        b = np.ascontiguousarray(b, dtype=np.uint32)
        out = np.empty(len(a) + len(b), dtype=np.uint32)
        k = lib.pilosa_union_sorted_u32(_p32(a), len(a), _p32(b), len(b),
                                         _p32(out))
        return out[:k]
    return np.union1d(a, b).astype(np.uint32)


def difference_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _get_lib()
    if lib is not None and len(a) + len(b) >= SORTED_NATIVE_MIN:
        a = np.ascontiguousarray(a, dtype=np.uint32)
        b = np.ascontiguousarray(b, dtype=np.uint32)
        out = np.empty(len(a), dtype=np.uint32)
        k = lib.pilosa_difference_sorted_u32(_p32(a), len(a), _p32(b),
                                              len(b), _p32(out))
        return out[:k]
    return np.setdiff1d(a, b, assume_unique=True).astype(np.uint32)


def xor_sorted(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    lib = _get_lib()
    if lib is not None and len(a) + len(b) >= SORTED_NATIVE_MIN:
        a = np.ascontiguousarray(a, dtype=np.uint32)
        b = np.ascontiguousarray(b, dtype=np.uint32)
        out = np.empty(len(a) + len(b), dtype=np.uint32)
        k = lib.pilosa_xor_sorted_u32(_p32(a), len(a), _p32(b), len(b),
                                       _p32(out))
        return out[:k]
    return np.setxor1d(a, b, assume_unique=True).astype(np.uint32)


def bitmap_to_values(words: np.ndarray) -> np.ndarray:
    """Bitmap words -> sorted uint32 values (trailing-zero scan). The
    native path requires uint64 input and sizes the output by
    len(words) (values are < len(words)*64, so any word count is
    safe); anything else falls back to numpy."""
    lib = _get_lib()
    if (lib is not None and words.dtype == np.uint64
            and words.flags.c_contiguous and len(words) <= (1 << 26)):
        out = np.empty(len(words) << 6, dtype=np.uint32)
        k = lib.pilosa_bitmap_to_values_u32(_p64(words), len(words),
                                            _p32(out))
        return out[:k].copy()
    bits = np.unpackbits(np.ascontiguousarray(words).view(np.uint8),
                         bitorder="little")
    return np.nonzero(bits)[0].astype(np.uint32)


def bitmap_contains(words: np.ndarray, a: np.ndarray) -> np.ndarray:
    """Membership mask of sorted values `a` against bitmap words."""
    lib = _get_lib()
    if (lib is not None and words.dtype == np.uint64
            and words.flags.c_contiguous and len(a) >= SORTED_NATIVE_MIN
            and int(a[-1]) >> 6 < len(words)):  # a is sorted; match the
        # fallback's IndexError domain instead of reading out of bounds
        a = np.ascontiguousarray(a, dtype=np.uint32)
        mask = np.empty(len(a), dtype=np.uint8)
        lib.pilosa_bitmap_contains_u32(_p64(words), _p32(a), len(a),
                                        mask.ctypes.data_as(_U8P))
        return mask.astype(bool)
    return ((words[a >> np.uint32(6)] >> (a.astype(np.uint64)
                                          & np.uint64(63)))
            & np.uint64(1)).astype(bool)
