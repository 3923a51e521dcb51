"""ctypes bindings for the native C++ runtime (native/roaring_codec.cpp).

The native library is built on first use (``make -C native``) and
rebuilt whenever it is not a product of the committed sources on THIS
host: the Makefile compiles with ``-march=native``, so a library copied
over from another machine may not even load (``ensure_built``). Every
entry point falls back to the pure-numpy implementation
(pilosa_tpu.roaring / ops.bitops) when the toolchain or library is
unavailable, so the package never hard-depends on the build;
``available()`` (the ``runtime.nativeLoaded`` gauge) says which it is.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "libpilosa_native.so")
_SOURCES = ("Makefile", "roaring_codec.cpp", "fuzz_roaring.cpp")

_U64 = np.dtype(np.uint64).str  # "<u8": what `__array_interface__` says

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False


def _build_stamp() -> str:
    """What a build depends on: the committed sources and the CPU that
    ``-march=native`` compiled for."""
    h = hashlib.sha256()
    for name in _SOURCES:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(f.read())
    flags = ""
    try:
        with open("/proc/cpuinfo") as f:
            flags = next((ln.strip() for ln in f if ln.startswith("flags")),
                         "")
    except OSError:
        pass
    return f"{h.hexdigest()}\n{flags}\n"


def ensure_built(target: str) -> bool:
    """Make ``native/<target>`` a build of the committed sources on this
    host: rebuilt from scratch unless the stamp file beside it records
    the same source hash and CPU flags. False when it cannot be built.
    Safe across processes (xdist workers, server + client): one builds
    under a file lock, the rest find its stamp."""
    out = os.path.join(_NATIVE_DIR, target)
    stamp_path = out + ".stamp"
    try:
        want = _build_stamp()
        with open(os.path.join(_NATIVE_DIR, ".build.lock"), "w") as lock:
            fcntl.flock(lock, fcntl.LOCK_EX)
            try:
                with open(stamp_path) as f:
                    have = f.read()
            except OSError:
                have = ""
            if have == want and os.path.exists(out):
                return True
            subprocess.run(["make", "-C", _NATIVE_DIR, "-s", "-B", target],
                           check=True, capture_output=True, timeout=300)
            with open(stamp_path, "w") as f:
                f.write(want)
            return True
    except (OSError, subprocess.SubprocessError):
        return False


def _load() -> ctypes.CDLL | None:
    global _lib, _tried
    with _lock:
        if _tried:
            return _lib
        _tried = True
        if os.environ.get("PILOSA_TPU_NO_NATIVE") == "1":
            return None
        if not ensure_built("libpilosa_native.so"):
            return None
        try:
            lib = ctypes.CDLL(_LIB_PATH)
        except OSError:
            return None
        lib.roaring_decode_count.restype = ctypes.c_int64
        lib.roaring_decode_count.argtypes = [ctypes.c_char_p, ctypes.c_int64]
        lib.roaring_decode.restype = ctypes.c_int64
        lib.roaring_decode.argtypes = [
            ctypes.c_char_p, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64]
        lib.roaring_encode_bound.restype = ctypes.c_int64
        lib.roaring_encode_bound.argtypes = [
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64]
        lib.roaring_encode.restype = ctypes.c_int64
        lib.roaring_encode.argtypes = [
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint8, flags="C"), ctypes.c_int64]
        lib.positions_to_words.restype = None
        lib.positions_to_words.argtypes = [
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64]
        lib.positions_to_rows.restype = ctypes.c_int
        lib.positions_to_rows.argtypes = [
            np.ctypeslib.ndpointer(np.uint32, ndim=2, flags=("C", "W")),
            ctypes.c_int64, ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint64, flags="C"),
            np.ctypeslib.ndpointer(np.int64, flags="C"),
            np.ctypeslib.ndpointer(np.int64, flags="C"), ctypes.c_int64]
        lib.words_to_positions.restype = ctypes.c_int64
        lib.words_to_positions.argtypes = [
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64]
        lib.popcount_words.restype = ctypes.c_int64
        lib.popcount_words.argtypes = [
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64]
        lib.intersection_count_words.restype = ctypes.c_int64
        lib.intersection_count_words.argtypes = [
            np.ctypeslib.ndpointer(np.uint32, flags="C"),
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64]
        lib.scatter_row_blocks.restype = None
        lib.scatter_row_blocks.argtypes = [
            np.ctypeslib.ndpointer(np.uint64, flags="C"), ctypes.c_int64,
            ctypes.c_int,
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint8, flags="C"),
            np.ctypeslib.ndpointer(np.int64, flags="C")]
        lib.scatter_bsi_blocks.restype = ctypes.c_int
        lib.scatter_bsi_blocks.argtypes = [
            np.ctypeslib.ndpointer(np.uint64, flags="C"),
            np.ctypeslib.ndpointer(np.int64, flags="C"), ctypes.c_int64,
            ctypes.c_int, ctypes.c_int,
            np.ctypeslib.ndpointer(np.uint32, flags="C"), ctypes.c_int64,
            ctypes.c_int64,
            np.ctypeslib.ndpointer(np.uint8, flags="C"),
            np.ctypeslib.ndpointer(np.int64, flags="C")]
        lib.pool_alloc.restype = ctypes.c_void_p
        lib.pool_alloc.argtypes = [ctypes.c_int64, ctypes.c_int]
        lib.pool_free.restype = None
        lib.pool_free.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.pool_reserve.restype = ctypes.c_int64
        lib.pool_reserve.argtypes = [ctypes.c_int64]
        lib.pool_set_limit.restype = None
        lib.pool_set_limit.argtypes = [ctypes.c_int64]
        lib.pool_stats.restype = None
        lib.pool_stats.argtypes = [np.ctypeslib.ndpointer(np.int64, flags="C")]
        _lib = lib
        return _lib


def available() -> bool:
    return _load() is not None


_MADV_HUGEPAGE = 14
_PAGE = 4096
_libc = None


def _advise_huge(arr: np.ndarray) -> None:
    """Opt a large, not-yet-touched buffer into 2 MiB pages (Linux
    MADV_HUGEPAGE). First-touch faults on virtualized hosts cost ~µs per
    4 KiB page — over 1 s for the scatter buffers — and the partition's
    ~1000 write streams thrash a 4 KiB-page TLB. Best-effort: any
    failure silently keeps normal pages."""
    global _libc
    import sys
    if sys.platform != "linux":  # advice value 14 is Linux-specific
        return
    try:
        if _libc is None:
            _libc = ctypes.CDLL(None, use_errno=True)
        addr = arr.ctypes.data
        a = (addr + _PAGE - 1) & ~(_PAGE - 1)
        e = (addr + arr.nbytes) & ~(_PAGE - 1)
        if e > a:
            _libc.madvise(ctypes.c_void_p(a), ctypes.c_size_t(e - a),
                          ctypes.c_int(_MADV_HUGEPAGE))
    except Exception:
        pass


def pool_reserve(n_bytes: int) -> int:
    """Pre-fault ``n_bytes`` of recycled-page pool memory (see the
    "recycled page pool" note in native/roaring_codec.cpp). Called at
    server boot (config ``import-pool-mb``, env
    PILOSA_TPU_IMPORT_POOL_MB) so bulk imports never pay first-touch
    faults on their block/staging buffers
    — the buffer-pool move every database makes, and the analog of the
    reference's mmap page cache staying warm across imports
    (fragment.go:311). Returns bytes actually reserved (0 if the native
    library is unavailable)."""
    lib = _load()
    if lib is None or n_bytes <= 0:
        return 0
    return int(lib.pool_reserve(int(n_bytes)))


def pool_set_limit(n_bytes: int) -> None:
    lib = _load()
    if lib is not None:
        lib.pool_set_limit(int(n_bytes))


def pool_stats() -> dict | None:
    lib = _load()
    if lib is None:
        return None
    out = np.zeros(4, dtype=np.int64)
    lib.pool_stats(out)
    return {"free_bytes": int(out[0]), "fresh_mmaps": int(out[1]),
            "recycled_allocs": int(out[2]), "limit_bytes": int(out[3])}


def pool_zeros(shape, dtype=np.uint32) -> np.ndarray | None:
    """np.zeros backed by pool memory: recycled chunks re-zero via
    memset at warm-memory speed instead of per-page fault+zero. The
    chunk returns to the pool when the array (and every view of it) is
    garbage-collected. None when the native library or memory is
    unavailable — callers fall back to np.zeros."""
    import weakref

    lib = _load()
    if lib is None:
        return None
    n_bytes = int(np.prod(shape)) * np.dtype(dtype).itemsize
    if n_bytes <= 0:
        return None
    ptr = lib.pool_alloc(n_bytes, 1)
    if not ptr:
        return None
    buf = (ctypes.c_uint8 * n_bytes).from_address(ptr)
    fin = weakref.finalize(buf, lib.pool_free, ptr, n_bytes)
    # At interpreter shutdown the pool (and lib) die with the process;
    # running the finalizer then could touch a torn-down CDLL.
    fin.atexit = False
    arr = np.frombuffer(buf, dtype=np.uint8, count=n_bytes)
    return arr.view(dtype).reshape(shape)


def decode_roaring(buf: bytes) -> np.ndarray:
    """Serialized roaring bitmap -> sorted uint64 positions."""
    lib = _load()
    if lib is None:
        from pilosa_tpu import roaring
        return roaring.decode(buf)
    n = lib.roaring_decode_count(buf, len(buf))
    if n < 0:
        raise ValueError("roaring: invalid buffer")
    out = np.empty(n, dtype=np.uint64)
    got = lib.roaring_decode(buf, len(buf), out, n)
    if got != n:
        raise ValueError("roaring: decode failed")
    return out


def encode_roaring(positions: np.ndarray) -> bytes:
    """Sorted uint64 positions -> serialized roaring bitmap."""
    positions = np.ascontiguousarray(positions, dtype=np.uint64)
    # The native encoder requires strictly-increasing input; duplicates
    # would inflate container N and double-count on decode.
    if len(positions) and not (positions[:-1] < positions[1:]).all():
        positions = np.unique(positions)
    lib = _load()
    if lib is None:
        from pilosa_tpu import roaring
        return roaring.encode(positions)
    cap = lib.roaring_encode_bound(positions, len(positions))
    out = np.empty(cap, dtype=np.uint8)
    n = lib.roaring_encode(positions, len(positions), out, cap)
    if n < 0:
        raise ValueError("roaring: encode failed")
    return out[:n].tobytes()


def or_positions_into(positions: np.ndarray, words: np.ndarray) -> bool:
    """OR uint64 bit ``positions`` into ``words``, a C-contiguous uint32
    buffer the caller owns (a row of a stack matrix): no copy of the
    positions, no temporary block. True when the native library did it;
    False, with the same bits set through ``bitops``, when there is
    none. One row a call, and ctypes lets go of the interpreter lock
    for each: `HostRow.words_into` calls it for the rows the distributed
    planner writes into its per-device blocks; a whole stack of
    `MeshPlanner._build_stack` goes through `or_positions_into_rows`,
    which comes here only with no library."""
    lib = _load()
    if lib is None:
        from pilosa_tpu.ops import bitops
        np.bitwise_or(words, bitops.positions_to_words(positions, len(words)),
                      out=words)
        return False
    # ndpointer refuses another dtype or a strided buffer: the native
    # loop is never handed memory it would misread.
    lib.positions_to_words(positions, len(positions), words, len(words))
    return True


def or_positions_into_rows(sources: list[np.ndarray], mat: np.ndarray,
                           rows: list[int]) -> bool:
    """OR the uint64 bit positions of ``sources[k]`` into ``mat[rows[k]]``
    for every k, in ONE native call: ``mat`` is a C-contiguous uint32
    ``[n_rows, W]`` matrix the caller owns (a row stack). ctypes lets go
    of the interpreter lock once for the whole stack, where a call a row
    queues for it again after every row (PERF.md §6, PR 32 and 34).
    ``sources`` keeps every array alive through the call; an array that
    is not a C-contiguous 1-D uint64 buffer, or a row outside the
    matrix, raises ValueError before anything is written. True when the
    native library did it; False, with the same bits set row by row
    through `or_positions_into`, when there is none."""
    if len(rows) != len(sources):
        raise ValueError("or_positions_into_rows: one row per source")
    if rows and not 0 <= min(rows) <= max(rows) < len(mat):
        raise ValueError("or_positions_into_rows: a row outside the matrix")
    addrs: list[int] = []
    lengths: list[int] = []
    for a in sources:
        # One dict per array gives address, length, dtype and layout
        # (strides is None for a C-contiguous buffer): the native loop
        # is never handed memory it would misread.
        ai = a.__array_interface__
        if (ai["typestr"] != _U64 or ai["strides"] is not None
                or len(ai["shape"]) != 1):
            raise ValueError(
                "or_positions_into_rows: a source is not a C-contiguous "
                "1-D uint64 array")
        addrs.append(ai["data"][0])
        lengths.append(ai["shape"][0])
    lib = _load()
    if lib is None:
        for a, r in zip(sources, rows):
            or_positions_into(a, mat[r])
        return False
    if lib.positions_to_rows(mat, mat.shape[0], mat.shape[1],
                             np.array(addrs, dtype=np.uint64),
                             np.array(lengths, dtype=np.int64),
                             np.array(rows, dtype=np.int64),
                             len(sources)) != 0:
        raise ValueError("or_positions_into_rows: refused by the library")
    return True


def words_to_positions(words: np.ndarray) -> np.ndarray:
    words = np.ascontiguousarray(words, dtype=np.uint32)
    lib = _load()
    if lib is None:
        from pilosa_tpu.ops import bitops
        return bitops.words_to_positions(words)
    n = lib.popcount_words(words, len(words))
    out = np.empty(n, dtype=np.uint64)
    got = lib.words_to_positions(words, len(words), out, n)
    if got != n:
        raise RuntimeError("words_to_positions mismatch")
    return out


def popcount_words(words: np.ndarray) -> int:
    words = np.ascontiguousarray(words, dtype=np.uint32)
    lib = _load()
    if lib is None:
        from pilosa_tpu.ops import bitops
        return bitops.np_count(words)
    return int(lib.popcount_words(words, len(words)))


def intersection_count_words(a: np.ndarray, b: np.ndarray) -> int:
    """Fused popcount(a & b) on the host — the CPU-baseline kernel."""
    a = np.ascontiguousarray(a.reshape(-1), dtype=np.uint32)
    b = np.ascontiguousarray(b.reshape(-1), dtype=np.uint32)
    lib = _load()
    if lib is None:
        from pilosa_tpu.ops import bitops
        return bitops.np_count(a & b)
    return int(lib.intersection_count_words(a, b, len(a)))


def scatter_row_blocks(cols: np.ndarray, exp: int,
                       n_shards: int, words_per_shard: int):
    """Scatter one row's absolute column ids into dense per-shard word
    blocks in a single unsorted pass. Returns (blocks[n_shards, W],
    touched[n_shards] bool, counts[n_shards] int64 — set bits per
    block, counted cache-hot) or None when the native library is
    missing (callers fall back to the sorted import path)."""
    lib = _load()
    if lib is None:
        return None
    cols = np.ascontiguousarray(cols, dtype=np.uint64)
    blocks = pool_zeros((n_shards, words_per_shard), np.uint32)
    if blocks is None:
        blocks = np.zeros((n_shards, words_per_shard), dtype=np.uint32)
        _advise_huge(blocks)
    touched = np.zeros(n_shards, dtype=np.uint8)
    counts = np.zeros(n_shards, dtype=np.int64)
    lib.scatter_row_blocks(cols, len(cols), exp,
                           blocks.reshape(-1), n_shards, words_per_shard,
                           touched, counts)
    return blocks, touched.astype(bool), counts


def scatter_bsi_blocks(cols: np.ndarray, vals: np.ndarray, exp: int,
                       depth: int, n_shards: int, words_per_shard: int):
    """Scatter (column, value) pairs into dense BSI bit-plane blocks
    ([n_shards, depth+2, W]; per-shard rows: exists, sign, planes) in one
    native pass. Duplicate columns resolve last-write-wins (the kernel
    dedupes against the exists plane, which the caller guarantees starts
    empty). Returns (blocks, touched, counts[n_shards, depth+2]) or
    None when the native library is missing or its staging alloc
    failed."""
    lib = _load()
    if lib is None:
        return None
    cols = np.ascontiguousarray(cols, dtype=np.uint64)
    vals = np.ascontiguousarray(vals, dtype=np.int64)
    blocks = pool_zeros((n_shards, depth + 2, words_per_shard), np.uint32)
    if blocks is None:
        blocks = np.zeros((n_shards, depth + 2, words_per_shard),
                          dtype=np.uint32)
        _advise_huge(blocks)
    touched = np.zeros(n_shards, dtype=np.uint8)
    counts = np.zeros((n_shards, depth + 2), dtype=np.int64)
    rc = lib.scatter_bsi_blocks(cols, vals, len(cols), exp, depth,
                                blocks.reshape(-1), n_shards,
                                words_per_shard, touched,
                                counts.reshape(-1))
    if rc != 0:  # staging alloc failed: caller takes the exact path
        return None
    return blocks, touched.astype(bool), counts
