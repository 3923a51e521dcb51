"""A dense row stack is built in place (MeshPlanner._build_stack): one
pass gathers what every fragment's row is, one native call scatters the
position arrays into one host matrix. Whatever form a row has in its
fragment, and whichever route writes it, the uploaded stack is
``np.stack`` of ``frag.row_words``.
"""

import ctypes
import gc
import importlib.util
import json
import os
import sys
import threading
import time
from collections import Counter

import numpy as np
import pytest

from pilosa_tpu import native
from pilosa_tpu.config import DENSE_CUTOFF, SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.core import Holder
from pilosa_tpu.core.fragment import Fragment
from pilosa_tpu.obs.stats import MemoryStats
from pilosa_tpu.parallel import MeshPlanner, make_mesh

MAX_BITS = MeshPlanner.SPARSE_UPLOAD_MAX_BITS
#: one row id per form a row can have in a fragment; MIX holds one
#: shard of each (and shard 0 of MIX has no fragment at all).
FORMS = ("no_fragment", "absent", "empty", "under", "over", "pending",
         "dense")
MIX = len(FORMS)
N_SHARDS = len(FORMS)
ROUTES = ("scattered", "copied", "coo", "numpy")


def _write(f, rng, form: str, row: int, shard: int) -> None:
    base = shard * SHARD_WIDTH

    def bits(n):
        cols = rng.choice(SHARD_WIDTH, n, replace=False) + base
        f.import_bits(np.full(n, row, dtype=np.uint64), cols)

    if form == "absent":
        f.set_bit(row + 100, base + 1)  # the fragment, another row
    elif form == "empty":
        f.set_bit(row, base + 7)
        f.clear_bit(row, base + 7)
    elif form == "under":
        bits(MAX_BITS // 4)
    elif form == "over":
        bits(MAX_BITS * 2)
    elif form == "pending":
        bits(MAX_BITS + 500)
        for col in rng.choice(SHARD_WIDTH, 5, replace=False):
            f.set_bit(row, base + int(col))
    elif form == "dense":
        bits(DENSE_CUTOFF + 5000)


@pytest.fixture
def built(rng):
    """(holder, index, field f): row k holds FORMS[k] in shards 1..3
    (shard 0 stays without a fragment in the standard view of ``g``,
    the field the no_fragment row is read from), row MIX one shard of
    each form."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    idx.create_field("g")
    for row, form in enumerate(FORMS):
        for shard in (1, 2, 3):
            _write(f, rng, form, row, shard)
    for shard, form in enumerate(FORMS):
        _write(f, rng, form, MIX, shard)
    frag = h.fragment("i", "f", "standard", 1)
    # The forms are what they say they are before any read flushes them.
    assert frag.rows[FORMS.index("empty")].n == 0
    assert not frag.rows[FORMS.index("over")].is_dense
    assert frag.rows[FORMS.index("pending")]._pending
    assert frag.rows[FORMS.index("dense")].is_dense
    return h, idx


def _want(h, field: str, row: int, shards: tuple, s_pad: int) -> np.ndarray:
    want = np.zeros((s_pad, WORDS_PER_SHARD), dtype=np.uint32)
    for i, shard in enumerate(shards):
        frag = h.fragment("i", field, "standard", shard)
        if frag is not None:
            want[i] = frag.row_words(row)
    return want


def _held(h, field: str, row: int, shards: tuple) -> int:
    """Shards whose fragment holds a non-empty ``row``."""
    frags = (h.fragment("i", field, "standard", s) for s in shards)
    return sum(1 for fr in frags
               if fr is not None and fr.row_cardinality(row) > 0)


@pytest.mark.parametrize("has_native", [True, False],
                         ids=["native", "no_native"])
@pytest.mark.parametrize("sparse", [True, False], ids=["tpu", "cpu"])
@pytest.mark.parametrize("form", FORMS + ("mix",))
def test_stack_built_in_place_equals_row_words(built, monkeypatch, form,
                                               sparse, has_native):
    h, idx = built
    if not has_native:
        # What PILOSA_TPU_NO_NATIVE=1 or a host without a toolchain
        # gives the process: no library, so no pool either.
        monkeypatch.setattr(native, "_load", lambda: None)
    elif not native.available():
        pytest.skip("the native library cannot be built here")
    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(), stats=stats)
    monkeypatch.setattr(planner, "_sparse_upload_enabled", lambda: sparse)
    field = "g" if form == "no_fragment" else "f"
    row = MIX if form == "mix" else FORMS.index(form)
    shards = tuple(range(N_SHARDS))
    want = _want(h, field, row, shards, planner._pad(N_SHARDS))

    upload, nbytes = planner._build_stack(idx, field, "standard", row,
                                          shards)
    got = np.asarray(upload())

    assert got.dtype == np.uint32 and nbytes == want.nbytes
    assert np.array_equal(got, want)
    if form in ("under", "over", "pending", "dense", "mix"):
        assert got.any()
    # The routes the rows took add up to the shards that hold the row;
    # without the library nothing is scattered natively.
    n = {r: stats.counter_value(f"planner.stackRows.{r}") for r in ROUTES}
    assert sum(n.values()) == _held(h, field, row, shards)
    assert n["numpy" if has_native else "scattered"] == 0
    assert n["coo"] == 0 or sparse
    if form in ("over", "pending"):
        assert n["numpy" if not has_native else "scattered"] == 3
    if form == "dense":
        assert n["copied"] == 3
    if form == "under":
        assert n["coo"] == (3 if sparse else 0)
    if form == "mix":
        # under -> coo on the TPU route; over, pending -> scatter;
        # dense -> one copy into dmat[k] (tpu) or mat[i] (cpu).
        assert n["copied"] == 1 and n["coo"] == (1 if sparse else 0)
    # One build, by the path the library's presence chose.
    assert stats.counter_value("planner.stackBuilds.native") == has_native
    assert stats.counter_value("planner.stackBuilds.perRow") == (
        not has_native)
    planner.close()


def _need_native():
    if not native.available():
        pytest.skip("the native library cannot be built here")


def _before_the_scatter(monkeypatch, act):
    """Run ``act()`` once at the seam of a build: after the gather has
    taken its references, before the one native call."""
    real = native.or_positions_into_rows
    done = []

    def seam(sources, mat, rows):
        if not done:
            done.append(act())
        return real(sources, mat, rows)

    monkeypatch.setattr(native, "or_positions_into_rows", seam)
    return done


def test_set_during_a_build_leaves_old_generations(built, monkeypatch):
    """A Set that lands while a stack is being built (after the gather
    took its reference to the row, before the native call wrote it)
    is not in the uploaded stack, and leaves the entry stamped with the
    generations read before the build: the next read sees the epoch
    moved, the generations differ, and the stack is rebuilt with it."""
    _need_native()
    h, idx = built
    f = idx.field("f")
    planner = MeshPlanner(h, make_mesh())
    row = FORMS.index("over")
    shards = (1, 2, 3)
    col = 1 * SHARD_WIDTH + 12345
    frag = h.fragment("i", "f", "standard", 1)
    assert not frag.contains(row, col)

    def set_and_flush():
        changed = f.set_bit(row, col)
        frag.row_words(row)  # flushed: the row's array is a new one
        return changed

    landed = _before_the_scatter(monkeypatch, set_and_flush)
    stale = np.asarray(planner._stack_rows(idx, "f", "standard", row,
                                           shards))
    assert landed == [True]
    word, bit = (col % SHARD_WIDTH) >> 5, np.uint32(1 << (col & 31))
    assert not stale[0, word] & bit
    fresh = np.asarray(planner._stack_rows(idx, "f", "standard", row,
                                           shards))
    assert fresh[0, word] & bit
    assert np.array_equal(
        fresh, _want(h, "f", row, shards, planner._pad(len(shards))))
    assert planner.cache_stats()["uploads"] == 2
    planner.close()


class _CountingLib:
    """The native library with every entry point's calls counted."""

    def __init__(self, lib):
        self._lib = lib
        self.calls = Counter()

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def call(*args):
            self.calls[name] += 1
            return fn(*args)

        return call


@pytest.fixture
def wide(rng):
    """(holder, index): field ``f`` rows 0 and 1 over 40 shards, each
    shard's row a position array above the COO threshold."""
    h = Holder()
    idx = h.create_index("i")
    f = idx.create_field("f")
    for row in (0, 1):
        for shard in range(40):
            cols = (rng.choice(SHARD_WIDTH, MAX_BITS + 100 + shard,
                               replace=False) + shard * SHARD_WIDTH)
            f.import_bits(np.full(len(cols), row, dtype=np.uint64), cols)
    return h, idx


@pytest.mark.parametrize("sparse", [True, False], ids=["tpu", "cpu"])
def test_a_stack_is_one_native_scatter_call(wide, monkeypatch, sparse):
    """40 shards of positions: one `positions_to_rows` call, one pooled
    matrix, and not one call a row (`positions_to_words`, which
    `or_positions_into` makes)."""
    _need_native()
    h, idx = wide
    lib = _CountingLib(native._load())
    monkeypatch.setattr(native, "_load", lambda: lib)
    stats = MemoryStats()
    planner = MeshPlanner(h, make_mesh(), stats=stats)
    monkeypatch.setattr(planner, "_sparse_upload_enabled", lambda: sparse)
    shards = tuple(range(40))
    want = _want(h, "f", 0, shards, planner._pad(40))
    lib.calls.clear()

    upload, _ = planner._build_stack(idx, "f", "standard", 0, shards)

    assert lib.calls["positions_to_rows"] == 1
    assert lib.calls["positions_to_words"] == 0
    assert lib.calls["pool_alloc"] == 1
    assert np.array_equal(np.asarray(upload()), want)
    assert stats.counter_value("planner.stackRows.scattered") == 40
    assert stats.counter_value("planner.stackBuilds.native") == 1
    assert stats.counter_value("planner.stackBuilds.perRow") == 0
    planner.close()


def test_rows_changed_after_the_gather_build_the_gathered_snapshot(
        wide, monkeypatch):
    """Between the gather and the native call another thread flushes a
    row, removes from one, empties one and densifies one. The builder's
    references keep the gathered arrays alive and nobody writes them in
    place: the stack is the snapshot, and the next build the new rows."""
    _need_native()
    h, idx = wide
    f = idx.field("f")
    planner = MeshPlanner(h, make_mesh())
    shards = tuple(range(40))
    s_pad = planner._pad(40)
    before = _want(h, "f", 0, shards, s_pad)
    frags = [h.fragment("i", "f", "standard", s) for s in shards]

    def change_rows():
        def work():
            f.set_bit(0, 0 * SHARD_WIDTH + 3)
            frags[0].row_words(0)  # flush: a new array
            gone = frags[1].row_positions(0)
            f.clear_bit(0, 1 * SHARD_WIDTH + int(gone[0]))  # np.delete
            for p in frags[2].row_positions(0):  # emptied
                f.clear_bit(0, 2 * SHARD_WIDTH + int(p))
            cols = np.arange(DENSE_CUTOFF + 10, dtype=np.uint64)
            f.import_bits(np.zeros(len(cols), dtype=np.uint64),
                          cols + np.uint64(3 * SHARD_WIDTH))  # densified
        t = threading.Thread(target=work)
        t.start()
        t.join(timeout=60)
        assert not t.is_alive()
        gc.collect()  # what only the fragments held is gone
        return True

    done = _before_the_scatter(monkeypatch, change_rows)
    upload, _ = planner._build_stack(idx, "f", "standard", 0, shards)
    assert done == [True]
    assert np.array_equal(np.asarray(upload()), before)
    assert frags[3].rows[0].is_dense and frags[2].rows[0].n == 0
    after = _want(h, "f", 0, shards, s_pad)
    assert not np.array_equal(after, before)
    upload, _ = planner._build_stack(idx, "f", "standard", 0, shards)
    assert np.array_equal(np.asarray(upload()), after)
    planner.close()


def test_native_scatter_bounds_positions_and_refuses_bad_rows():
    """`native.or_positions_into_rows`: a position at or past the row
    width is ignored (as `or_positions_into` ignores it), a destination
    row outside the matrix or a source that is not a contiguous uint64
    buffer is refused with nothing written."""
    _need_native()
    w = 64
    good = np.array([0, 33, w * 32 - 1], dtype=np.uint64)
    wild = np.array([5, w * 32, w * 32 + 7, 2**40, 2**64 - 1],
                    dtype=np.uint64)
    mat = np.zeros((3, w), dtype=np.uint32)
    native.or_positions_into_rows(
        [good, wild, np.empty(0, dtype=np.uint64), good], mat, [0, 2, 1, 2])
    want = np.zeros((3, w), dtype=np.uint32)
    for r, pos in ((0, good), (2, good), (2, wild[:1])):
        native.or_positions_into(pos, want[r])
    assert np.array_equal(mat, want) and not mat[1].any()
    assert mat[2, 0] == (1 | 1 << 5) and mat[2, w - 1] == 1 << 31

    untouched = mat.copy()
    for rows in ([0, 3], [-1, 0], [0]):
        with pytest.raises(ValueError):
            native.or_positions_into_rows([good, good], mat, rows)
    for bad in (good.astype(np.int64), np.arange(8, dtype=np.uint64)[::2],
                good.reshape(1, -1)):
        with pytest.raises(ValueError):
            native.or_positions_into_rows([good, bad], mat, [0, 1])
    with pytest.raises(ctypes.ArgumentError):  # a strided matrix
        native.or_positions_into_rows([good], mat[:, ::2], [0])
    assert np.array_equal(mat, untouched)


def test_two_builders_beside_busy_python_threads(wide):
    """Two builders (`StackStore.MAX_WORKERS`) beside four threads that
    never let go of the interpreter willingly: every stack is
    ``np.stack`` of ``row_words``."""
    _need_native()
    h, idx = wide
    planner = MeshPlanner(h, make_mesh())
    shards = tuple(range(40))
    want = {row: _want(h, "f", row, shards, planner._pad(40))
            for row in (0, 1)}
    stop = threading.Event()
    wrong: list = []

    def spin():
        x = 0
        while not stop.is_set():
            x = (x * 31 + 7) % 1000003

    def build(row):
        try:
            for _ in range(6):
                upload, _ = planner._build_stack(idx, "f", "standard", row,
                                                 shards)
                if not np.array_equal(np.asarray(upload()), want[row]):
                    wrong.append(row)
        except Exception as e:  # read in the main thread below
            wrong.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    busy = [threading.Thread(target=spin) for _ in range(4)]
    builders = [threading.Thread(target=build, args=(row,))
                for row in (0, 1)]
    try:
        for t in busy + builders:
            t.start()
        deadline = time.monotonic() + 120
        for t in builders:
            t.join(timeout=max(0.0, deadline - time.monotonic()))
    finally:
        stop.set()
        for t in busy:
            t.join(timeout=10)
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in busy + builders)
    assert wrong == []
    planner.close()


def test_readback_check_script_passes_at_a_tiny_size(capsys):
    """scripts/stack_readback_check.py, the chip run's check that a
    pooled matrix is never reused under its transfer, end to end on the
    CPU backend: every fetch a build, an upload and an eviction, every
    device stack equal to a host rebuild."""
    _need_native()
    path = os.path.join(os.path.dirname(os.path.dirname(__file__)),
                        "scripts", "stack_readback_check.py")
    spec = importlib.util.spec_from_file_location("stack_readback_check",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main(["--shards", "5", "--uploads", "24"]) == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["ok"] and out["uploads"] == 24 and out["evictions"] >= 19
    assert out["mismatches"] == [] and out["pool_recycled"] > 0
    assert out["stackRows"]["numpy"] == 0
