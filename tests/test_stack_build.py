"""A dense row stack is built in place (MeshPlanner._build_stack): every
fragment writes its row straight into one host matrix. Whatever form a
row has in its fragment, and whichever route writes it, the uploaded
stack is ``np.stack`` of ``frag.row_words``.
"""

import importlib.util
import json
import os

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
    planner.close()


def test_set_during_a_build_leaves_old_generations(built, monkeypatch):
    """A Set that lands while a stack is being built (after its shard's
    row was written into the matrix) leaves the entry stamped with the
    generations read before the build: the next read sees the epoch
    moved, the generations differ, and the stack is rebuilt."""
    h, idx = built
    f = idx.field("f")
    planner = MeshPlanner(h, make_mesh())
    row = FORMS.index("over")
    shards = (1, 2, 3)
    col = 1 * SHARD_WIDTH + 12345
    assert not h.fragment("i", "f", "standard", 1).contains(row, col)
    last = h.fragment("i", "f", "standard", 3)
    real = Fragment.row_words_into
    landed = []

    def write_then_set(self, row_id, out):
        if self is last and not landed:
            landed.append(f.set_bit(row, col))  # shard 1 is written
        return real(self, row_id, out)

    monkeypatch.setattr(Fragment, "row_words_into", write_then_set)
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


def test_readback_check_script_passes_at_a_tiny_size(capsys):
    """scripts/stack_readback_check.py, the chip run's check that a
    pooled matrix is never reused under its transfer, end to end on the
    CPU backend: every fetch a build, an upload and an eviction, every
    device stack equal to a host rebuild."""
    if not native.available():
        pytest.skip("the native library cannot be built here")
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
