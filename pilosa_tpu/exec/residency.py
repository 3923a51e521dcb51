"""Container-classed device residency: packed vs dense leaf stacks.

The reference resists memory pressure with its roaring container
classes (roaring.go: array/run/bitmap containers chosen per container
by cardinality). This module ports that idea to HBM: a planner leaf
stack has a *representation class* chosen by measured row cardinality —

* ``dense``  — the ``[S, W]`` uint32 bit-plane stack, as always;
* ``packed`` — a ``[S, K]`` int32 stack of SORTED in-shard column
  indices, pow2-padded per stack with the ``SENTINEL`` (SHARD_WIDTH),
  so a low-cardinality row costs ``4*K`` bytes per shard instead of
  the 128 KiB dense block. K is the pow2 bucket of the largest
  per-shard cardinality in the stack, so one row's stack is a single
  rectangular device array and shapes reuse compiled kernels.

Every op the dense class supports has a packed kernel variant in
``KERNELS`` — the class table / kernel table symmetry is enforced by
the ``residency-pairing`` analysis checker, so a future representation
class cannot land half-wired. The planner picks the variant at plan
time (the class is part of the structural plan signature, so programs
specialize per class and the coalescer/result-cache keys stay honest):

* ``count``      — popcount-over-indices: a packed Count() never
  expands; it counts non-sentinel entries.
* ``and_count``  — sparse∧dense: gather the dense word at each index
  and test the bit (data motion tracks set bits, not shard width).
* ``pair_count`` — sparse∧sparse: sorted-membership intersection of
  two index stacks via searchsorted.
* ``expand``     — the general fallback: scatter the indices into a
  dense ``[S, W]`` plane *inside* the jitted program, so any bitmap
  tree runs unchanged while HBM residency stays packed.

Selection: ``PILOSA_TPU_RESIDENCY_PACKED`` = ``on`` | ``off`` |
``auto`` (env wins over the server knob's ``set_mode``). ``auto``
packs only rows whose packed stack is at least ``AUTO_RATIO``× smaller
than dense; ``on`` packs everything that fits at all; high-cardinality
rows fall back to dense in EVERY mode (a packed full row would be 32×
larger than the dense block). Both sides are bit-identical by
generative test (tests/test_residency.py).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from pilosa_tpu.config import SHARD_WIDTH, WORDS_PER_SHARD
from pilosa_tpu.exec import keyplane as _keyplane
from pilosa_tpu.ops import bitops
from pilosa_tpu.sketch import kernels as sketch_kernels

_MODES = ("on", "off", "auto")
_default_mode = "auto"

#: representation-class names. REPR_CLASSES is the class table the
#: residency-pairing checker pairs against KERNELS below.
DENSE = "dense"
PACKED = "packed"
#: HLL register planes (pilosa_tpu/sketch): [S, 2^p] uint8 register
#: stacks plus packed [S, C] bucket|rho column planes for the filtered
#: distinct path — Count(Distinct(...)) never materializes a row set.
HLL = "hll"
#: key-translation planes (exec/keyplane): one [3, H] uint32 stack per
#: translate store — sorted splitmix64 hash halves plus the id lane,
#: probed by a lexicographic binary search. Forward translation shares
#: the stack cache, the budget, and this accounting with row stacks.
KEYPLANE = "keyplane"
REPR_CLASSES = (DENSE, PACKED, HLL, KEYPLANE)

#: padding value for packed index stacks: one past the last valid
#: in-shard column. Chosen so ``idx >> 5`` lands exactly on the trash
#: word W in the expand scatter.
SENTINEL = SHARD_WIDTH

#: minimum packed stack width (entries) — below this the pow2 bucket
#: space would fragment compiles for no memory win.
MIN_PACK_WIDTH = 8

#: ``auto`` packs only when the packed stack is at least this many
#: times smaller than the dense block (K <= W / AUTO_RATIO): the class
#: choice is baked into compiled programs, so marginal wins aren't
#: worth the extra program population.
AUTO_RATIO = 8

#: hard ceiling in every mode: past W/2 entries the packed form stops
#: being smaller than dense (4 B/entry vs 4 B/word) — fall back.
MAX_PACK_WIDTH = WORDS_PER_SHARD // 2


def set_mode(mode: str) -> None:
    """Server-knob default; the PILOSA_TPU_RESIDENCY_PACKED env var
    (the test/operator override) takes precedence when set."""
    global _default_mode
    if mode not in _MODES:
        raise ValueError(f"residency_packed mode must be one of {_MODES}")
    _default_mode = mode


def mode() -> str:
    m = os.environ.get("PILOSA_TPU_RESIDENCY_PACKED", "").strip().lower()
    return m if m in _MODES else _default_mode


def pack_width(max_bits: int) -> int:
    """Packed stack width (entries) for a row whose largest per-shard
    cardinality is ``max_bits``: the pow2 bucket, floored at
    MIN_PACK_WIDTH so tiny rows share compiled shapes."""
    n = max(int(max_bits), MIN_PACK_WIDTH)
    return 1 << (n - 1).bit_length()


def choose_class(max_bits: int) -> str:
    """Representation class for a row stack whose largest per-shard
    cardinality is ``max_bits``, under the current mode. Falls back to
    dense for high-cardinality rows in every mode."""
    m = mode()
    if m == "off":
        return DENSE
    k = pack_width(max_bits)
    if k > MAX_PACK_WIDTH:
        return DENSE
    if m == "auto" and k > WORDS_PER_SHARD // AUTO_RATIO:
        return DENSE
    return PACKED


# ---------------------------------------------------------------------------
# byte accounting — THE helper both representation classes answer to
# (satellite: the ``s_pad * WORDS_PER_SHARD * 4`` lines were hand-
# expanded across the planner; the eviction budget drifts silently if
# any of them disagrees with what is actually resident).
# ---------------------------------------------------------------------------


def dense_nbytes(s_pad: int) -> int:
    """HBM bytes of a dense [s_pad, W] uint32 stack."""
    return int(s_pad) * WORDS_PER_SHARD * 4


def packed_nbytes(s_pad: int, k: int) -> int:
    """HBM bytes of a packed [s_pad, K] int32 index stack."""
    return int(s_pad) * int(k) * 4


# ---------------------------------------------------------------------------
# kernel variants (traced inside the planner's jitted programs)
# ---------------------------------------------------------------------------


def packed_expand(idxs):
    """[S, K] packed indices -> [S, W] dense uint32 planes.

    Scatter-with-add: valid entries are unique per row, so the bits
    they contribute to a word are distinct powers of two and add IS or.
    Sentinel entries land in a trash word at column W (SENTINEL >> 5
    == W exactly), sliced off before return.
    """
    s = idxs.shape[0]
    w = (idxs >> 5).astype(jnp.int32)                    # sentinel -> W
    b = jnp.uint32(1) << (idxs & 31).astype(jnp.uint32)
    base = jnp.zeros((s, WORDS_PER_SHARD + 1), dtype=jnp.uint32)
    rows = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[:, None],
                            idxs.shape)
    return base.at[rows, w].add(b)[:, :WORDS_PER_SHARD]


def packed_count(idxs):
    """Popcount-over-indices: set bits per shard without expanding."""
    return jnp.sum(idxs < SENTINEL, axis=-1, dtype=jnp.int32)


def packed_and_dense_count(idxs, plane):
    """|packed ∧ dense| per shard: gather each index's word from the
    dense plane and test its bit — O(K) data motion instead of O(W)."""
    valid = idxs < SENTINEL
    w = jnp.clip(idxs >> 5, 0, WORDS_PER_SHARD - 1).astype(jnp.int32)
    words = jnp.take_along_axis(plane, w, axis=-1)
    bits = (words >> (idxs & 31).astype(jnp.uint32)) & jnp.uint32(1)
    return jnp.sum(bits.astype(jnp.int32) * valid.astype(jnp.int32),
                   axis=-1, dtype=jnp.int32)


def packed_pair_count(a_idx, b_idx):
    """|packed ∧ packed| per shard: sorted-membership intersection.
    Both stacks are sorted with sentinel padding at the tail, so a
    searchsorted probe of a's entries into b plus an equality check
    counts the intersection; a's sentinels are masked out so they can
    never match b's sentinel padding."""
    def one(a_row, b_row):
        pos = jnp.searchsorted(b_row, a_row)
        pos = jnp.clip(pos, 0, b_row.shape[0] - 1)
        hit = (b_row[pos] == a_row) & (a_row < SENTINEL)
        return jnp.sum(hit, dtype=jnp.int32)

    return jax.vmap(one)(a_idx, b_idx)


def _dense_expand(planes):
    return planes


def _dense_and_count(a, b):
    return bitops.intersection_count(a, b)


#: (representation class, op) -> device kernel. The residency-pairing
#: checker requires every class in REPR_CLASSES to register a variant
#: for every op the dense class supports — a new class cannot land
#: with a partial kernel set.
KERNELS = {
    (DENSE, "expand"): _dense_expand,
    (DENSE, "count"): bitops.count,
    (DENSE, "and_count"): _dense_and_count,
    (DENSE, "pair_count"): bitops.intersection_count,
    (PACKED, "expand"): packed_expand,
    (PACKED, "count"): packed_count,
    (PACKED, "and_count"): packed_and_dense_count,
    (PACKED, "pair_count"): packed_pair_count,
    (HLL, "expand"): sketch_kernels.hll_expand,
    (HLL, "count"): sketch_kernels.hll_count,
    (HLL, "and_count"): sketch_kernels.hll_and_count,
    (HLL, "pair_count"): sketch_kernels.hll_pair_count,
    (KEYPLANE, "expand"): _keyplane.plane_expand,
    (KEYPLANE, "count"): _keyplane.plane_count,
    (KEYPLANE, "and_count"): _keyplane.plane_and_count,
    (KEYPLANE, "pair_count"): _keyplane.plane_pair_count,
}


def kernel(klass: str, op: str):
    """Dispatch-table lookup; raising on an unknown pair keeps a class
    table / kernel table drift loud at plan time, not wrong at run
    time."""
    try:
        return KERNELS[(klass, op)]
    except KeyError:
        raise KeyError(
            f"no {op!r} kernel registered for representation class "
            f"{klass!r} — see exec/residency.py KERNELS") from None
