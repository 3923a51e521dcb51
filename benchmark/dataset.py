"""The seeded dataset of a deployment and the roaring payload that loads it.

Copied from ``chip_smoke.py``'s ``shard_positions`` (proven on the chip),
generalised from its fixed 8 rows to the rows and densities a configuration
file states. A density is the share of a shard's columns drawn per row, with
replacement, so a row holds a little fewer distinct bits than that.

The roaring encoder is the benchmark's own: the load is part of the
yardstick (``import_mbits``), so the bytes it sends may not change with the
program's codec.
"""

from __future__ import annotations

import struct

import numpy as np

ROARING_MAGIC = 12348
TYPE_ARRAY = 1
TYPE_BITMAP = 2
ARRAY_MAX = 4096
CONTAINER_BITS = 1 << 16
_META = np.dtype([("key", "<u8"), ("typ", "<u2"), ("n1", "<u2")])


def row_densities(field_spec: dict) -> list[float]:
    """Density of each row of one field of a configuration."""
    dense = {int(r): float(d)
             for r, d in field_spec.get("dense_rows", {}).items()}
    return [dense.get(r, float(field_spec["density"]))
            for r in range(int(field_spec["rows"]))]


def shard_positions(seed: int, field: str, shard: int, width: int,
                    densities: list[float]) -> list[np.ndarray]:
    """Sorted unique in-shard columns of each row of ``field`` in
    ``shard``: a pure function of the seed, so any thread regenerates
    the same bits in any order."""
    rng = np.random.default_rng([seed, *field.encode(), shard])
    return [np.unique(rng.integers(0, width, int(width * d),
                                   dtype=np.uint32))
            for d in densities]


def shard_rows(config: dict, seed: int, shard: int) -> dict:
    """``{field: positions of each row}`` of one shard of a configuration,
    fields in name order."""
    width = 1 << int(config["shard_width_exp"])
    return {f: shard_positions(seed, f, shard, width, row_densities(spec))
            for f, spec in sorted(config["fields"].items())}


def pack_rows(per_field: dict, width: int) -> dict:
    """``{field: [rows, words]}`` uint64, one bit per column that any row
    of the shard holds, little-endian, straight from the positions.

    Columns that no row of any field holds in this shard are left out of
    the words (three quarters of them at these densities): without ``Not``
    no tree of rows can count such a column, and the reference's passes
    over the words are what its time is made of."""
    used = np.unique(np.concatenate(
        [pos for per_row in per_field.values() for pos in per_row]
        or [np.empty(0, dtype=np.uint32)]))
    n_bits = -(-max(len(used), 1) // 64) * 64
    out = {}
    dense = np.zeros(n_bits, dtype=bool)
    for field, per_row in per_field.items():
        words = np.empty((len(per_row), n_bits // 64), dtype=np.uint64)
        for r, pos in enumerate(per_row):
            dense[:] = False
            dense[np.searchsorted(used, pos)] = True
            words[r] = np.packbits(dense, bitorder="little").view(np.uint64)
        out[field] = words
    return out


def fragment_positions(per_row: list[np.ndarray], width: int) -> np.ndarray:
    """The fragment's positions in the import-roaring encoding:
    ``row * width + column``, ascending."""
    return np.concatenate([pos.astype(np.uint64) + np.uint64(r * width)
                           for r, pos in enumerate(per_row)])


def roaring_encode(positions: np.ndarray) -> bytes:
    """Strictly ascending uint64 positions -> Pilosa's roaring wire
    format (cookie 12348): array containers up to 4,096 values, bitmap
    containers above; no run containers."""
    positions = np.asarray(positions, dtype=np.uint64)
    if len(positions) == 0:
        return struct.pack("<II", ROARING_MAGIC, 0)
    if not (positions[:-1] < positions[1:]).all():
        raise ValueError("roaring_encode: positions not strictly ascending")
    keys = positions >> np.uint64(16)
    lows = (positions & np.uint64(0xFFFF)).astype("<u2")
    starts = np.flatnonzero(np.concatenate(([True], keys[1:] != keys[:-1])))
    counts = np.diff(np.concatenate((starts, [len(positions)])))
    is_bitmap = counts > ARRAY_MAX
    meta = np.empty(len(starts), dtype=_META)
    meta["key"] = keys[starts]
    meta["typ"] = np.where(is_bitmap, TYPE_BITMAP, TYPE_ARRAY)
    meta["n1"] = counts - 1
    sizes = np.where(is_bitmap, CONTAINER_BITS // 8, 2 * counts)
    data_start = 8 + 12 * len(starts) + 4 * len(starts)
    offsets = data_start + np.concatenate(([0], np.cumsum(sizes)[:-1]))
    if not is_bitmap.any():
        payload = [lows.tobytes()]
    else:
        payload = []
        for lo, n, big in zip(starts.tolist(), counts.tolist(),
                              is_bitmap.tolist()):
            vals = lows[lo:lo + n]
            if big:
                bits = np.zeros(CONTAINER_BITS, dtype=bool)
                bits[vals] = True
                payload.append(np.packbits(bits, bitorder="little").tobytes())
            else:
                payload.append(vals.tobytes())
    return b"".join([struct.pack("<II", ROARING_MAGIC, len(starts)),
                     meta.tobytes(), offsets.astype("<u4").tobytes(),
                     *payload])
