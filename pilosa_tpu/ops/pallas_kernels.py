"""Pallas TPU kernels for the hot bitmap-reduction path.

The reference's hottest loops are the container intersection-count kernels
(roaring/roaring.go:3121-3258) driven by Count(Intersect(...)). Here that is
a single fused VPU pass: load uint32 word tiles from HBM into VMEM, bitwise
op, ``population_count``, row-sum — one HBM read per operand, no
intermediate materialization.

XLA usually fuses `popcount(a & b).sum()` on its own; these kernels pin the
fusion and the tiling for the benchmark path and give us a place to fold in
multi-op trees (e.g. popcount((a & b) &~ c)) that XLA sometimes splits.

On the CPU backend (the test mesh) the same kernels run with
``interpret=True``: the choice is a pure function of the platform, made
once per call in ``pair_count``, so on a TPU a kernel the
chip's compiler refuses raises instead of being answered some other
way. PILOSA_TPU_NO_PALLAS=1 is the one, explicit, way to the pure-XLA
expression.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from pilosa_tpu.ops import bitops

_DISABLED = os.environ.get("PILOSA_TPU_NO_PALLAS", "") == "1"

#: Row tile: 8 sublanes of int32; lane dim handled by the W tile.
_TILE_M = 8
#: Word tile along the shard axis; 2048 u32 = 8 KiB per operand tile.
_TILE_W = 2048

_OPS = {
    "and": jnp.bitwise_and,
    "or": jnp.bitwise_or,
    "xor": jnp.bitwise_xor,
    "andnot": lambda a, b: jnp.bitwise_and(a, jnp.bitwise_not(b)),
}


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def _accumulate_rowsum(o_ref, x):
    """Shared reduce tail: popcount, row-sum, init-or-accumulate over the
    W-tile grid axis."""
    pc = jax.lax.population_count(x).astype(jnp.int32)
    partial = jnp.sum(pc, axis=-1, keepdims=True)
    w_idx = pl.program_id(1)

    @pl.when(w_idx == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(w_idx != 0)
    def _acc():
        o_ref[...] += partial


def _count_kernel(op, a_ref, b_ref, o_ref):
    _accumulate_rowsum(o_ref, op(a_ref[...], b_ref[...]))


def _pad2d(x, tm, tw):
    m, w = x.shape
    pm = (-m) % tm
    pw = (-w) % tw
    if pm or pw:
        x = jnp.pad(x, ((0, pm), (0, pw)))
    return x


@functools.partial(jax.jit, static_argnames=("op", "interpret"))
def _pallas_pair_count(a, b, op: str, interpret: bool):
    """counts[...] = popcount(op(a, b)) per row; a, b broadcastable [..., W].

    Broadcast happens inside the jit so XLA elides the copy — a single
    filter row counted against an M-row stack still reads each operand
    from HBM once.
    """
    shape = jnp.broadcast_shapes(a.shape, b.shape)
    a = jnp.broadcast_to(a, shape).reshape((-1, shape[-1]))
    b = jnp.broadcast_to(b, shape).reshape((-1, shape[-1]))
    m0 = a.shape[0]
    a = _pad2d(a, _TILE_M, _TILE_W)
    b = _pad2d(b, _TILE_M, _TILE_W)
    m, w = a.shape
    grid = (m // _TILE_M, w // _TILE_W)
    out = pl.pallas_call(
        functools.partial(_count_kernel, _OPS[op]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((_TILE_M, _TILE_W), lambda i, j: (i, j)),
            pl.BlockSpec((_TILE_M, _TILE_W), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((_TILE_M, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, 1), jnp.int32),
        interpret=interpret,
    )(a, b)
    return out[:m0, 0]


def available() -> bool:
    return not _DISABLED


def pair_count(a, b, op: str = "and"):
    """Fused ``popcount(op(a, b))`` per row over [..., W] arrays; the
    XLA expression only under PILOSA_TPU_NO_PALLAS=1."""
    if _DISABLED:
        return {
            "and": bitops.intersection_count,
            "or": bitops.union_count,
            "xor": bitops.xor_count,
            "andnot": bitops.difference_count,
        }[op](a, b)
    shape = jnp.broadcast_shapes(a.shape, b.shape)[:-1]
    return _pallas_pair_count(a, b, op, _interpret()).reshape(shape)
