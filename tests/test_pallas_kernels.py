"""Pallas kernels vs the plain-XLA oracle (interpret mode on CPU)."""

import jax.numpy as jnp
import numpy as np
import pytest

from pilosa_tpu.ops import bitops, pallas_kernels


@pytest.fixture
def pair(rng):
    a = rng.integers(0, 2**32, size=(5, 2048 * 3 + 100), dtype=np.uint32)
    b = rng.integers(0, 2**32, size=(5, 2048 * 3 + 100), dtype=np.uint32)
    return jnp.asarray(a), jnp.asarray(b)


@pytest.mark.parametrize("op,oracle", [
    ("and", bitops.intersection_count),
    ("or", bitops.union_count),
    ("xor", bitops.xor_count),
    ("andnot", bitops.difference_count),
])
def test_pair_count(pair, op, oracle):
    a, b = pair
    got = pallas_kernels.pair_count(a, b, op)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(oracle(a, b)))


def test_pair_count_3d(pair):
    a, b = pair
    a3 = jnp.stack([a, b])
    b3 = jnp.stack([b, a])
    got = pallas_kernels.pair_count(a3, b3, "and")
    assert got.shape == (2, 5)
    np.testing.assert_array_equal(
        np.asarray(got[0]), np.asarray(bitops.intersection_count(a, b))
    )
