"""`ops/limbscan.limb_cumsum` alone: the two-level limb scan equals
`np.cumsum` at int64 bit for bit, whatever the limb count, with every
limb full (each carries into the next pair) and across block borders
(the int64 scan over the block totals carries)."""

import jax
import numpy as np
import pytest

from tidb_tpu.ops.limbscan import BLOCK, limb_count, limb_cumsum


@pytest.mark.parametrize("n", [128, 256, (1 << 17) + 128])
@pytest.mark.parametrize("limbs", range(1, 9))
def test_equals_numpy_at_int64(limbs, n):
    bits = min(8 * limbs, 63)
    rng = np.random.default_rng(limbs * n)
    # as many summands as add up inside int64, the others 0
    held = rng.permutation(n) < (1 << (63 - bits))
    lanes = [np.where(held, rng.integers(0, 1 << bits, n, dtype=np.int64), 0),
             np.where(held, (1 << bits) - 1, 0),        # every limb full
             rng.integers(0, 2, n, dtype=np.int64)]     # a NULL lane
    got = jax.jit(lambda a, b, c: limb_cumsum(
        [(a, bits), (b, bits), (c, 1)]))(*lanes)
    for x, c in zip(lanes, got):
        assert c.dtype == np.int64 and c.shape == (n,)
        assert np.array_equal(np.asarray(c), np.cumsum(x))


def test_bits_above_the_bound_are_dropped_and_no_lane_is_no_dot():
    """A summand wider than it said is cut to its bound, not smeared
    into its neighbour's limbs: `runagg` reruns such a record wider and
    throws this answer away, but it has to be an answer."""
    x = np.arange(2 * BLOCK, dtype=np.int64) * 0x101
    got = jax.jit(lambda v: limb_cumsum([(v, 8), (v, 16)]))(x)
    assert np.array_equal(np.asarray(got[0]), np.cumsum(x & 0xFF))
    assert np.array_equal(np.asarray(got[1]), np.cumsum(x & 0xFFFF))
    assert limb_cumsum([]) == []
    assert [limb_count(b) for b in (0, 1, 8, 9, 31, 32, 40, 63)] \
        == [1, 1, 1, 2, 4, 4, 5, 8]
