"""Exact prefix sums of bounded non-negative integers in two levels:
narrow limbs summed inside blocks on the MXU, one small wide scan over
the block totals.

XLA:TPU lowers a scan along the minor axis as a 128-wide window a vreg:
`jnp.cumsum` at int64 (two u32 words) over 2^23 slots is 8.9 ms on a v5e
for 96 MB of traffic.  The same sums are a matrix product: view a lane
as `[n / 128, 128]`, multiply by the 128 x 128 upper triangle of ones,
and every row holds its own inclusive prefix sums.  The MXU multiplies
bfloat16, which holds an integer below 256 exactly, so a summand is cut
into limbs of 8 bits; a product is `limb * 1`, a block's sum is at most
128 * 255 = 32,640, and float32 accumulates that exactly in any order.
Two limbs share an output: the odd one's triangle holds 256 (a power of
two, exact), the pair lies side by side along the contraction, and the
float32 sum `lo + 256 * hi` stays below 2^23, still exact, so the dot
writes half the words.  The pairs are put back together at int64, and
what the blocks before a slot's own add up to (an int64 scan over the
n / 128 block totals, each the plain sum of its block) is added in the
same pass.
"""

from __future__ import annotations

import jax.numpy as jnp

BLOCK = 128     # slots a block: a vreg's lanes, the MXU's side
LIMB_BITS = 8   # a limb is below 256: exact in bfloat16
LIMB = 1 << LIMB_BITS


def limb_count(bits: int) -> int:
    """Limbs a summand below 2**`bits` is cut into."""
    return max(-(-bits // LIMB_BITS), 1)


def _block_sums(groups: list, weights) -> list:
    """[n / BLOCK, K] bfloat16 arrays times the [K, BLOCK] `weights` in
    one batched dot -> as many int32 [n / BLOCK, BLOCK] arrays."""
    if not groups:
        return []
    sums = jnp.dot(jnp.stack(groups), weights,
                   preferred_element_type=jnp.float32)
    return list(sums.astype(jnp.int32))  # valueflow: ok - below 2^23, whole


def limb_cumsum(lanes: list) -> list:
    """Inclusive prefix sums at int64 of [(x, bits)]: each `x` an int64
    array of n slots, n whole blocks, every value in [0, 2**`bits`); the
    whole array's sum has to fit int64.  Bits of a value at or above
    `bits` are dropped.  One batched dot for every pair of limbs of every
    lane, one more for the lanes with an odd limb left over."""
    if not lanes:
        return []
    n = lanes[0][0].shape[0]
    assert n % BLOCK == 0 and all(x.shape == (n,) for x, _ in lanes), \
        "lanes of whole blocks, all of one length"
    pairs, odd = [], []
    for x, bits in lanes:
        limbs = [((x >> (i * LIMB_BITS)) & (LIMB - 1)).astype(jnp.bfloat16)
                 .reshape(n // BLOCK, BLOCK)
                 for i in range(limb_count(bits))]
        pairs += [jnp.concatenate(limbs[i:i + 2], axis=1)
                  for i in range(0, len(limbs) - 1, 2)]
        odd += limbs[len(limbs) & ~1:]
    tri = jnp.triu(jnp.ones((BLOCK, BLOCK), jnp.bfloat16))
    pairs = iter(_block_sums(pairs, jnp.concatenate([tri, tri * LIMB])))
    odd = iter(_block_sums(odd, tri))
    out = []
    for x, bits in lanes:
        k = limb_count(bits)
        parts = [next(pairs) for _ in range(k // 2)] \
            + [next(odd) for _ in range(k & 1)]
        inside = parts[0].astype(jnp.int64)
        for i, p in enumerate(parts[1:], 1):
            inside = inside + (p.astype(jnp.int64) << (2 * LIMB_BITS * i))
        # a block's total from the summands themselves, not from the
        # dot's last column: a column read turns the dot's output, and
        # all that reads it, to the transposed layout (three relayouts of
        # every slot, 1.7 ms a launch of 18)
        totals = jnp.sum((x & ((1 << bits) - 1)).reshape(n // BLOCK, BLOCK),
                         axis=1)
        before = jnp.cumsum(totals, dtype=jnp.int64) - totals
        out.append((inside + before[:, None]).reshape(n))
    return out


__all__ = ["BLOCK", "limb_count", "limb_cumsum"]
