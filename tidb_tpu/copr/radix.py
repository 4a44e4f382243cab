"""SCATTER-strategy device group-by: multi-pass scatter radix partition
+ segment reduce (the high-NDV follow-on to copr/segment.py).

Motivation (ROADMAP "kill the real-TPU high-NDV cliff"): SEGMENT's
partition pass is one giant single-key ``lax.sort`` — O(n log n)
comparator lanes on hardware built for streaming data movement, and on
real TPU the hndv bench rung ran at 0.05x a single numpy core (the
2026-07-31 record tabulated in ROADMAP.md).  Flare (PAPERS.md) is the
precedent for replacing a general-purpose engine's sort-based shuffle
with native specialized partitioning; HiFrames compiles dataframe
aggregations to tight partition loops the same way.

Algorithm (per device, static shapes, one traced program):

1. Group keys hash exactly as SEGMENT (copr/segment.key_hash, or the
   hoisted ``prehashed`` column).  The top log2(B) bits of the hash are
   the radix bucket id over the pow2 ``num_buckets`` space; dead rows
   take a tail bucket ``B`` (one extra bit) so they sort last.
2. ``radix_passes(B)`` STABLE counting-sort passes order rows
   bucket-major, RADIX_BITS per pass, LSB digit first: per pass a
   bucket-digit histogram, an exclusive cumsum of bucket offsets, and a
   gather/scatter reorder of the row-index permutation — O(passes * n)
   data movement, no comparator network.  The lowering is plain XLA:
   each RADIX_BITS-digit pass runs as RADIX_BITS 1-bit stable partition
   subpasses — a 1-bit counting sort degenerates to one cumsum (the
   histogram+offsets of a 2-digit space) plus one scatter, all fully
   vectorized.  It is a stable LSD radix sort of the bucket key, so the
   final permutation — and therefore every downstream state — is
   bit-identical across regrows.
3. The shared partition->states suffix of copr/segment.py
   (states_from_partition) detects segment boundaries and
   scatter-reduces into the (num_buckets,) state table: hash collisions
   still split into duplicate partials merged host-side by true key
   equality, ``__ngroups__`` still drives the client's bucket regrow.

Within a bucket, rows keep batch order (stable passes) rather than
residual-hash order, so two groups sharing a bucket may interleave into
extra duplicate segments; at the high NDV this strategy is selected for
(buckets ~ 1.25x groups) multi-group buckets are rare, and duplicates
are merged host-side exactly like hash collisions — correctness never
depends on occupancy, only the observed ``__ngroups__`` does (and the
regrow loop already converges on it: more buckets = more ordered bits
= fewer interleavings).
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from ..ops.sortkeys import INT64_MAX
from . import dag as D
from .segment import batch_hash, states_from_partition

# --------------------------------------------------------------------- #
# the multi-pass scatter partition
# --------------------------------------------------------------------- #

def _partition_xla(bid, bits: int, n: int):
    """Stable LSD radix partition, one bit per subpass, pure XLA: the
    1-bit counting sort's histogram+offsets degenerate to a single
    cumsum (offsets = [0, total_zeros]) and the reorder is one scatter
    of the index permutation — O(n) streaming work per subpass, no
    comparator lanes.  RADIX_BITS subpasses == one priced pass."""
    idx = jnp.arange(n, dtype=jnp.int32)
    pos_iota = jnp.arange(n, dtype=jnp.int32)
    for s in range(bits):
        b = ((bid[idx] >> jnp.int32(s)) & jnp.int32(1)).astype(jnp.int32)  # valueflow: ok - masked to one bit
        zb = jnp.cumsum(jnp.int32(1) - b, dtype=jnp.int32)  # incl. zeros
        nz = zb[n - 1]
        # zeros keep order at offset 0; ones at offset total_zeros
        pos = jnp.where(b == 0, zb - 1, nz + pos_iota - zb)
        idx = jnp.zeros((n,), jnp.int32).at[pos].set(idx)
    return idx


def scatter_permutation(h, sel, num_buckets: int, n: int):
    """Row permutation ordering rows bucket-major over the pow2
    ``num_buckets`` radix space: the partition key is the top
    log2(B) + RADIX_RESIDUAL_BITS bits of the uint64 hash (bucket id
    major, residual hash minor — the residual bits keep co-bucketed
    groups from interleaving into duplicate segments), dead rows in a
    tail key one bit above."""
    bits = D.radix_key_bits(num_buckets)
    key_bits = bits - 1                   # top bit = dead-row tail key
    # np scalar: stays 64-bit regardless of the embedder's x64 flag
    key = (h >> np.uint64(64 - key_bits)).astype(jnp.int32)  # valueflow: ok - top key_bits <= 31 bits survive the shift
    key = jnp.where(sel, key, jnp.int32(1 << key_bits))
    return _partition_xla(key, bits, n)


def agg_scatter_states(agg: D.Aggregation, batch, ev, memo) -> dict:
    """SCATTER-strategy per-device partial states: multi-pass scatter
    radix partition + the shared segment-reduce suffix.  State layout,
    host merge, and the ``__ngroups__`` regrow contract are identical
    to SEGMENT — only the partition pass differs."""
    from .exec import _sel_array, group_keyinfo
    B = agg.num_buckets
    assert B > 0 and (B & (B - 1)) == 0, \
        "SCATTER aggregation needs a power-of-two num_buckets"
    assert D.radix_passes(B) <= D.MAX_RADIX_PASSES, \
        "SCATTER pass count exceeds MAX_RADIX_PASSES (contract-checked)"
    n = len(batch.cols[0][0]) if batch.cols else 0
    sel = _sel_array(batch.sel, n)

    keyinfo = group_keyinfo(agg, batch, ev, memo, n)
    h = batch_hash(agg, batch, keyinfo, n)
    idx = scatter_permutation(h, sel, B, n)
    # boundary detection compares the FULL hash (not just bucket bits):
    # same int64 view + dead-row parking convention as SEGMENT
    hv = jnp.where(sel, h.astype(jnp.int64), INT64_MAX)
    return states_from_partition(agg, batch, ev, keyinfo, hv[idx], idx,
                                 sel[idx], n)


# --------------------------------------------------------------------- #
# prehash hoist (regrow re-entries reuse the hashed keys)
# --------------------------------------------------------------------- #

def prehash_plan(agg: D.Aggregation, hash_offset: int):
    """If this radix-strategy aggregation can hoist its key hash, return
    ``(prehashed_dag, leaf_scan)`` — the rebuilt dag whose leaf scan
    reads one extra int64 column at ``hash_offset`` (the stacked hash
    array the client appends), plus the ORIGINAL leaf scan the hash
    program evaluates keys over; else None.  Hoistable: a plain
    TableScan(+Selection) chain — a Projection/Expand/join would change
    the batch schema the appended column rides on."""
    import dataclasses
    if agg.strategy not in D.RADIX_STRATEGIES or agg.prehashed:
        return None
    chain = []
    cur = agg.child
    while isinstance(cur, D.Selection):
        chain.append(cur)
        cur = cur.child
    if not isinstance(cur, D.TableScan):
        return None
    from ..types import dtypes as dt
    new_scan = D.TableScan(cur.col_offsets + (hash_offset,),
                           cur.col_dtypes + (dt.bigint(False),))
    node: D.CopNode = new_scan
    for sel_node in reversed(chain):
        node = dataclasses.replace(sel_node, child=node)
    return dataclasses.replace(agg, child=node, prehashed=True), cur


class HashProgram:
    """Tiny sharded program computing the per-row uint64 key hash over
    the stacked scan columns, stored as int64 in the same (S, C) layout
    — launched ONCE per statement so every bucket-space regrow re-entry
    reuses it (the prehash satellite).  Dead/pad rows hash too (their
    lanes are masked downstream by ``sel``), so no live-count input is
    needed and the program is capacity-independent.  Resolves through
    the copforge compile cache like every spmd builder (keyed on a
    minimal keys-only dag + a ``keyhash`` variant tag), so the hash
    program warms/persists and never re-compiles on the serving path
    after a restart."""

    def __init__(self, scan: D.TableScan, group_by: tuple, mesh):
        from jax.sharding import PartitionSpec as P

        from ..analysis.compilekey import named_jit
        from ..compilecache import cached_call
        from ..expr.compile import Evaluator
        from ..parallel.mesh import SHARD_AXIS, shard_map
        self.mesh = mesh
        self.scan = scan
        self.group_by = group_by
        # the keys-only dag identifying WHAT is hashed (scan + key
        # exprs); num_buckets never shapes the program
        key_dag = D.Aggregation(scan, tuple(group_by), (),
                                D.GroupStrategy.SCATTER, num_buckets=1)

        def device_fn(cols, counts):
            del counts
            from .exec import DeviceBatch, group_keyinfo
            from .segment import key_hash
            s, c = cols[0][0].shape
            flat = [(v.reshape(-1), True if m is None else m.reshape(-1))
                    for v, m in cols]
            picked = [flat[off] for off in scan.col_offsets]
            batch = DeviceBatch(list(picked), True)
            keyinfo = group_keyinfo(key_dag, batch, Evaluator(jnp), {},
                                    s * c)
            hv = key_hash(keyinfo, s * c).astype(jnp.int64)
            return hv.reshape(s, c)

        self._fn = named_jit(shard_map(
            device_fn, mesh=mesh, in_specs=(P(SHARD_AXIS), P(SHARD_AXIS)),
            out_specs=P(SHARD_AXIS)), "hash", key_dag)
        self.name = self._fn.__name__
        self._cached = cached_call(self._fn, key_dag, mesh, "solo",
                                   extra=("keyhash",))

    def __call__(self, cols, counts):
        return self._cached(tuple(cols), counts)


@functools.lru_cache(maxsize=64)
def get_hash_program(scan: D.TableScan, group_by: tuple,
                     mesh) -> HashProgram:
    return HashProgram(scan, group_by, mesh)


# --------------------------------------------------------------------- #
# per-pass phase microbench (the bench hndv rung's breakdown)
# --------------------------------------------------------------------- #

def phase_bench(n: int, num_buckets: int, iters: int = 3) -> dict:
    """Measured per-pass phase times (histogram / cumsum / scatter ms)
    of the partition over synthetic digits, plus the priced pass count
    — the bench JSON's ``radix_breakdown``.  Single-device: the phases
    are per-device work, the mesh only multiplies them.  Rows cap at
    2^20 (the reported ``rows``) so the advisory microbench never
    dominates a rung's wall/memory budget."""
    import time

    import jax
    n = max(min(n, 1 << 20), 1)       # host int: bench-sized row cap
    rng = np.random.default_rng(17)
    dig = jnp.asarray(rng.integers(0, 1 << D.RADIX_BITS, n),
                      dtype=jnp.int32)
    idx = jnp.arange(n, dtype=jnp.int32)
    nd = 1 << D.RADIX_BITS

    @jax.jit
    def hist_phase(d):
        return jnp.zeros((nd,), jnp.int32).at[d].add(1)

    @jax.jit
    def cumsum_phase(h):
        return jnp.cumsum(h, dtype=jnp.int32) - h

    @jax.jit
    def scatter_phase(d, ix):
        zb = jnp.cumsum(jnp.int32(1) - (d & 1), dtype=jnp.int32)
        pos_iota = jnp.arange(n, dtype=jnp.int32)
        pos = jnp.where((d & 1) == 0, zb - 1, zb[n - 1] + pos_iota - zb)
        return jnp.zeros((n,), jnp.int32).at[pos].set(ix)

    def timed(fn, *args):
        jax.block_until_ready(fn(*args))          # compile outside timing
        best = float("inf")
        for _ in range(max(iters, 1)):
            t = time.time()
            jax.block_until_ready(fn(*args))
            best = min(best, time.time() - t)
        return round(best * 1e3, 3)

    hist = hist_phase(dig)
    return {"passes": D.radix_passes(num_buckets), "rows": n,
            "histogram_ms": timed(hist_phase, dig),
            "cumsum_ms": timed(cumsum_phase, hist),
            "scatter_ms": timed(scatter_phase, dig, idx)}


__all__ = ["agg_scatter_states", "scatter_permutation", "prehash_plan",
           "get_hash_program", "HashProgram", "phase_bench"]
