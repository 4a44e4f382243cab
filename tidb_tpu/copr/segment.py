"""SEGMENT-strategy device group-by: hash -> radix bucket partition +
per-bucket segment reduce (the high-NDV aggregation kernel).

Reference analog: the parallel HashAgg the reference runs for
high-cardinality group-by (pkg/executor/aggregate/agg_hash_executor.go:94)
and the group-by-as-segment-reduction formulation of "Accelerating
Machine Learning Queries with Linear Algebra Query Processing"
(PAPERS.md).  Hash tables lose to partition+segment ops on TPU
(SURVEY.md §7 hard part 4); the SORT strategy already exploits that, but
its comparator carries 1 + 2*k int lanes per row and at millions of
groups the multi-operand sort is what turned the real-TPU hndv bench
rung into a 1000x cliff (0.05x numpy, ROADMAP.md's 2026-07-31 table).

Algorithm (per device, one traced program, static shapes throughout):

1. Group keys lower to the same canonical (zeroed value, null flag,
   order-preserving int64 code) triples the SORT path uses
   (copr/exec.group_keyinfo).
2. The key tuple avalanche-hashes (splitmix64 finalizer folded per key)
   into ONE uint64.  The top log2(num_buckets) bits are the radix bucket
   id over the power-of-two bucket space the planner/copcost derived
   from stats NDV, so partitioning rows bucket-major and ordering each
   bucket's residual key space happen in a single single-key partition
   pass — regardless of group-key arity.  A ``prehashed`` aggregation
   reads the hash from its LAST scan column instead (the client hoists
   hashing out of the bucket-space regrow loop, store/client).
3. Segment boundaries fall where the hash or any true key code/null flag
   changes between adjacent live rows.  The code comparison makes a
   64-bit hash collision produce DUPLICATE partial groups, never merged
   ones: the host final merge (copr/aggregate.merge_sorted_states)
   re-groups by true key equality, so a duplicate costs one table slot
   while a collision-merged group would be silently wrong.
4. Rows segment-reduce (`jax.ops.segment_sum`-style ``.at[gids]``
   scatters) into a (num_buckets,) state table; ``__ngroups__`` reports
   the true distinct count so the dispatcher regrows ``num_buckets`` and
   re-runs on overflow — the paging analog (SURVEY.md §5.7).

The partition-to-states suffix (boundary detect + scatter-reduce) is
shared with the SCATTER strategy (copr/radix.py), which replaces the
single giant ``lax.sort`` of step 2-3 with a multi-pass scatter radix
partition — same state layout, same collision-to-duplicate contract.

Like SORT, the per-device tables merge HOST-side with the stacked shard
layout of parallel/spmd.py (per-device group sets are unaligned — no
elementwise psum merge exists); int/decimal SUM limbs still ride the
2^31 limb-exactness fence of copr/exec._one_agg_state.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.sortkeys import INT64_MAX
from . import dag as D

# splitmix64 finalizer constants (Steele et al.); numpy scalars so the
# uint64 lanes stay 64-bit regardless of the embedder's x64 default
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)
_S30 = np.uint64(30)
_S27 = np.uint64(27)
_S31 = np.uint64(31)

# trace-count observability for the prehash satellite: every TRACE of
# the avalanche hash bumps this (tests pin that a bucket-space regrow
# sequence hashes the key tuple exactly once — in the hoisted hash
# program, not once per capacity re-entry)
HASH_TRACES = [0]


def _finalize64(z):
    """splitmix64 avalanche: every input bit reaches every output bit,
    so the TOP log2(B) bits are a uniform radix bucket id."""
    z = (z ^ (z >> _S30)) * _MIX1
    z = (z ^ (z >> _S27)) * _MIX2
    return z ^ (z >> _S31)


def key_hash(keyinfo, n):
    """One uint64 avalanche hash per row over the canonical key tuple.
    NULL flags fold in (a NULL key and a zero key must land in
    different buckets with overwhelming probability; exactness does not
    depend on it — boundary detection compares flags too)."""
    HASH_TRACES[0] += 1
    h = jnp.full((n,), _GOLDEN, jnp.uint64)
    for _vz, m, nullf, code in keyinfo:
        cu = code.astype(jnp.uint64)
        if m is not True:
            cu = cu + nullf.astype(jnp.uint64) * _GOLDEN
        h = _finalize64(h ^ cu)
    return h


def batch_hash(agg: D.Aggregation, batch, keyinfo, n):
    """Per-row uint64 key hash: the hoisted LAST scan column when the
    aggregation is ``prehashed`` (store/client computes it once per
    statement so regrow re-entries skip the k-key avalanche chain),
    else freshly avalanched from the canonical key tuple."""
    if agg.prehashed:
        hv = batch.cols[-1][0]
        # stored as int64 (device column dtype); two's-complement cast
        # restores the original uint64 bit pattern exactly
        return hv.astype(jnp.uint64)
    return key_hash(keyinfo, n)


def states_from_partition(agg: D.Aggregation, batch, ev, keyinfo,
                          hv_s, idx, sel_s, n) -> dict:
    """Shared partition->states suffix of the SEGMENT and SCATTER
    strategies: given rows permuted bucket-major by ``idx`` (with the
    permuted hash ``hv_s`` and live mask ``sel_s``), detect segment
    boundaries where the hash OR any true key code/null flag changes
    (the collision-to-duplicate guarantee) and scatter-reduce each
    segment into a (num_buckets,) state table."""
    from .exec import _ensure_array, _one_agg_state, _reduce
    B = agg.num_buckets
    # segment boundary: live row whose hash OR any true key differs from
    # the previous row (the collision-to-duplicate guarantee)
    diff = jnp.arange(n, dtype=jnp.int64) == 0
    diff = diff | (hv_s != jnp.roll(hv_s, 1))
    for _vz, m, nullf, code in keyinfo:
        cd_s = code[idx]
        diff = diff | (cd_s != jnp.roll(cd_s, 1))
        if m is not True:
            nf_s = nullf[idx]
            diff = diff | (nf_s != jnp.roll(nf_s, 1))
    newgrp = sel_s & diff
    gid = jnp.cumsum(newgrp.astype(jnp.int64)) - 1
    ngroups = jnp.sum(newgrp.astype(jnp.int64))
    gids = jnp.where(sel_s, gid, B)        # dead rows -> dropped scatter

    states: dict = {"__ngroups__": ngroups}
    states["__rows__"] = _reduce(sel_s.astype(jnp.int64), sel_s, gids, B,
                                 "sum", ev.platform)
    for j, (vz, m, _nf, _cd) in enumerate(keyinfo):
        val = jnp.zeros((B,), vz.dtype).at[gids].set(vz[idx], mode="drop")
        valid = jnp.zeros((B,), bool).at[gids].set(
            jnp.ones(n, bool)[idx] if m is True else m[idx], mode="drop")
        states[f"k{j}"] = {"val": val, "valid": valid}

    # aggregate over the PERMUTED batch so arg rows line up with gids
    pcols = [(_ensure_array(v, n)[idx],
              True if m is True else m[idx]) for v, m in batch.cols]
    pmemo: dict = {}
    for i, a in enumerate(agg.aggs):
        if a.func == D.AggFunc.COUNT and a.arg is None:
            states[f"a{i}"] = {"count": states["__rows__"]}
            continue
        av, am = ev.eval(a.arg, pcols, pmemo)
        states[f"a{i}"] = _one_agg_state(a, av, am, sel_s, gids, B, n,
                                         ev.platform)
    return states


def agg_segment_states(agg: D.Aggregation, batch, ev, memo) -> dict:
    """SEGMENT-strategy per-device partial states: radix-partition rows
    by hash bucket via ONE single-key ``lax.sort``, segment-reduce each
    bucket's key runs into a (num_buckets,) group table.  Same state
    layout as the SORT path (k{j} val/valid, a{i}, __rows__,
    __ngroups__) so merge/finalize and the regrow loop stay one code
    path."""
    from .exec import _sel_array, group_keyinfo
    B = agg.num_buckets
    assert B > 0 and (B & (B - 1)) == 0, \
        "SEGMENT aggregation needs a power-of-two num_buckets"
    n = len(batch.cols[0][0]) if batch.cols else 0
    sel = _sel_array(batch.sel, n)

    keyinfo = group_keyinfo(agg, batch, ev, memo, n)
    hv = batch_hash(agg, batch, keyinfo, n).astype(jnp.int64)
    # dead rows park at the tail; a live row hashing to INT64_MAX merely
    # interleaves with them, and its gids stay correct via sel_s below
    hv = jnp.where(sel, hv, INT64_MAX)
    # the radix partition pass: ONE single-key sort orders rows by
    # (bucket id = top bits, residual hash = low bits) at once
    hv_s, idx = lax.sort((hv, jnp.arange(n, dtype=jnp.int64)), num_keys=1)
    sel_s = sel[idx]
    return states_from_partition(agg, batch, ev, keyinfo, hv_s, idx,
                                 sel_s, n)


__all__ = ["agg_segment_states", "key_hash", "batch_hash",
           "states_from_partition", "HASH_TRACES"]
