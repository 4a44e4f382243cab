"""Device-side ANALYZE kernel.

One jitted XLA program per (row-count, bucket, topn) signature computes,
for an int64-encoded column + validity mask:

  count, null_count, exact NDV, equal-depth histogram (bounds / cumulative
  counts / per-bound repeats), TopN (values + counts), FM sketch bitmask,
  and a CM sketch counter table.

Reference analog: pkg/statistics/row_sampler.go + cmsketch.go + fmsketch.go
+ histogram build in pkg/statistics/builder.go — all replaced by a single
sort + segment-sum pass, which is the TPU-idiomatic formulation (sorting is
MXU/VPU-friendly; no hash tables, no per-row host loops).

All dtypes reach this kernel as int64 in an order-preserving encoding
(ints/dates/times/decimals/dict-codes are already ordinal; float64 goes
through the sign-magnitude flip in `sortable_f64`).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

FM_MAPS = 64          # fmsketch.go keeps one hash map; we keep 64 KMV-style
CM_DEPTH = 4          # cmsketch.go NewCMSketch(depth=..) default-ish
CM_WIDTH = 2048


def sortable_f64(a: np.ndarray) -> np.ndarray:
    """Map float64 to int64 preserving total order (NaN sorts last)."""
    i = a.view(np.int64).copy()
    i ^= (i >> 63) & np.int64(0x7FFFFFFFFFFFFFFF)
    return i


def unsortable_f64(i: int) -> float:
    v = np.int64(i)
    v ^= (v >> 63) & np.int64(0x7FFFFFFFFFFFFFFF)
    return float(np.array(v, dtype=np.int64).view(np.float64))


def _hash64(x, seed):
    """splitmix64 finalizer — branch-free, vectorizes on device."""
    h = (x + jnp.uint64(seed)) * jnp.uint64(0x9E3779B97F4A7C15)
    h = (h ^ (h >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return h ^ (h >> 31)


def cop_stats_column(x, valid, n_buckets, n_top):
    n = x.shape[0]
    nv = valid.sum()
    # two-key sort: invalid rows strictly after valid ones, values exact
    inv = (~valid).astype(jnp.int32)
    _, xs = jax.lax.sort((inv, x), num_keys=2)
    pos = jnp.arange(n)
    in_valid = pos < nv
    # run-length structure over the sorted valid region
    prev = jnp.concatenate([xs[:1] - 1, xs[:-1]])
    boundary = (xs != prev) | (pos == 0)
    ndv = jnp.sum(boundary & in_valid)
    run_id = jnp.cumsum(boundary.astype(jnp.int32)) - 1
    run_counts = jax.ops.segment_sum(in_valid.astype(jnp.int64), run_id, n)
    run_vals = jax.ops.segment_max(jnp.where(in_valid, xs, jnp.int64(-2**62)),
                                   run_id, n)
    # TopN (tiny tables: fewer rows than n_top slots — clamp, then pad
    # with zero-count entries so the output shape stays static)
    k = min(n_top, n)
    top_counts, top_idx = jax.lax.top_k(run_counts, k)
    top_vals = run_vals[top_idx]
    if k < n_top:
        top_counts = jnp.concatenate(
            [top_counts, jnp.zeros(n_top - k, top_counts.dtype)])
        top_vals = jnp.concatenate(
            [top_vals, jnp.zeros(n_top - k, top_vals.dtype)])
    # equal-depth histogram: bound j at sorted position min((j+1)*size, nv)-1
    size = jnp.maximum((nv + n_buckets - 1) // n_buckets, 1)
    ub_pos = jnp.minimum((jnp.arange(n_buckets) + 1) * size, nv) - 1
    ub_pos_c = jnp.clip(ub_pos, 0, n - 1)
    bounds = xs[ub_pos_c]
    cum_counts = ub_pos + 1                      # rows <= bounds[j]
    # repeats of each bound = pos+1 - first position of that value
    xs_clean = jnp.where(in_valid, xs, jnp.int64(2**62))
    first_pos = jnp.searchsorted(xs_clean, bounds, side="left")
    repeats = jnp.maximum(cum_counts - first_pos, 0)
    # FM/KMV sketch: k minimum hash values over DISTINCT values (run
    # starts of the sorted column) — mergeable across shards
    h = _hash64(xs.astype(jnp.uint64), 0x5bd1e995)
    h = jnp.where(boundary & in_valid, h, jnp.uint64(0xFFFFFFFFFFFFFFFF))
    hs = jnp.sort(h)[:FM_MAPS]
    # CM sketch: depth x width counters
    cm = jnp.zeros((CM_DEPTH, CM_WIDTH), dtype=jnp.int64)
    for d in range(CM_DEPTH):
        idx = (_hash64(xs.astype(jnp.uint64), 0xABCD + d * 7919) %
               jnp.uint64(CM_WIDTH)).astype(jnp.int32)
        cm = cm.at[d, idx].add(in_valid.astype(jnp.int64))
    return dict(count=nv.astype(jnp.int64),
                min_val=xs[0],
                null_count=(n - nv).astype(jnp.int64),
                ndv=ndv.astype(jnp.int64),
                bounds=bounds, cum_counts=cum_counts, repeats=repeats,
                top_vals=top_vals, top_counts=top_counts,
                kmv=hs, cm=cm)


# jitted under the function's name, which is its module's in a profiler
# trace: beside the cop_<program>_... programs, not an anonymous kernel
_stats_kernel = jax.jit(cop_stats_column, static_argnums=(2, 3))


def build_column_stats(data: np.ndarray, valid: np.ndarray,
                       n_buckets: int = 64, n_top: int = 16):
    """Run the ANALYZE kernel; returns plain-numpy dict."""
    if data.dtype == np.float64:
        enc = sortable_f64(data)
    else:
        enc = data.astype(np.int64, copy=False)
    out = _stats_kernel(jnp.asarray(enc), jnp.asarray(valid),
                        int(n_buckets), int(n_top))
    return {k: np.asarray(v) for k, v in out.items()}
