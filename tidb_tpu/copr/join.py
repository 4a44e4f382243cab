"""Device-side m:n join expansion: sorted-range lookup + cumsum slots.

Reference analog: the multi-match hash probe of the parallel hash join
(pkg/executor/join/hash_join_v2.go — partitioned build, concurrent probe
workers chasing hash-bucket chains).  Hash tables with chained buckets are
hostile to TPU (data-dependent loops, scatter-heavy); the TPU redesign
keeps the build side SORTED by key so a probe is two `searchsorted` ops
(lo/hi) giving each probe row's match count, and output rows are assigned
by cumsum — every step a dense vector op with static shapes.

The output batch has a fixed `out_capacity`; the true required size is
returned so the dispatcher can regrow and retry (kv.Request.Paging
grow-from-min analog, SURVEY.md §5.7).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .joinbuild import APART, KEY_ITSELF


def _compare_narrow(kv, ref) -> bool:
    """Whether a probe key and a build-side array can meet at int32: both
    are read at 32 signed bits or fewer (an int64 lane is two emulated
    32-bit lanes on a TPU)."""
    return ref.dtype == jnp.int32 and kv.dtype in (
        jnp.int8, jnp.int16, jnp.int32)


def direct_lookup(kv, grp, packing):
    """Probe a direct-addressed build side (dag.LookupJoin `dense`).
    kv: probe keys; grp: the aux group [(array, mask | True)].  Returns
    (matched, [(value, valid | True) per build column]); a column's
    value is meaningless where `matched` is False.

    `key - base` wraps for a key far outside the range; read unsigned it
    is then never below `span` (span <= 2^24 and both operands share one
    signed width), so one unsigned compare is the whole bounds check."""
    n_words, pbit, layout = packing
    meta = grp[0][0]
    kt, ut = (jnp.int32, jnp.uint32) if _compare_narrow(kv, meta) \
        else (jnp.int64, jnp.uint64)
    d = kv.astype(kt) - meta[0].astype(kt)
    matched = lax.bitcast_convert_type(d, ut) < meta[1].astype(ut)
    idx = jnp.where(matched, d, 0).astype(jnp.int32)  # valueflow: ok - a matched row's offset is below span <= 2^24
    words = [grp[2 + w][0].at[idx].get(mode="promise_in_bounds")
             for w in range(n_words)]
    if pbit >= 0:
        matched = matched & ((words[0] >> pbit) & 1).astype(bool)
    mins = grp[1][0]
    apart = iter(grp[2 + n_words:])
    out = []
    for j, (w, shift, bits, vbit, wide) in enumerate(layout):
        if w == KEY_ITSELF:
            out.append((kv.astype(jnp.int64 if wide else jnp.int32), True))
            continue
        if w == APART:
            tv, tm = next(apart)
            out.append((tv.at[idx].get(mode="promise_in_bounds"),
                        True if tm is True
                        else tm.at[idx].get(mode="promise_in_bounds")))
            continue
        vt = jnp.int64 if wide else jnp.int32
        v = ((words[w] >> shift) & ((1 << bits) - 1)).astype(vt) \
            + mins[j].astype(vt)
        out.append((v, True if vbit < 0
                    else ((words[w] >> vbit) & 1).astype(bool)))
    return matched, out


def sorted_lookup(kv, grp):
    """Probe a sorted unique build side: binary search, equality check,
    one gather a column (they come in key order: no permutation).
    Compared at the keys' own width where the probe key is as narrow."""
    sorted_keys = grp[0][0]
    if not _compare_narrow(kv, sorted_keys):
        sorted_keys = sorted_keys.astype(jnp.int64)
    kv = kv.astype(sorted_keys.dtype)
    idx = jnp.clip(jnp.searchsorted(sorted_keys, kv), 0,
                   sorted_keys.shape[0] - 1)
    matched = sorted_keys[idx] == kv
    return matched, [(bv[idx], True if bm is True else bm[idx])
                     for bv, bm in grp[2:]]


def match_ranges(sorted_keys, n_live, probe_keys, probe_ok):
    """Per-probe-row match ranges against a sorted build-key array.

    sorted_keys: (B,) int64, live keys sorted ascending in the first
    `n_live` slots (the rest arbitrary — callers park dead rows at the end
    with an INT64_MAX fill).  n_live: traced scalar or python int.
    probe_ok: bool mask (False = NULL/dead probe key -> matches nothing).
    Returns (lo, hi, cnt): int32/int64 arrays, cnt == matches per row.
    Clamping lo/hi to n_live keeps sentinel-valued dead slots out of the
    ranges even when a live key equals INT64_MAX.
    """
    lo = jnp.searchsorted(sorted_keys, probe_keys, side="left")
    hi = jnp.searchsorted(sorted_keys, probe_keys, side="right")
    lo = jnp.minimum(lo, n_live)
    hi = jnp.minimum(hi, n_live)
    cnt = jnp.where(probe_ok, hi - lo, 0)
    return lo, hi, cnt


def expand_slots(sel, cnt, kind: str, out_capacity: int):
    """Assign output slots for an inner/left expand join.

    sel: live probe rows; cnt: matches per probe row (0 where dead).
    Left joins give every live-but-unmatched probe row one null-extension
    slot.  Returns (probe_idx, offset, valid_out, is_ext, total):
      probe_idx (OC,) — which probe row fills each output slot,
      offset    (OC,) — 0-based index into that row's match range,
      valid_out (OC,) — slot holds a real output row,
      is_ext    (OC,) — slot is a left-join null extension,
      total     ()    — true output size (compare vs out_capacity).
    """
    n = cnt.shape[0]
    if kind == "left":
        cnt_ext = jnp.where(sel & (cnt == 0), 1, cnt)
    else:
        cnt_ext = cnt
    cum = jnp.cumsum(cnt_ext)
    starts = cum - cnt_ext
    total = cum[-1] if n else jnp.int64(0)
    j = jnp.arange(out_capacity, dtype=cum.dtype)
    pi = jnp.clip(jnp.searchsorted(cum, j, side="right"), 0, max(n - 1, 0))
    offset = j - starts[pi]
    valid_out = j < total
    is_ext = valid_out & (cnt[pi] == 0)
    return pi, offset, valid_out, is_ext, total


def gather_expand(batch_cols, sel, probe_key_ok, build_cols, perm,
                  lo, cnt, kind: str, out_capacity: int):
    """Materialize the expanded join output.

    batch_cols: probe [(value, mask|True)]; build_cols likewise (already
    row-aligned with `perm`'s target space); perm: sorted-order ->
    original-build-row permutation; lo/cnt from match_ranges.
    Returns (out_cols, out_sel, total) where out_cols = probe ++ build.
    """
    pi, offset, valid_out, is_ext, total = expand_slots(
        sel, cnt, kind, out_capacity)
    out_cols = []
    for v, m in batch_cols:
        gv = v[pi]
        gm = True if m is True else m[pi]
        out_cols.append((gv, gm))
    b = perm.shape[0]
    brow = perm[jnp.clip(lo[pi] + offset, 0, max(b - 1, 0))]
    bvalid_base = ~is_ext
    for v, m in build_cols:
        gv = v[brow]
        gm = bvalid_base if m is True else (m[brow] & bvalid_base)
        out_cols.append((gv, gm))
    return out_cols, valid_out, total


__all__ = ["direct_lookup", "sorted_lookup", "match_ranges", "expand_slots",
           "gather_expand"]
