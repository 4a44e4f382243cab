"""Fused coprocessor execution: DAG -> one jit-compiled XLA program.

Reference analog: unistore/cophandler/closure_exec.go:468 — the fused
scan→selection→agg/topN/limit single-pass "closure" executor that is the
CPU hot loop the TPU kernels replace.  Where the reference builds a Go
closure per DAG, we trace the DAG once into jnp ops and let XLA fuse the
whole pipeline into a handful of HBM-bandwidth-bound kernels; programs are
cached per (dag digest, shard capacity) like the cop cache keys on
(region version, request digest) (coprocessor_cache.go, SURVEY.md §A.6).

Execution model: static shapes only (XLA).  A shard is a fixed-capacity
batch of columns; live rows are tracked with a selection mask `sel` instead
of compaction (dynamic shapes).  Row-returning plans compact on device into
a caller-chosen capacity via cumsum-scatter; if the result overflows, the
dispatcher retries with a larger capacity — the paging analog
(kv.Request.Paging, SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field, replace
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..expr.compile import Evaluator, vand
from ..ops.sortkeys import INT64_MAX, INT64_MIN, sortable_int64
from ..types import dtypes as dt
from . import dag as D

K = dt.TypeKind

# Dense grouped reduction: below this group count, reduce via broadcast
# compare (VPU-friendly, fuses into the scan); above, scatter-add.
DENSE_BROADCAST_MAX_GROUPS = 64

# The dense SUM/COUNT reduction (`_dense_limb_states`): integer lanes are
# split into int32 limbs of LIMB_BITS bits and every accumulator sums at
# most ACC_RUN of them, so no sum leaves 32 bits.  22 is the least width
# at which an int64 takes three limbs; rows are viewed as whole
# (sublane, lane) tiles of LANES lanes, ACC_RUN tiles to an accumulator
# tile.  One variadic reduce takes at most REDUCE_OPERANDS operands
# (more groups x lanes than that take several passes).
LIMB_BITS = 22
ACC_RUN = 1 << (31 - LIMB_BITS)
LANES = 128
REDUCE_OPERANDS = 128


@dataclass
class DeviceBatch:
    """Columns + live-row selection mask flowing between fused operators.

    `extras` carries named traced scalars that must surface to the
    dispatcher alongside the result — today the true output size of an
    expanding join, so the paging loop can regrow its capacity.

    `stacked` says how the slot axis lies in device memory: as that many
    equal row-major runs (the (S, C) stacked shards of one device,
    flattened by parallel/spmd, which the TPU holds interleaved tile by
    tile).  The scan sets it, operators that keep the slot axis keep it,
    those that build a new one (Expand, an expanding join, TopN) drop it
    to 1.

    `facts` is the one dict of a trace: a lowering writes there, under
    the name `copr/facts.py` gives it, what it decided (static values
    only).  An operator makes its output with `replace(batch, ...)`,
    naming what it changes, so extras and facts go from batch to batch."""
    cols: list  # list[(value, valid)]
    sel: Any    # bool array | True
    extras: dict = field(default_factory=dict)
    stacked: int = 1
    facts: dict = field(default_factory=dict)


def _ensure_array(v, n):
    if hasattr(v, "shape") and v.shape:
        return v
    return jnp.full((n,), v)  # planlint: ok - dtype follows the operand


def _sel_array(sel, n):
    return jnp.ones((n,), bool) if sel is True else sel


# --------------------------------------------------------------------- #
# Aggregation partial states (the psum seam, SURVEY.md §A.4)
# --------------------------------------------------------------------- #

def _reduce(vals, mask, gids, num_groups, how: str, platform: str):
    """Masked (optionally grouped) reduction.

    how: 'sum' | 'min' | 'max'.  gids None => scalar reduction.
    Grouped: dense (G,) output.  Strategy is PER-PLATFORM (`platform`:
    the one the program is traced for, `Evaluator.platform`): on TPU a
    broadcast one-hot compare for small G (scatter lowering on TPU can
    serialize); on CPU the (G, N) broadcast costs G x the scan traffic
    per aggregate and XLA's scatter-add is cheap, so CPU always
    scatters.  The integer SUM and COUNT states of a DENSE aggregation
    on a TPU do not come here: `_dense_limb_states`."""
    neutral = {"sum": 0, "min": _max_of(vals.dtype), "max": _min_of(vals.dtype)}[how]
    v = jnp.where(mask, vals, jnp.asarray(neutral, vals.dtype))
    if gids is None:
        return getattr(jnp, how)(v)
    if _onehot_form(num_groups, platform):
        onehot = gids[None, :] == jnp.arange(num_groups, dtype=gids.dtype)[:, None]
        vv = jnp.where(onehot, v[None, :], jnp.asarray(neutral, vals.dtype))
        return getattr(jnp, how)(vv, axis=1)
    out = jnp.full((num_groups,), neutral, vals.dtype)
    if how == "sum":
        return out.at[gids].add(v, mode="drop")
    return getattr(out.at[gids], how)(v, mode="drop")


def _onehot_form(num_groups: int, platform: str) -> bool:
    """A grouped reduction as a one-hot compare (small G, not the CPU)
    or as a scatter?  `_reduce`'s docstring says why."""
    if platform is None:
        raise ValueError("an aggregation is lowered for a platform: "
                         "Evaluator(jnp, platform=...)")
    return platform != "cpu" and num_groups <= DENSE_BROADCAST_MAX_GROUPS


def _max_of(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf
    return jnp.iinfo(dtype).max


def _min_of(dtype):
    if jnp.issubdtype(dtype, jnp.floating):
        return -jnp.inf
    return jnp.iinfo(dtype).min


def _one_agg_state(a: D.AggDesc, av, am, sel, gids, num_groups, n,
                   platform: str, narrow: bool = False, cnt=None) -> dict:
    """Partial state for one AggDesc over (possibly grouped) rows.
    `platform`: `_reduce`'s.  `cnt`: the count of the argument's
    non-NULL live rows a group, where the caller has it already.

    Layout (all named arrays so psum/pmin/pmax merges are mechanical —
    see parallel/collectives.py MERGE_SPECS):
      count -> {count}
      sum   -> decimal/int: {hi, lo, cnt} (int64 limb split, exact when
               recombined host-side); proven-narrow decimal/int
               (analysis/valueflow): {sum, cnt} single int64 word;
               float: {sum, cnt}
      min   -> {min, cnt};  max -> {max, cnt}
    """
    av = _ensure_array(av, n)
    mask = sel if am is True else (sel & am)

    def reduce(vals, how):
        return _reduce(vals, mask, gids, num_groups, how, platform)
    if cnt is None:
        cnt = reduce(mask.astype(jnp.int64), "sum")
    if a.func == D.AggFunc.COUNT:
        return {"count": cnt}
    if a.func == D.AggFunc.SUM:
        kind = a.arg.dtype.kind
        if kind in (K.FLOAT64, K.FLOAT32):
            return {"sum": reduce(av.astype(jnp.float64), "sum"),
                    "cnt": cnt}
        if narrow:
            # valueflow proved Σv over the WHOLE table (all shards, all
            # batches, with headroom) stays inside int64, so the per-batch
            # sum and every psum/host partial can't wrap either: one int64
            # word, half the state bytes, no limb fence.  Bit-identical to
            # the limb path (Σhi<<32 + Σlo == Σv in two's complement).
            return {"sum": reduce(av.astype(jnp.int64), "sum"), "cnt": cnt}
        # decimal AND integer sums accumulate as (hi, lo) int64 limbs.
        # Exactness argument (types/decimal.py): per row |hi| < 2^32 and
        # lo < 2^32, so with n < 2^31 rows per batch neither limb sum can
        # wrap int64; recombination is exact.  n is a static shape, so
        # this fence is free.
        _limb_row_fence(n)
        with jax.named_scope("limb_split"):
            v = av.astype(jnp.int64)
            hi = reduce(v >> 32, "sum")
            lo = reduce(v & 0xFFFFFFFF, "sum")
        return {"hi": hi, "lo": lo, "cnt": cnt}
    if a.func == D.AggFunc.MIN:
        return {"min": reduce(av, "min"), "cnt": cnt}
    if a.func == D.AggFunc.MAX:
        return {"max": reduce(av, "max"), "cnt": cnt}
    raise NotImplementedError(a.func)


def _limb_row_fence(n: int) -> None:
    if n >= 2 ** 31:
        raise OverflowError(
            f"shard batch of {n} rows exceeds the 2^31 limb-exact "
            "SUM bound; use more/smaller shards")


def agg_states(agg: D.Aggregation, scan_cols, row_count, ev: Evaluator,
               aux, stacked: int = 1) -> tuple:
    """Execute agg.child and build partial states.

    An Expand child (WITH ROLLUP) aggregates LEVEL BY LEVEL over the
    un-expanded batch instead of materializing the levels×n replication:
    each grouping-set level synthesizes its key/gid columns over the SAME
    n-row child batch, builds DENSE partial states, and merges them with
    the shard-merge combiners — identical math, 1/levels the peak HBM
    (the levels×n materialization OOM-crashed the v5e worker at SF=10).
    Returns (states, child_batch-for-extras-and-facts).  `stacked`: the
    runs the flat scan columns consist of (DeviceBatch.stacked).

    TPU-only (`ev.platform`): on CPU the materialized expand fuses into
    one pass and measures slightly faster; on TPU the replication is
    what OOMs."""
    ch = agg.child
    if isinstance(ch, D.Expand) \
            and agg.strategy == D.GroupStrategy.DENSE \
            and ev.platform == "tpu":
        with jax.named_scope("scan_filter"):
            base = _exec_node(ch.child, scan_cols, row_count, ev, aux,
                              stacked)
        with jax.named_scope("aggregate"):
            return _expand_level_states(agg, ch, base, ev), base
    with jax.named_scope("scan_filter"):
        batch = _exec_node(ch, scan_cols, row_count, ev, aux, stacked)
    with jax.named_scope("aggregate"):
        return _agg_partial_states(agg, batch, ev, {}), batch


def _expand_level_states(agg: D.Aggregation, exp: D.Expand,
                         base: DeviceBatch, ev: Evaluator) -> dict:
    from .aggregate import _MERGE
    n = len(base.cols[0][0]) if base.cols else 0
    L = len(exp.keys)
    memo: dict = {}
    child_cols = [(_ensure_array(v, n), m) for v, m in base.cols]
    keyvals = []
    for k in exp.keys:
        v, m = ev.eval(k, base.cols, memo)
        keyvals.append((_ensure_array(v, n), m))

    def combine(name, a, b):
        how = _MERGE[name]
        if how == "sum":
            return a + b
        return jnp.minimum(a, b) if how == "min" else jnp.maximum(a, b)

    merged: dict = {}
    for lvl in range(exp.levels):
        cols = list(child_cols)
        for j, (v, m) in enumerate(keyvals):
            if lvl + j < L:            # key j live on this level
                cols.append((v, m))
            else:                      # rolled: NULL for every row
                cols.append((v, jnp.zeros(n, bool)))
        cols.append((jnp.full(n, lvl, jnp.int64), True))
        st = _agg_partial_states(agg, replace(base, cols=cols), ev, {})
        if not merged:
            merged = st
        else:
            for k, v in st.items():
                if isinstance(v, dict):
                    merged[k] = {f: combine(f, merged[k][f], a)
                                 for f, a in v.items()}
                else:
                    merged[k] = combine(k, merged[k], v)
    return merged


def _agg_partial_states(agg: D.Aggregation, batch: DeviceBatch, ev: Evaluator,
                        memo: dict):
    """Per-shard partial-state pytree for an Aggregation node.

    SCALAR/DENSE: fixed group domain, psum-mergeable across shards.
    SORT: unbounded key domain via multi-key sort + segment-reduce into
    a fixed-capacity group table (host merge across shards) — the TPU
    answer to the reference's high-NDV parallel HashAgg
    (pkg/executor/aggregate/agg_hash_executor.go:94); hash tables lose to
    sort+segment ops on TPU (SURVEY.md §7 hard part 4).  On a TPU its
    COUNTs and integer SUMs are lowered by copr/runagg (the aggregates'
    inputs travel with the sort; no gather or scatter a slot); a MIN,
    a MAX, a float SUM and the CPU mesh keep `_agg_sort_states`.
    Adds '__rows__' (COUNT(*) per group) for occupancy.
    """
    if agg.host_merged:
        # what the launch says of itself (copr/facts.py)
        batch.facts["agg_strategy"] = agg.strategy.value
        batch.facts["group_capacity"] = agg.group_capacity
        if agg.topn is not None:    # copr/runagg alone ranks on the device
            batch.facts["group_topn"] = "host"
        from .runagg import agg_run_states, run_form
        if ev.platform == "tpu" and run_form(agg):
            return agg_run_states(agg, batch, ev, memo)
        return _agg_sort_states(agg, batch, ev, memo)

    n = len(batch.cols[0][0]) if batch.cols else 0
    sel = _sel_array(batch.sel, n)

    gids = None
    num_groups = 1
    if agg.strategy == D.GroupStrategy.DENSE:
        gids = _dense_group_ids(agg, batch, ev, memo)
        num_groups = agg.num_groups
        if dense_limb_form(agg, ev.platform):
            return _dense_limb_states(agg, batch, ev, memo, gids, sel, n)
        batch.facts["agg_limbs"] = 0

    states: dict[str, Any] = {}
    states["__rows__"] = _reduce(sel.astype(jnp.int64), sel, gids,
                                 num_groups, "sum", ev.platform)
    for i, a in enumerate(agg.aggs):
        if a.func == D.AggFunc.COUNT and a.arg is None:
            states[f"a{i}"] = {"count": states["__rows__"]}
            continue
        av, am = ev.eval(a.arg, batch.cols, memo)
        states[f"a{i}"] = _one_agg_state(a, av, am, sel, gids, num_groups, n,
                                         ev.platform,
                                         narrow=(i in agg.narrow_sums))
    return states


def dense_limb_form(agg: D.Aggregation, platform: str) -> bool:
    """Does this aggregation's SUM and COUNT reduction take the limb form
    (`_dense_limb_states`) in a program traced for `platform`?  Where
    the dense one-hot form ends and scatter begins is `_reduce`'s rule."""
    return agg.strategy == D.GroupStrategy.DENSE \
        and _onehot_form(agg.num_groups, platform)


def dense_view(n: int, stacked: int = 1) -> tuple:
    """((runs, blocks, tiles, LANES), pad): the view of `n` flat rows the
    dense SUM/COUNT reduction reduces over its `tiles` axis, at most
    ACC_RUN of them.  Flat rows made of `stacked` equal runs of whole
    tiles are viewed run by run: on the TPU, where stacked (S, C) shards
    lie tile by tile across the S runs, that is the arrays' own byte
    order, and the reduce adds tile upon tile, element by element, with
    no cross-lane step in the pass.  Any other batch is one run, padded
    with `pad` dead rows."""
    stacked = max(stacked, 1)
    tiles = n // stacked // LANES
    acc = min(ACC_RUN, tiles)
    if tiles and n == stacked * tiles * LANES and tiles % acc == 0:
        return (stacked, tiles // acc, acc, LANES), 0
    tiles = max(-(-n // LANES), 1)
    acc = min(ACC_RUN, tiles)
    blocks = -(-tiles // acc)
    return (1, blocks, acc, LANES), blocks * acc * LANES - n


def _limbs(v) -> list:
    """An integer (or boolean) array as int32 limbs of LIMB_BITS bits,
    as few as its dtype needs: the low ones masked (unsigned), the top
    one shifted arithmetically (signed), so that sum(limb_i <<
    (LIMB_BITS * i)) == v for every value of the dtype.  (uint64 reads
    as the int64 of the same bits, as the (hi, lo) split always has.)"""
    if v.dtype == bool or v.dtype.itemsize < 4:
        return [v.astype(jnp.int32)]    # valueflow: ok - widening only
    if jnp.issubdtype(v.dtype, jnp.unsignedinteger):
        v = v.astype(jnp.int64)
    k = -(-8 * v.dtype.itemsize // LIMB_BITS)
    mask = (1 << LIMB_BITS) - 1
    return [((v >> (LIMB_BITS * i)) & mask).astype(jnp.int32)  # valueflow: ok - masked to LIMB_BITS bits
            for i in range(k - 1)] \
        + [(v >> (LIMB_BITS * (k - 1))).astype(jnp.int32)]  # valueflow: ok - at most 32 - LIMB_BITS bits left


def _add_tuples(a, b):
    return tuple(x + y for x, y in zip(a, b))


def _words(sums: Sequence) -> tuple:
    """Limb sums s_i (int64 per group) as the (hi, lo) words of the SUM
    state: hi * 2^32 + lo == sum(s_i << (LIMB_BITS * i)), both words as
    far inside int64 as sum(v >> 32) and sum(v & 0xFFFFFFFF) are."""
    hi = lo = jnp.zeros_like(sums[0])
    for i, s in enumerate(sums):
        shift = LIMB_BITS * i
        if shift >= 32:
            hi = hi + (s << (shift - 32))
        else:
            hi = hi + (s >> (32 - shift))
            lo = lo + ((s & ((1 << (32 - shift)) - 1)) << shift)
    return hi, lo


def _dense_limb_states(agg: D.Aggregation, batch: DeviceBatch, ev: Evaluator,
                       memo: dict, gids, sel, n: int) -> dict:
    """The partial states of a DENSE aggregation (`_one_agg_state`'s
    layouts, '__rows__' included) with every integer SUM and every COUNT
    reduced in ONE pass over the rows.

    Lanes are collected once: each distinct NULL mask (the selection
    alone is one) is a COUNT lane, each distinct (argument, mask) is
    split into int32 limb lanes (`_limbs`): `sum(x)` and `avg(x)` share
    theirs.  The group one-hot is built once a group and mask, and all
    lanes of all groups go through one variadic reduce over the tiles
    axis of `dense_view` (the products and limbs have that one consumer
    and are never written out), whose int32 partial sums a second,
    small reduce adds up at int64.  The limb sums are put together
    again on the device into {hi, lo, cnt} or, for a `narrow_sums` slot,
    {sum, cnt}.  Float SUMs, MIN and MAX keep `_reduce` and take only
    their `cnt` from here."""
    G = agg.num_groups
    shape, pad = dense_view(n, batch.stacked)

    def view(a, fill=0):
        if pad:
            a = jnp.pad(a, (0, pad), constant_values=fill)
        return a.reshape(shape)

    masks: list = []    # (NULL mask | True, group ids (dead rows: G), COUNT lane)
    lanes: list = []    # an int32 lane a row: (mask, limb; None: the mask's count)
    sums: dict = {}     # (argument, COUNT lane) -> (first limb's lane, limbs)

    def count_lane(am) -> int:
        for seen, _g, lane in masks:
            if seen is am:
                return lane
        live = sel if am is True else (sel & am)
        masks.append((am, view(jnp.where(live, gids, G), G), len(lanes)))
        lanes.append((len(masks) - 1, None))
        return len(lanes) - 1

    def sum_lanes(a, av, cnt: int) -> tuple:
        try:
            key = (a.arg, cnt)
            hash(key)
        except TypeError:       # an argument that holds an array constant
            key = (len(lanes), cnt)
        if key not in sums:
            limbs = _limbs(_ensure_array(av, n))
            sums[key] = (len(lanes), len(limbs))
            lanes.extend((lanes[cnt][0], view(limb)) for limb in limbs)
        return sums[key]

    rows = count_lane(True)
    plan = []           # an aggregate: (value, mask, COUNT lane, first, limbs)
    for a in agg.aggs:
        if a.func == D.AggFunc.COUNT and a.arg is None:
            plan.append(None)
            continue
        av, am = ev.eval(a.arg, batch.cols, memo)
        cnt = count_lane(am)
        limb = a.func == D.AggFunc.SUM \
            and a.arg.dtype.kind not in (K.FLOAT64, K.FLOAT32)
        plan.append((av, am, cnt) + (sum_lanes(a, av, cnt) if limb
                                     else (0, 0)))

    width = len(lanes)
    step = max(1, REDUCE_OPERANDS // width)
    parts = []          # a group: its lanes' int32 partial sums
    for g0 in range(0, G, step):
        ops = []
        for grp in range(g0, min(G, g0 + step)):
            hits = [g == grp for _am, g, _lane in masks]
            ops += [hits[m].astype(jnp.int32) if limb is None  # valueflow: ok - bool lane, [0, 1]
                    else jnp.where(hits[m], limb, 0) for m, limb in lanes]
        part = lax.reduce(tuple(ops), tuple(jnp.zeros((), jnp.int32)
                                            for _ in ops),
                          _add_tuples, (2,))
        parts += [part[at:at + width] for at in range(0, len(part), width)]
    # the second level: a lane's partial sums of every group, stacked,
    # summed at int64 into the (G,) array the states are made of.  (With
    # the sums stacked as scalars into a (G, lanes) table and a state its
    # column, one element came out wrong behind the psum of a one-device
    # mesh on a v5e: PERF.md section 7.)
    wide = tuple(jnp.stack([p[c] for p in parts]).astype(jnp.int64)
                 for c in range(width))
    table = lax.reduce(wide, tuple(jnp.zeros((), jnp.int64) for _ in wide),
                       _add_tuples, (1, 2, 3))

    batch.facts["agg_limbs"] = width
    states: dict[str, Any] = {"__rows__": table[rows]}
    for i, (a, lane) in enumerate(zip(agg.aggs, plan)):
        if lane is None:
            states[f"a{i}"] = {"count": table[rows]}
            continue
        av, am, cnt, first, k = lane
        if not k:
            states[f"a{i}"] = _one_agg_state(a, av, am, sel, gids, G, n,
                                             ev.platform, cnt=table[cnt])
        elif i in agg.narrow_sums:
            # the total fits one word (valueflow), so adding the shifted
            # limb sums modulo 2^64 gives it exactly
            total = table[first]
            for j in range(1, k):
                total = total + (table[first + j] << (LIMB_BITS * j))
            states[f"a{i}"] = {"sum": total, "cnt": table[cnt]}
        else:
            _limb_row_fence(n)
            hi, lo = _words(table[first:first + k])
            states[f"a{i}"] = {"hi": hi, "lo": lo, "cnt": table[cnt]}
    return states


def group_keyinfo(agg: D.Aggregation, batch: DeviceBatch, ev: Evaluator,
                  memo: dict, n: int) -> list:
    """Canonical per-group-key (zeroed value, mask, null flag, order-
    preserving int64 code) tuples — the key representation both
    lowerings of SORT share.  NULL values are zeroed so all NULLs
    share one group; -0.0 groups with +0.0 (SQL equality, not bit
    equality)."""
    keyinfo = []
    for e in agg.group_by:
        v, m = ev.eval(e, batch.cols, memo)
        v = _ensure_array(v, n)
        if v.dtype == bool:
            v = v.astype(jnp.int64)
        nullf = (jnp.zeros(n, jnp.int32) if m is True
                 else (~m).astype(jnp.int32))  # valueflow: ok - bool lane, [0, 1]
        vz = v if m is True else jnp.where(m, v, jnp.zeros((), v.dtype))
        if e.dtype.is_float:
            vz = jnp.where(vz == 0, jnp.zeros((), vz.dtype), vz)
        code = sortable_int64(jnp, vz, e.dtype.is_float,
                              e.dtype.kind == K.UINT64)
        keyinfo.append((vz, m, nullf, code))
    return keyinfo


def _agg_sort_states(agg: D.Aggregation, batch: DeviceBatch, ev: Evaluator,
                     memo: dict):
    """SORT-strategy grouped aggregation: one multi-key lax.sort, segment
    boundaries by key change, scatter-reduce into a (group_capacity,)
    state table.

    Per key j the states carry {'val', 'valid'} gathered from the group's
    rows (NULL values zeroed so all NULLs share one group), plus
    '__ngroups__' — the TRUE distinct-group count, so the dispatcher can
    regrow capacity and re-run when it exceeds group_capacity (the paging
    analog, SURVEY.md §5.7)."""
    G = agg.group_capacity
    assert G > 0, "SORT aggregation needs group_capacity"
    n = len(batch.cols[0][0]) if batch.cols else 0
    sel = _sel_array(batch.sel, n)

    keyinfo = group_keyinfo(agg, batch, ev, memo, n)

    dead = (~sel).astype(jnp.int32)  # valueflow: ok - bool lane, [0, 1]
    ops: list = [dead]
    for _vz, _m, nullf, code in keyinfo:
        ops += [nullf, code]
    ops.append(jnp.arange(n, dtype=jnp.int64))
    with jax.named_scope("sort"):
        *sorted_keys, idx = lax.sort(tuple(ops),
                                     num_keys=1 + 2 * len(keyinfo))
    sel_s = sel[idx]

    # group boundary: live row whose key tuple differs from the previous
    diff = jnp.arange(n, dtype=jnp.int64) == 0
    for j in range(len(keyinfo)):
        nf_s, cd_s = sorted_keys[1 + 2 * j], sorted_keys[2 + 2 * j]
        diff = diff | (nf_s != jnp.roll(nf_s, 1)) | (cd_s != jnp.roll(cd_s, 1))
    newgrp = sel_s & diff
    gid = jnp.cumsum(newgrp.astype(jnp.int64)) - 1
    ngroups = jnp.sum(newgrp.astype(jnp.int64))
    gids = jnp.where(sel_s, gid, G)        # dead rows -> dropped scatter

    states: dict[str, Any] = {"__ngroups__": ngroups}
    states["__rows__"] = _reduce(sel_s.astype(jnp.int64), sel_s, gids, G,
                                 "sum", ev.platform)
    for j, (vz, m, _nf, _cd) in enumerate(keyinfo):
        val = jnp.zeros((G,), vz.dtype).at[gids].set(vz[idx], mode="drop")
        valid = jnp.zeros((G,), bool).at[gids].set(
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
        states[f"a{i}"] = _one_agg_state(a, av, am, sel_s, gids, G, n,
                                         ev.platform)
    return states


def _dense_group_ids(agg: D.Aggregation, batch: DeviceBatch, ev: Evaluator,
                     memo: dict):
    """Mixed-radix dense group id from the group-by key codes.

    Key domain [0, size_i); nullable keys get slot 0 for NULL and codes
    shifted by one (domain_sizes already include the NULL slot)."""
    n = len(batch.cols[0][0])
    gid = jnp.zeros((n,), jnp.int32)
    for e, size in zip(agg.group_by, agg.domain_sizes):
        v, m = ev.eval(e, batch.cols, memo)
        v = _ensure_array(v, n).astype(jnp.int32)  # valueflow: ok - DENSE key domain <= MAX_DENSE_GROUPS < 2^31
        if e.dtype.nullable:
            code = v + 1 if m is True else jnp.where(m, v + 1, 0)
        else:
            code = v
        gid = gid * jnp.int32(size) + code
    return gid


# --------------------------------------------------------------------- #
# Row output: device-side compaction (paging analog)
# --------------------------------------------------------------------- #

def compact(batch: DeviceBatch, capacity: int):
    """Pack live rows to the front of fixed-size output buffers via
    cumsum-scatter.  Returns (cols, count); rows past `capacity` are
    dropped — callers compare count vs capacity and re-run bigger."""
    n = len(batch.cols[0][0]) if batch.cols else 0
    sel = _sel_array(batch.sel, n)
    pos = jnp.cumsum(sel) - 1
    idx = jnp.where(sel, pos, capacity)  # out-of-bounds => dropped
    out_cols = []
    for v, m in batch.cols:
        v = _ensure_array(v, n)
        if v.dtype == bool:
            v = v.astype(jnp.int64)
        data = jnp.zeros((capacity,), v.dtype).at[idx].set(v, mode="drop")
        valid = jnp.zeros((capacity,), bool).at[idx].set(
            _sel_array(m, n) if m is not True else jnp.ones((n,), bool),
            mode="drop")
        out_cols.append((data, valid))
    return out_cols, jnp.sum(sel)


def compact_root(batch: DeviceBatch, capacity: int, platform: str):
    """The live rows of a rows-returning program's root in `capacity`
    slots a device, for the host: (cols + [the slots' live mask], need).
    `need` is the capacity the live rows take; above `capacity` rows are
    missing and the dispatcher reruns with more (the paging loop).

    Where a gather costs its indices and a scatter 90 ns an update (a
    TPU: PERF.md, PR 28) the rows leave as a lookup join's live probe
    rows do, by ONE column sort and ONE stacked gather
    (copr/join.compact_rows): the live rows then lie in no order and not
    at the front, which is what the mask says.  Elsewhere, and where the
    slots do not divide into the compaction's columns or all fit, by
    `compact`, whose rows lie at the front.  The batch's facts say which
    (copr/facts.py `rows_capacity`, `rows_compact`)."""
    from .join import compact_rows
    n = len(batch.cols[0][0]) if batch.cols else 0
    by_sort = platform != "cpu" and n > capacity \
        and not n % D.COMPACT_COLUMNS and not capacity % D.COMPACT_COLUMNS
    batch.facts["rows_capacity"] = capacity
    batch.facts["rows_compact"] = 1 if by_sort else 0
    if not by_sort:
        out_cols, need = compact(batch, capacity)
        live = jnp.arange(capacity, dtype=jnp.int64) < need
        return out_cols + [(live, live)], need
    with jax.named_scope("rows_compact"):
        cols, ok, need = compact_rows(
            [(_ensure_array(v, n), m) for v, m in batch.cols],
            _sel_array(batch.sel, n), capacity, batch.stacked)
    out_cols = [(v, ok if m is True else m) for v, m in cols]
    return out_cols + [(ok, ok)], need


# --------------------------------------------------------------------- #
# Node execution (traced)
# --------------------------------------------------------------------- #

def _exec_node(node: D.CopNode, scan_cols: Sequence, row_count, ev: Evaluator,
               aux: Sequence = (), stacked: int = 1):
    """`stacked`: the runs the flat scan columns consist of
    (DeviceBatch.stacked)."""
    if isinstance(node, D.TableScan):
        cols = [scan_cols[off] for off in node.col_offsets]
        n = len(cols[0][0]) if cols else 0
        if getattr(row_count, "ndim", 0) == 0:
            sel = jnp.arange(n, dtype=jnp.int64) < row_count
        else:
            # caller supplied a precomputed live-row mask (e.g. several
            # flattened shards with per-shard row counts, parallel/spmd.py)
            sel = row_count
        return DeviceBatch(list(cols), sel, stacked=stacked)

    def child():
        return _exec_node(node.child, scan_cols, row_count, ev, aux,
                          stacked)

    if isinstance(node, D.Selection):
        batch = child()
        memo: dict = {}
        sel = batch.sel
        n = len(batch.cols[0][0])
        for cond in node.conditions:
            v, m = ev.eval(cond, batch.cols, memo)
            v = _ensure_array(v, n)
            if v.dtype != bool:
                v = v != 0
            keep = v if m is True else (v & m)  # NULL -> filtered out
            sel = keep if sel is True else (sel & keep)
        return replace(batch, sel=sel)

    if isinstance(node, D.Projection):
        batch = child()
        memo = {}
        n = len(batch.cols[0][0])
        cols = []
        for e in node.exprs:
            v, m = ev.eval(e, batch.cols, memo)
            cols.append((_ensure_array(v, n), m))
        return replace(batch, cols=cols)

    if isinstance(node, D.Expand):
        batch = child()
        n = len(batch.cols[0][0]) if batch.cols else 0
        L = len(node.keys)
        LV = node.levels
        memo = {}
        sel = _sel_array(batch.sel, n)
        out_cols = []
        for v, m in batch.cols:
            v = _ensure_array(v, n)
            out_cols.append((jnp.tile(v, LV),
                             True if m is True else jnp.tile(m, LV)))
        lvl = jnp.repeat(jnp.arange(LV, dtype=jnp.int64), n)
        for j, k in enumerate(node.keys):
            v, m = ev.eval(k, batch.cols, memo)
            v = jnp.tile(_ensure_array(v, n), LV)
            keep = (lvl + j) < L       # key j live on levels l < L - j
            mj = keep if m is True else (jnp.tile(m, LV) & keep)
            out_cols.append((v, mj))
        out_cols.append((lvl, True))
        return replace(batch, cols=out_cols, sel=jnp.tile(sel, LV),
                       stacked=1)

    if isinstance(node, D.Limit):
        batch = child()
        n = len(batch.cols[0][0])
        sel = _sel_array(batch.sel, n)
        keep = sel & (jnp.cumsum(sel) <= node.limit)
        return replace(batch, sel=keep)

    if isinstance(node, D.TopN):
        with jax.named_scope("scan_filter"):
            batch = child()
        return _exec_topn(node, batch, ev)

    if isinstance(node, D.LookupJoin):
        batch = child()
        return _exec_lookup_join(node, batch, ev, aux)

    raise TypeError(node)


def _compact_probe(batch: DeviceBatch, capacity: int) -> DeviceBatch:
    """The batch's live rows in a batch of `capacity` slots
    (dag.LookupJoin.probe_capacity before the lookup, `match_capacity`
    after it; in no order: copr/join.live_rows).
    Extras: `join_live`, the live rows the device found, and
    `join_need`, the capacity they take: where that exceeds `capacity`
    rows are missing and the dispatcher reruns the statement
    uncompacted."""
    from .join import compact_rows
    n = len(batch.cols[0][0])
    sel = _sel_array(batch.sel, n)
    with jax.named_scope("join_compact"):
        cols, ok, need = compact_rows(
            [(_ensure_array(v, n), m) for v, m in batch.cols], sel,
            capacity, batch.stacked)
    return replace(batch, cols=cols, sel=ok, stacked=1, extras={
        **batch.extras, "join_live": jnp.sum(sel, dtype=jnp.int32),
        "join_need": need})


def _exec_lookup_join(node: D.LookupJoin, batch: DeviceBatch, ev: Evaluator,
                      aux) -> DeviceBatch:
    """Broadcast lookup join (see dag.LookupJoin for the two forms a
    build side takes).  aux is a tuple of GROUPS, one per chained join
    level."""
    def compacted(batch, capacity):
        n = len(batch.cols[0][0])
        if capacity < n and not n % D.COMPACT_COLUMNS:
            return _compact_probe(batch, capacity)
        # nothing to gain: every slot is looked up, none is lost
        zero = jnp.zeros((), jnp.int32)
        return replace(batch, extras={
            **batch.extras, "join_live": zero, "join_need": zero})

    if node.probe_capacity:
        batch = compacted(batch, node.probe_capacity)
    n = len(batch.cols[0][0])
    grp = aux[node.aux_slot]
    kv, km = ev.eval(node.probe_key, batch.cols, {})
    kv = _ensure_array(kv, n)

    if node.unique and node.kind in ("inner", "left"):
        from .join import direct_lookup, sorted_lookup
        if node.sharded:
            # the device's own tables, and after them the partition that
            # says which device owns a key and where its slot is
            # (dag.LookupJoin.sharded)
            return _sharded_lookup(node, batch, ev, grp[:-1], grp[-1][0],
                                   kv, km, compacted)
        with jax.named_scope("join_probe"):
            if node.dense:
                # the rows whose result something reads: the window form
                # places its windows by them and counts its misses there
                live = batch.sel if km is True else km if batch.sel is True \
                    else batch.sel & km
                matched, build, miss = direct_lookup(
                    kv, grp, node.packing, node.probe_window, live,
                    batch.stacked)
                if node.probe_window:
                    batch = replace(batch, extras={
                        **batch.extras, "join_window_miss":
                        batch.extras.get("join_window_miss", 0) + miss})
            else:
                matched, build = sorted_lookup(kv, grp)
        if km is not True:
            matched = matched & km
        out_cols = list(batch.cols)
        for gv, gm in build:
            out_cols.append((gv, matched if gm is True else (gm & matched)))
        sel = batch.sel
        if node.kind == "inner":
            sel = matched if sel is True else (sel & matched)
        out = replace(batch, cols=out_cols, sel=sel)
        return compacted(out, node.match_capacity) if node.match_capacity \
            else out

    sorted_keys = grp[0][0].astype(jnp.int64)
    kv = kv.astype(jnp.int64)
    perm = grp[1][0]
    build_cols = grp[2:]
    from .join import gather_expand, match_ranges
    sel = _sel_array(batch.sel, n)
    key_ok = sel if km is True else (sel & km)
    lo, _hi, cnt = match_ranges(sorted_keys, sorted_keys.shape[0], kv, key_ok)

    if node.kind in ("semi", "anti"):
        keep = (cnt > 0) if node.kind == "semi" else (cnt == 0)
        if node.kind == "anti" and node.null_aware and km is not True:
            keep = keep & km       # NOT IN: NULL probe key -> filtered
        return replace(batch, sel=sel & keep)

    oc = node.out_capacity
    assert oc > 0, "non-unique LookupJoin needs out_capacity"
    probe = [(_ensure_array(v, n), m) for v, m in batch.cols]
    out_cols, out_sel, total = gather_expand(
        probe, sel, key_ok, list(build_cols), perm, lo, cnt, node.kind, oc)
    return replace(batch, cols=out_cols, sel=out_sel, stacked=1,
                   extras={**batch.extras, "join_total": total})


def _sharded_lookup(node: D.LookupJoin, batch: DeviceBatch, ev: Evaluator,
                    grp, part, kv, km, compacted) -> DeviceBatch:
    """A unique direct-addressed lookup in a build that stays sharded
    where it lives (dag.LookupJoin `sharded`, `exchange`), inside a
    shard_map program: a live probe row whose key this device owns is
    looked up in place, by windows where the plan says so; one whose key
    another device owns travels there (parallel/exchange.exchange_rows),
    is looked up where the table is and goes on from there.  The output
    is the device's own slots and then the slots it received: every live
    row of the mesh is in exactly one device's output, and every row of
    one key on one device (a GROUP BY of the key above has its groups
    whole).  Extras: `exchange_need`, the slots the fullest bucket
    takes (rows are missing above the capacity), and `exchange_sent`.
    With `exchange` 0 (one device) nothing travels."""
    from ..parallel.exchange import (exchange_passes, exchange_rows,
                                     key_places)
    from ..parallel.mesh import SHARD_AXIS
    from .join import _compare_narrow, direct_lookup
    n = len(batch.cols[0][0])
    kt = jnp.int32 if _compare_narrow(kv, part) else jnp.int64
    part = part.astype(kt)
    sel = _sel_array(batch.sel, n)
    live = sel if km is True else sel & km

    def lookup(keys, window=0, ok=True, stacked=1):
        dest, at = key_places(keys.astype(kt), part)
        return dest, direct_lookup(keys, grp, node.packing, window, ok,
                                   stacked, offset=at)
    if not node.exchange:
        with jax.named_scope("join_probe"):
            dest, (matched, build, miss) = lookup(
                kv, node.probe_window, live, batch.stacked)
            # (nothing travels: a key another device owns finds no row)
            matched = matched & (dest == lax.axis_index(SHARD_AXIS))
        extras, away, cap = dict(batch.extras), False, 0
    else:
        n_dev = lax.axis_size(SHARD_AXIS)
        cap = min(node.exchange, n)     # a bucket never holds more than all
        probe = [(_ensure_array(v, n), m) for v, m in batch.cols]
        batch.facts["exchange_passes"] = exchange_passes(n, n_dev, cap)
        with jax.named_scope("join_exchange"):
            dest, at = key_places(kv.astype(kt), part)
            rcols, rok, need, sent = exchange_rows(
                probe, live, dest, n_dev, cap, batch.stacked)
        away = live & (dest != lax.axis_index(SHARD_AXIS))
        with jax.named_scope("join_probe"):
            matched, build, miss = direct_lookup(
                kv, grp, node.packing, node.probe_window, live & ~away,
                batch.stacked, offset=at)
            matched = matched & ~away
            rkv, rkm = ev.eval(node.probe_key, rcols, {})
            rkv = _ensure_array(rkv, n_dev * cap)
            _rdest, (rmatched, rbuild, _) = lookup(rkv)
        extras = {**batch.extras, "exchange_need": need,
                  "exchange_sent": sent}
    if node.probe_window:
        extras["join_window_miss"] = \
            batch.extras.get("join_window_miss", 0) + miss
    if km is not True:
        matched = matched & km
    out_cols = list(batch.cols)
    for gv, gm in build:
        out_cols.append((gv, matched if gm is True else (gm & matched)))
    here = sel if away is False else sel & ~away
    if node.kind == "inner":
        here = here & matched
    stacked = batch.stacked
    if node.exchange:
        # a row that left is live where it went, not here
        if rkm is not True:
            rmatched = rmatched & rkm
        m = n_dev * cap

        def both(a, b):
            if a is True and b is True:
                return True
            return jnp.concatenate([_sel_array(a, n), _sel_array(b, m)])
        rout = list(rcols) + [(gv, rmatched if gm is True else gm & rmatched)
                              for gv, gm in rbuild]
        out_cols = [(jnp.concatenate([_ensure_array(v, n), rv]),
                     both(vm, rvm))
                    for (v, vm), (rv, rvm) in zip(out_cols, rout)]
        there = rok & rmatched if node.kind == "inner" else rok
        here, stacked = jnp.concatenate([here, there]), 1
    out = replace(batch, cols=out_cols, sel=here, stacked=stacked,
                  extras=extras)
    return compacted(out, node.match_capacity) if node.match_capacity \
        else out


def _topn_lanes(node: D.TopN, cols, sel, rows, ev: Evaluator) -> list:
    """The TopN comparator's lanes over one batch of rows, ascending, in
    priority order: (1) dead-row flag so filtered rows always sort last,
    (2) per key, a NULL flag encoding MySQL ordering (NULLs first ASC,
    last DESC), (3) the order-preserving integer key — bitwise-NOT for
    DESC, an exact overflow-free order reversal — and (4) `rows`, each
    row's index in the whole input, which makes the order total and
    batch-stable.  No clamping: every distinct key value keeps its rank
    (review finding: clamping collapsed the extreme key values at the
    limit boundary).

    The comparator is kept as narrow as the plan allows, because the
    TPU compiler's time for a sort grows steeply with the number and
    width of its key lanes (int64 is emulated as two 32-bit lanes; a
    stable sort carries a hidden index key on top of ours): a key known
    non-NULL gets no NULL flag, a key read at a narrow physical width is
    compared at int32, and the index rides as the last key of an
    unstable sort."""
    memo: dict = {}
    n = len(rows)
    lanes = [(~sel).astype(jnp.int32)]  # valueflow: ok - bool lane, [0, 1]
    for e, desc in (node.sort_keys or ((node.sort_key, node.desc),)):
        v, m = ev.eval(e, cols, memo)
        v = _ensure_array(v, n)
        if m is not True:
            # NULL sorts first in ASC, last in DESC
            flag = jnp.where(m, 1, 0) if not desc else jnp.where(m, 0, 1)
            lanes.append(flag.astype(jnp.int32))  # valueflow: ok - literal 0/1 lanes
        if jnp.issubdtype(v.dtype, jnp.signedinteger) \
                and v.dtype.itemsize <= 4:
            key = v.astype(jnp.int32)   # valueflow: ok - widening only
        else:
            key = sortable_int64(jnp, v, e.dtype.is_float,
                                 e.dtype.kind == K.UINT64)
        lanes.append(~key if desc else key)   # exact reversal
    lanes.append(rows)
    return lanes


def _sort_lanes(lanes: Sequence) -> tuple:
    return lax.sort(tuple(lanes), num_keys=len(lanes), is_stable=False)


def _lex_smaller(a, b):
    """Of two lane tuples the lexicographically smaller: the comparator
    of a variadic `lax.reduce` that takes a minimum tuple."""
    lt = False
    for x, y in zip(reversed(a), reversed(b)):
        lt = (x < y) | ((x == y) & lt)
    return tuple(jnp.where(lt, x, y) for x, y in zip(a, b))


def _lane_tops(lanes: Sequence) -> tuple:
    """That reduce's initial values: every lane's largest."""
    return tuple(jnp.asarray(_max_of(lane.dtype), lane.dtype)
                 for lane in lanes)


def _block_minima(lanes: Sequence) -> list:
    """Per block of the (stacked, M, rows) views (`topn_head`), the
    lexicographic minimum of the lane tuple: ONE variadic reduction
    under the lexicographic comparator, which the lanes' producers fuse
    into, so every source column is read once and no lane is written
    out.  (On a v5e it streams at the HBM roofline; a cascade of plain
    masked minima, lane by lane, re-reads its sources and was 1.5x
    slower — PERF.md section 6, PR 24.)"""
    return list(lax.reduce(tuple(lanes), _lane_tops(lanes), _lex_smaller,
                           (0, 2)))


def topn_head(lanes_of, n: int, k: int, block_len: int,
              stacked: int = 1):
    """The first `k` rows of the total order the comparator lanes define
    over `n` rows, found exactly without sorting them all.  Returns the
    head `k` of every sorted lane (the last being the rows' indices) and
    `pick`, which takes those `k` rows out of any (n,) source array.

    `lanes_of(take, rows)` builds the lanes (`_topn_lanes`) over the rows
    `take` picks out of any (n,) source array; `rows` are their indices,
    the last lane.  The rows are viewed as M = n / block_len blocks.
    Which rows share a block is free (the index is in the tuple), so a
    block is block_len / stacked consecutive rows of every one of the
    `stacked` runs the flat arrays consist of (DeviceBatch.stacked): on
    the TPU, stacked (S, C) shards lie tile by tile across the S runs,
    that (stacked, M, rows) view is the array's own byte order and moves
    nothing, where M blocks of consecutive rows cost a relayout pass
    over every column (9 of 10.6 ms a statement at 2^26 rows, PERF.md
    section 6).

    1. per block, the minimum of the lane tuple (unique: the index is
       its last lane): M tuples;
    2. those M tuples sorted; the first min(k, M) name the kept blocks;
    3. the kept blocks' rows gathered from the SOURCE arrays, their
       lanes rebuilt, sorted; the head k is the answer.

    A block not kept has k blocks before it, each holding a row smaller
    than every row of its own, so none of its rows is among the first k:
    the kept blocks hold the whole answer and the full comparator orders
    them — ties, NULLs, dead rows and extreme keys exactly as sorting
    everything would.  `block_len == n` is that full sort."""
    idx_dtype = jnp.int32 if n < 2 ** 31 else jnp.int64
    rows = jnp.arange(n, dtype=idx_dtype)
    if block_len == n:
        with jax.named_scope("sort"):
            heads = [lane[:k] for lane in
                     _sort_lanes(lanes_of(lambda a: a, rows))]
        return heads, lambda a: a[heads[-1]]
    if n % stacked or block_len % stacked:
        stacked = 1
    m, run, per_run = n // block_len, n // stacked, block_len // stacked

    def view(a):
        return a.reshape(stacked, m, per_run)
    with jax.named_scope("block_min"):
        *_, first = _sort_lanes(_block_minima(
            [view(lane) for lane in lanes_of(lambda a: a, rows)]))
    blocks = first[:min(k, m)] % run // per_run

    def take(a):
        return view(a)[:, blocks].reshape(-1)
    # the kept rows' indices, in take's order, without gathering an iota
    rows = (jnp.arange(stacked, dtype=idx_dtype)[:, None, None] * run
            + blocks[None, :, None] * per_run
            + jnp.arange(per_run, dtype=idx_dtype)[None, None, :]).reshape(-1)
    with jax.named_scope("sort"):
        heads = [lane[:k] for lane in _sort_lanes(lanes_of(take, rows))]
    # the head is picked out of the gathered blocks, at the position
    # worked back from its row index: indexing the flat source by row
    # index would relayout every stacked column (and a position lane
    # riding the sort as payload costs 12 s more compile)
    in_run = heads[-1] % run
    kept = jnp.argmax(
        (in_run // per_run)[:, None] == blocks[None, :], axis=1)
    pos = (heads[-1] // run * len(blocks) + kept) * per_run \
        + in_run % per_run
    return heads, lambda a: take(a)[pos]


def _exec_topn(node: D.TopN, batch: DeviceBatch, ev: Evaluator) -> DeviceBatch:
    """Per-shard TopN: the comparator lanes of `_topn_lanes`, the first
    `limit` rows of their order by `topn_head` (block-minimum pruning,
    block length from `dag.topn_block_len`; one block — the full
    multi-key sort — where pruning cannot pay), then a head-k gather."""
    n = len(batch.cols[0][0])
    sel = _sel_array(batch.sel, n)
    k = min(node.limit, n)
    cols = [(_ensure_array(cv, n), cm) for cv, cm in batch.cols]

    def lanes_of(take, rows):
        return _topn_lanes(
            node, [(take(cv), cm if cm is True else take(cm))
                   for cv, cm in cols], take(sel), rows, ev)

    block_len = D.topn_block_len(n, k)
    (dead, *_), pick = topn_head(lanes_of, n, k, block_len, batch.stacked)
    batch.facts["topn_blocks"] = n // block_len if n else 1
    # dead rows sort last, so the head's live rows are its first ones
    return replace(
        batch, cols=[(pick(cv), (pick(cm) if cm is not True else True))
                     for cv, cm in cols], sel=dead == 0, stacked=1)


# --------------------------------------------------------------------- #
# Program build + cache
# --------------------------------------------------------------------- #

class CopProgram:
    """A compiled coprocessor program for one DAG shape.

    kind == 'agg': __call__(scan_cols, row_count) -> partial-state pytree
    kind == 'rows': -> (cols, count) compacted to `row_capacity`
    """

    def __init__(self, dag_root: D.CopNode, row_capacity: int = 0):
        self.root = dag_root
        self.row_capacity = row_capacity
        self.agg = _find_agg(dag_root)
        self.kind = "agg" if self.agg is not None else "rows"
        # programs containing an expanding join return an extras dict
        # (true join output size) after the result, for the regrow loop
        self.has_extras = D.has_extras(dag_root)
        from ..analysis.compilekey import named_jit
        self._fn = named_jit(self._trace, "local", dag_root)

    def _trace(self, scan_cols, row_count, aux_cols=()):
        # At the jit boundary "all valid" is encoded as None (a pytree node,
        # hence static structure); inside the trace it becomes the literal
        # True the Evaluator's fast paths key on.
        scan_cols = [(v, True if m is None else m) for v, m in scan_cols]
        aux_cols = tuple(
            tuple((v, True if m is None else m) for v, m in grp)
            for grp in aux_cols)
        # single-device programs run on the process default backend
        ev = Evaluator(jnp, platform=jax.default_backend())
        if self.agg is not None:
            states, batch = agg_states(self.agg, scan_cols, row_count, ev,
                                       aux_cols)
            return (states, batch.extras) if self.has_extras else states
        batch = _exec_node(self.root, scan_cols, row_count, ev, aux_cols)
        cols, cnt = compact(batch, self.row_capacity)
        return (cols, cnt, batch.extras) if self.has_extras else (cols, cnt)

    def __call__(self, scan_cols, row_count, aux_cols=()):
        return self._fn(scan_cols, row_count, aux_cols)


def _find_agg(node: D.CopNode) -> Optional[D.Aggregation]:
    """The pushdown DAG holds at most one Aggregation, as the root
    (mirrors tipb: agg is the final pushed executor)."""
    if isinstance(node, D.Aggregation):
        return node
    return None


@functools.lru_cache(maxsize=256)
def get_program(dag_root: D.CopNode, row_capacity: int = 0) -> CopProgram:
    """jit-program cache keyed on (dag digest, capacity) — the analog of the
    coprocessor cache + plan-digest jit cache (SURVEY.md §A.6)."""
    return CopProgram(dag_root, row_capacity)


__all__ = ["DeviceBatch", "CopProgram", "get_program", "compact",
           "compact_root", "group_keyinfo"]
