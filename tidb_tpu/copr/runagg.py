"""The TPU's lowering of a SORT aggregation: sort packed records, reduce
the runs by scans, compact the run ends.  No gather and no scatter has
as many indices as the batch has slots.

Reference analog: the reference's answer to a high-NDV GROUP BY is the
parallel HashAgg (pkg/executor/aggregate/agg_hash_executor.go:94); a TPU
has no fast scatter (0.75 s for 2^23 elements on a v5e) and an XLA
gather costs 7-10 ns an index whatever the table, so what
`exec._agg_sort_states` does a slot (gather every column through the
sort's permutation, scatter every state into the table) is seconds a
statement.  Here the group key AND what the aggregates read travel with
the sort, as one record a row:

1. *The record.*  Per row, most significant first: a dead bit, per key
   an optional NULL bit and the key, per aggregate an optional NULL bit
   and the SUM's argument.  In the **exact** form (`pack_words` 1 or 2:
   `dag.Aggregation`) every value is stored as its distance from the
   least the live rows hold, in as many bits as the largest distance
   takes: the layout is computed on the device, only the number of
   32-bit words is static.  A record that does not fit says so
   (`__bits__`) and the dispatcher reruns the statement wider.  In the
   **wide** form (`pack_words` 0) the layout is static, from the
   dtypes: the sort key is 31 bits of a hash of the key tuple
   (`key_hash`) and the keys ride as payload.  A **dependent**
   key (`dag.Aggregation.dependent`: a function of the other keys, as
   the columns a unique lookup join brings are of its probe key) is in
   neither form part of the key: every row of a run holds the same
   value, so it decides no run.  It rides the sort as payload at its
   dtype's width (`join.pack_rows`), NULL bit included, and is read at
   the run's end like any key.  (Not inside the exact record's words,
   whose layout the device works out: whether it has room there is not
   known when the sort's lanes are counted.)
2. *One sort* by the record's first word (`lax.sort`, unstable, the
   other words its payload: a second key lane costs XLA:TPU half as
   much compile time again): equal keys become runs, dead rows sort
   last.  So the exact form's key part has to fit the first word.  A run boundary is where the key
   part changes; in the wide form where the hash OR any key changes, so
   two keys that collide in the hash become several partial groups of
   each, which the host merges by true key equality, never one.
3. *Scans.*  One running maximum (int32) says where each slot's run
   begins; one prefix sum a summand (a distance, a NULL bit: none is
   negative, none wraps).  The prefix sums are made in two levels
   (`ops/limbscan.limb_cumsum`): a summand's static bit bound says how
   many 8-bit limbs it is cut into, the limbs of every summand are
   prefix-summed inside blocks of 128 slots by a batched dot with a
   triangle of ones on the MXU (two limbs an output), and an int64 scan
   over the n / 128 block totals carries the blocks: exact, and 0.5 to
   1.5 ms a summand of four or five limbs at 2^23 slots where
   `jnp.cumsum` at int64 over every slot takes XLA:TPU 8.9.  COUNT(*)
   is the run's length.  (A running maximum at int64, which would carry
   the prefix at a run's start forward, takes XLA:TPU 90 s to compile;
   with the prefix sum beside it the compiler died.)
4. *The table.*  The run ends are compacted to the capacity's slots by
   `join.live_rows` (128 interleaved columns, each sorted on its own).
   A run's total is its prefix sum at its end less the one before its
   first slot: two stacked gathers of `group_capacity` indices, not n.
   `__ngroups__` is the distinct count, or what the compaction would
   have needed where that is more than it had.
5. *TopN* (`dag.Aggregation.topn`, exact form only: there no group is
   in two slots): the table's first `limit` groups by the statement's
   ORDER BY (`_first_groups`), so ten rows cross to the host and not
   the table.

State layout, `__rows__`, NULL keys (all NULLs one group), `{hi, lo,
cnt}` SUM words exact past int64: `exec._agg_sort_states`'s, so the host
merge and the regrow loop are untouched but for one thing: a slot holds
a group where `__rows__` > 0, not where it lies before `__ngroups__`.
"""

from __future__ import annotations

from dataclasses import replace
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ..ops.limbscan import limb_count, limb_cumsum
from ..ops.sortkeys import sortable_int64
from ..types import dtypes as dt
from . import dag as D
from .join import COMPACT_COLUMNS, gather_rows, live_rows, _tile_order

K = dt.TypeKind
_U64 = jnp.uint64

# splitmix64 finalizer constants (Steele et al.); numpy scalars so the
# uint64 lanes stay 64-bit regardless of the embedder's x64 default
_GOLDEN = np.uint64(0x9E3779B97F4A7C15)
_MIX1 = np.uint64(0xBF58476D1CE4E5B9)
_MIX2 = np.uint64(0x94D049BB133111EB)


def run_form(agg: D.Aggregation) -> bool:
    """Can this form compute the aggregation?  COUNTs and integer or
    DECIMAL SUMs over at least one group key (a MIN, a MAX or a float
    SUM keeps `_agg_sort_states`)."""
    return bool(agg.group_by) and all(
        a.func == D.AggFunc.COUNT
        or (a.func == D.AggFunc.SUM
            and a.arg.dtype.kind not in (K.FLOAT64, K.FLOAT32))
        for a in agg.aggs)


# --------------------------------------------------------------------- #
# the wide form's sort key
# --------------------------------------------------------------------- #

def _finalize64(z):
    """splitmix64 avalanche: every input bit reaches every output bit."""
    z = (z ^ (z >> np.uint64(30))) * _MIX1
    z = (z ^ (z >> np.uint64(27))) * _MIX2
    return z ^ (z >> np.uint64(31))


def key_hash(keyinfo, n):
    """One uint64 avalanche hash per row over the canonical key tuple
    (`exec.group_keyinfo`).  NULL flags fold in (a NULL key and a zero
    key should land apart; exactness does not depend on it — the run
    boundaries compare the keys themselves)."""
    h = jnp.full((n,), _GOLDEN, jnp.uint64)
    for _vz, m, nullf, code in keyinfo:
        cu = code.astype(jnp.uint64)
        if m is not True:
            cu = cu + nullf.astype(jnp.uint64) * _GOLDEN
        h = _finalize64(h ^ cu)
    return h


# --------------------------------------------------------------------- #
# fields: what a record holds
# --------------------------------------------------------------------- #

def _as_bits(v):
    """(int64 array that is equal where `v` is, its inverse)."""
    dtype = v.dtype
    if jnp.issubdtype(dtype, jnp.floating):
        it = jnp.int32 if dtype.itemsize == 4 else jnp.int64
        return (lax.bitcast_convert_type(v, it).astype(jnp.int64),
                lambda b: lax.bitcast_convert_type(b.astype(it), dtype))  # valueflow: ok - the bits it was made of
    return v.astype(jnp.int64), lambda b: b.astype(dtype)  # valueflow: ok - a value of the dtype, back


def _bit_length(span):
    """Bits the unsigned 64-bit scalar `span` takes (0 for 0)."""
    return (64 - lax.clz(span.astype(_U64))).astype(jnp.int32)  # valueflow: ok - at most 64


def _distance(x, live):
    """`x` (int64) over the rows `live` as (least value, distance from
    it as uint64 with 0 at the other rows, bits the largest takes)."""
    lo = jnp.min(jnp.where(live, x, jnp.iinfo(jnp.int64).max))
    hi = jnp.max(jnp.where(live, x, jnp.iinfo(jnp.int64).min))
    empty = hi < lo
    lo = jnp.where(empty, jnp.int64(0), lo)
    span = jnp.where(empty, jnp.int64(0), hi - lo)   # wraps: read unsigned
    off = jnp.where(live, (x - lo).astype(_U64), _U64(0))
    return lo, off, _bit_length(span)


def _agg_fields(agg, batch, ev, memo, sel, n) -> list:
    """Per aggregate with an argument: (the rows it counts, live and not
    NULL, or None where that is every live row; None for a COUNT or the
    SUM argument's `_distance` over those rows)."""
    from .exec import _ensure_array
    out = []
    for a in agg.aggs:
        if a.arg is None:
            continue
        av, am = ev.eval(a.arg, batch.cols, memo)
        valid = None if am is True else sel & am
        out.append((valid, None if a.func == D.AggFunc.COUNT else _distance(
            _ensure_array(av, n).astype(jnp.int64),
            sel if valid is None else valid)))
    return out


# --------------------------------------------------------------------- #
# the two record forms: (words to sort, what to read back of them)
# --------------------------------------------------------------------- #
# `read(sorted words)` -> (dead, [what a run's rows share], [(key,
# valid | True)], [(valid | None, [summand lanes], least) an aggregate
# with an argument]): a summand is int64, not negative and below 2 to
# the `_summand_bits` of its form.

def _sum_room(n: int) -> int:
    """Bits a distance may take for n of them to add up inside int64."""
    return 63 - max(n - 1, 1).bit_length()


def _summand_bits(words: int, n: int) -> int:
    """The static bound on a SUM's summand lane.  An exact record's
    distance shares its words with a dead bit at least and is rerun
    wider (`__bits__`) past `_sum_room`; the wide form's is a half."""
    return min(32 * words - 1, _sum_room(n)) if words else 32


def _payload_keys(keys, sel):
    """Keys that ride as payload, as columns of `join.pack_rows` (their
    bits at their dtypes' widths).  -> (their inverses, the columns)."""
    backs, cols = [], []
    for vz, m in keys:
        bits, back = _as_bits(vz)
        if vz.dtype.itemsize <= 4:
            bits = bits.astype(jnp.int32)  # valueflow: ok - the value's own 32 bits or fewer
        backs.append(back)
        cols.append((bits, True if m is True else (sel & m)))
    return backs, cols


def _exact_record(keys, aggs, sel, n, words: int, riders=frozenset()):
    """The exact form; the keys `riders` (indexes) ride behind the
    record's words as payload.  -> (words, bits, read): `bits` the
    record takes (more than 32 * `words`, or 65 where the key part
    passes a word or a SUM's distances could pass int64 summed over n
    rows: it did not fit)."""
    from .join import pack_rows
    ut = jnp.uint32 if words == 1 else _U64
    fields = [((~sel).astype(ut), 1)]   # (bits as `ut`, their number)
    key_slots = []
    for j, (vz, m) in enumerate(keys):
        if j in riders:
            continue
        bits, back = _as_bits(vz)
        if m is not True:
            fields.append(((sel & ~m).astype(ut), 1))
        lo, off, w = _distance(bits, sel)
        fields.append((off.astype(ut), w))  # valueflow: ok - a record that does not fit is rerun
        key_slots.append((len(fields) - 1, lo, back, m is not True))
    n_key = len(fields)
    agg_slots = []
    sum_room = _sum_room(n)
    too_wide = jnp.zeros((), bool)
    for valid, dist in aggs:
        at_valid = at_off = lo = None
        if valid is not None:
            fields.append((valid.astype(ut), 1))
            at_valid = len(fields) - 1
        if dist is not None:
            lo, off, w = dist
            too_wide = too_wide | (w > sum_room)
            fields.append((off.astype(ut), w))  # valueflow: ok - a record that does not fit is rerun
            at_off = len(fields) - 1
        agg_slots.append((at_valid, at_off, lo))
    widths = [jnp.asarray(w, jnp.int32) for _f, w in fields]
    total = sum(widths[1:], widths[0])
    # the sort compares the first word alone: the key part has to lie
    # in it, so the record is pushed up against the top
    too_wide = too_wide | (sum(widths[1:n_key], widths[0]) > 32)
    slack = jnp.maximum(32 * words - total, 0)
    rec = jnp.zeros((n,), ut)
    for (f, _w), w in zip(fields, widths):
        rec = (rec << w.astype(ut)) | f
    rec = rec << slack.astype(ut)
    # a field's place: the bits of the fields after it
    below = [slack + total - sum(widths[1:i + 1], widths[0])
             for i in range(len(fields))]
    out_words = [rec] if words == 1 else [
        (rec >> _U64(32)).astype(jnp.uint32), rec.astype(jnp.uint32)]  # valueflow: ok - the record's two words
    backs, carried = _payload_keys(
        [keys[j] for j in sorted(riders)], sel)
    payload, apart, unpack = pack_rows(carried)
    assert not apart, "keys are integers, floats or bits"

    def read(sorted_words):
        r = sorted_words[0] if words == 1 else (
            (sorted_words[0].astype(_U64) << _U64(32))
            | sorted_words[1].astype(_U64))

        def field(i):
            mask = (ut(1) << widths[i].astype(ut)) - ut(1)
            return (r >> below[i].astype(ut)) & mask
        in_record = iter([
            (back(field(at).astype(jnp.int64) + lo),  # valueflow: ok - a distance below 2^63 or the wrap that undoes one
             (field(at - 1) == 0) if nullable else True)
            for at, lo, back, nullable in key_slots])
        behind = iter([(back(bits), valid) for back, (bits, valid)
                       in zip(backs, unpack(sorted_words[words:]))])
        key_out = [next(behind if j in riders else in_record)
                   for j in range(len(keys))]
        agg_out = [(None if av is None else field(av) != 0,
                    [] if ao is None else [field(ao).astype(jnp.int64)],  # valueflow: ok - sum_room bits at most
                    lo) for av, ao, lo in agg_slots]
        return (field(0) != 0, [r >> below[n_key - 1].astype(ut)],
                key_out, agg_out)
    return (out_words + payload,
            jnp.where(too_wide, jnp.int32(65), total), read)


def _wide_record(keys, aggs, sel, hashed, riders=frozenset()):
    """The wide form: a word of hash (of the keys that are not `riders`)
    under a dead bit, then the keys (as their bits) and the aggregates'
    distances in as few words as their dtypes take (`join.pack_rows`).
    -> (words, read)."""
    from .join import pack_rows
    top = jnp.where(sel, (hashed >> _U64(33)).astype(jnp.uint32),  # valueflow: ok - 31 bits are left
                    jnp.uint32(1 << 31))
    backs, cols = _payload_keys(keys, sel)
    agg_slots = []
    for valid, dist in aggs:
        at_valid = at_off = lo = None
        if valid is not None:
            cols.append((valid, True))
            at_valid = len(cols) - 1
        if dist is not None:    # up to 64 bits: summed as two halves
            lo, off, _w = dist
            cols += [(off.astype(jnp.uint32), True),  # valueflow: ok - the low half
                     ((off >> _U64(32)).astype(jnp.uint32), True)]  # valueflow: ok - the high half
            at_off = len(cols) - 2
        agg_slots.append((at_valid, at_off, lo))
    payload, apart, unpack = pack_rows(cols)
    assert not apart, "keys and arguments are integers, floats or bits"

    def read(sorted_words):
        got = unpack(sorted_words[1:])
        shared = [sorted_words[0]]
        for j, (bits, valid) in enumerate(got[:len(keys)]):
            if j not in riders:     # a rider changes with no run
                shared += [bits] if valid is True else [bits, valid]
        key_out = [(back(bits), valid)
                   for back, (bits, valid) in zip(backs, got)]
        agg_out = [(None if av is None else got[av][0],
                    [] if ao is None else [got[ao][0].astype(jnp.int64),
                                           got[ao + 1][0].astype(jnp.int64)],
                    lo) for av, ao, lo in agg_slots]
        return (sorted_words[0] >> 31) != 0, shared, key_out, agg_out
    return [top] + payload, read


# --------------------------------------------------------------------- #
# the lowering
# --------------------------------------------------------------------- #

def agg_run_states(agg: D.Aggregation, batch, ev, memo: dict) -> dict:
    """Per-device partial states of a SORT aggregation in the form this
    module's docstring describes."""
    from .exec import (_ensure_array, _limb_row_fence, _sel_array,
                       group_keyinfo)
    G = agg.group_capacity
    assert G > 0, "SORT aggregation needs group_capacity"
    n0 = len(batch.cols[0][0]) if batch.cols else 0
    _limb_row_fence(n0)
    pad = -n0 % COMPACT_COLUMNS
    stacked = batch.stacked if not pad else 1
    if pad:     # a toy batch: dead rows up to whole columns
        batch = replace(batch, sel=jnp.pad(_sel_array(batch.sel, n0),
                                           (0, pad)), cols=[
            (jnp.pad(_ensure_array(v, n0), (0, pad)),
             True if m is True else jnp.pad(m, (0, pad)))
            for v, m in batch.cols])
    n = n0 + pad
    sel = _sel_array(batch.sel, n)

    # the keys as the other SORT lowering canonicalises them (NULLs
    # zeroed: one group; -0.0 with +0.0)
    keyinfo = group_keyinfo(agg, batch, ev, memo, n)
    keys = [(vz, m) for vz, m, _nullf, _code in keyinfo]
    aggs = _agg_fields(agg, batch, ev, memo, sel, n)
    # the keys the others determine decide no run: payload in both forms
    riders = frozenset(agg.dependent)
    if riders:
        batch.facts["dependent_keys"] = len(riders)
    states: dict[str, Any] = {}
    if agg.pack_words:
        words, bits, read = _exact_record(keys, aggs, sel, n,
                                          agg.pack_words, riders)
        states["__bits__"] = bits.astype(jnp.int64)
    else:
        hashed = key_hash([k for j, k in enumerate(keyinfo)
                           if j not in riders], n)
        words, read = _wide_record(keys, aggs, sel, hashed, riders)
    with jax.named_scope("sort"):
        words = lax.sort(tuple(_tile_order(w, stacked) for w in words),
                         num_keys=1, is_stable=False)
    dead, shared, key_out, agg_out = read(list(words))

    with jax.named_scope("runs"):
        idx = lax.iota(jnp.int32, n)
        start = idx == 0
        for x in shared:
            start = start | (x != jnp.roll(x, 1))
        end = jnp.roll(start, -1) | (idx == n - 1)
        # a slot's run begins at `first`; a run's total of a summand is
        # its prefix sum at the run's end less the one before its first
        # slot.  Both are read at the table's slots alone, below
        first = lax.cummax(jnp.where(start, idx, 0))
        at_end = [(v, valid) for v, valid in key_out] + [(first, True)]
        at = []         # an aggregate: (its count's lane | None, its sums')
        summands = []   # (lane, the bits it stays below)
        bits = _summand_bits(agg.pack_words, n)
        for valid, lanes, _lo in agg_out:
            at.append((None if valid is None else len(summands),
                       len(summands) + (valid is not None), len(lanes)))
            if valid is not None:
                summands.append((valid.astype(jnp.int32), 1))  # valueflow: ok - bool lane, [0, 1]
            summands += [(x, bits) for x in lanes]
        batch.facts["scan_limbs"] = sum(limb_count(b) for _x, b in summands)
        # a count stays at its lane's 32 bits: n is below 2^31
        sums = [c.astype(x.dtype) for c, (x, _b) in zip(  # valueflow: ok - a count of rows or int64 as it was
            limb_cumsum([(x.astype(jnp.int64), b) for x, b in summands]),
            summands)]
        at_end += [(c, True) for c in sums]
        at_first = [(c - x, True) for c, (x, _b) in zip(sums, summands)]
        run_end = end & ~dead
        ngroups = jnp.sum(run_end, dtype=jnp.int64)

    # the table's slots: the capacity in whole columns; every slot where
    # that is a quarter of them or more (a short column overflows on a
    # few run ends more than its share, and n slots cost little then)
    cap = n if 4 * G >= n else -(-G // COMPACT_COLUMNS) * COMPACT_COLUMNS
    with jax.named_scope("group_table"):
        places, ok, need = live_rows(run_end, cap, 1)
        got = gather_rows(at_end, places, 1)
        begun = got[len(keys)][0]
        before = gather_rows(at_first, begun, 1) if at_first else []
        totals = [jnp.where(ok, e[0] - b[0], 0) for e, b
                  in zip(got[len(keys) + 1:], before)]
    states["__ngroups__"] = jnp.where(
        need > cap, jnp.maximum(ngroups, need.astype(jnp.int64)), ngroups)

    rows = jnp.where(ok, places - begun + 1, 0).astype(jnp.int64)
    table: dict[str, Any] = {"__rows__": rows}
    for j in range(len(keys)):
        v, valid = got[j]
        table[f"k{j}"] = {
            "val": jnp.where(ok, v, jnp.zeros((), v.dtype)),
            "valid": ok if valid is True else (ok & valid)}
    it = iter(zip(at, agg_out))
    for i, a in enumerate(agg.aggs):
        if a.arg is None:
            table[f"a{i}"] = {"count": rows}
            continue
        (at_valid, at_lanes, n_lanes), (_v, _lanes, lo) = next(it)
        cnt = rows if at_valid is None \
            else totals[at_valid].astype(jnp.int64)
        if a.func == D.AggFunc.COUNT:
            table[f"a{i}"] = {"count": cnt}
            continue
        # sum = sum of the distances + cnt * least: as the two words,
        # each far inside int64 (distances: below 2^63 in all, or two
        # halves of 32 bits a row; cnt < 2^31)
        sums = totals[at_lanes:at_lanes + n_lanes]
        if n_lanes == 1:
            sums = [sums[0] & 0xFFFFFFFF, sums[0] >> 32]
        table[f"a{i}"] = {"hi": sums[1] + cnt * (lo >> 32),
                          "lo": sums[0] + cnt * (lo & 0xFFFFFFFF),
                          "cnt": cnt}

    topn = agg.topn
    ranked = topn is not None and topn.on_device and bool(agg.pack_words) \
        and topn.limit <= D.GROUP_TOPN_MAX
    if ranked:
        batch.facts["group_topn"] = "device"
        with jax.named_scope("group_topn"):
            table = _first_groups(agg, topn, table, cap)
    states.update(table)
    return states


def _first_groups(agg: D.Aggregation, topn: D.GroupTopN, table: dict,
                  cap: int) -> dict:
    """The table's first `topn.limit` groups in the order `topn.keys`
    give, as a table of that many slots; a slot past the groups there
    are has `__rows__` 0.  `limit` rounds of ONE variadic reduce under
    the lexicographic comparator (`exec._block_minima`'s), each taking
    the least tuple not taken yet: a `lax.sort` of these lanes, two of
    them int64, takes XLA:TPU 40 s to compile, and `limit` is small
    (`dag.GROUP_TOPN_MAX`).

    Lanes as `exec._topn_lanes`: an empty slot last; per key a NULL flag
    (NULLs first ascending, last descending) where there can be one and
    the order-preserving value, `~` for descending; a SUM as its two
    words made canonical (low word below 2^32), which order as the sum
    does whatever its size; last the slot, which makes the order total."""
    from .exec import _lane_tops, _lex_smaller
    k = min(topn.limit, cap)
    lanes = []
    for kind, i, desc in topn.keys:
        def ordered(x, desc=desc):
            return ~x if desc else x

        def nulls(valid, desc=desc):
            flag = jnp.where(valid, 0, 1) if desc else jnp.where(valid, 1, 0)
            return flag.astype(jnp.int32)  # valueflow: ok - literal 0/1 lanes
        if kind == "key":
            st, e = table[f"k{i}"], agg.group_by[i]
            if e.dtype.nullable:
                lanes.append(nulls(st["valid"]))
            v = st["val"]
            if jnp.issubdtype(v.dtype, jnp.signedinteger) \
                    and v.dtype.itemsize <= 4:
                lanes.append(ordered(v.astype(jnp.int32)))  # valueflow: ok - widening only
            else:
                lanes.append(ordered(sortable_int64(
                    jnp, v, e.dtype.is_float, e.dtype.kind == K.UINT64)))
            continue
        st = table[f"a{i}"]
        if "count" in st:
            lanes.append(ordered(st["count"]))
            continue
        if agg.aggs[i].arg.dtype.nullable:
            lanes.append(nulls(st["cnt"] > 0))
        lanes += [ordered(st["hi"] + (st["lo"] >> 32)),
                  ordered(st["lo"] & 0xFFFFFFFF)]
    slot = lax.iota(jnp.int32, cap)
    empty = table["__rows__"] == 0
    tops = _lane_tops([slot] + lanes + [slot])

    def take(taken, _):
        gone = (empty | taken).astype(jnp.int32)  # valueflow: ok - bool lane, [0, 1]
        best = lax.reduce((gone, *lanes, slot), tops, _lex_smaller, (0,))
        return taken | (slot == best[-1]), (best[-1], best[0])
    _, (picked, gone) = lax.scan(take, jnp.zeros((cap,), bool), None,
                                 length=k)

    def head(a):
        return jnp.where(gone == 0, a[picked], jnp.zeros((), a.dtype))
    return jax.tree_util.tree_map(head, table)


__all__ = ["agg_run_states", "run_form"]
