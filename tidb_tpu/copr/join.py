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

from typing import Sequence

import jax.numpy as jnp
from jax import lax

from .dag import COMPACT_COLUMNS
from .joinbuild import APART, KEY_ITSELF, UNREAD


def _compare_narrow(kv, ref) -> bool:
    """Whether a probe key and a build-side array can meet at int32: both
    are read at 32 signed bits or fewer (an int64 lane is two emulated
    32-bit lanes on a TPU)."""
    return ref.dtype == jnp.int32 and kv.dtype in (
        jnp.int8, jnp.int16, jnp.int32)


def direct_lookup(kv, grp, packing, window: int = 0, live=True,
                  stacked: int = 1, offset=None):
    """Probe a direct-addressed build side (dag.LookupJoin `dense`).
    kv: probe keys; grp: the aux group [(array, mask | True)].  Returns
    (matched, [(value, valid | True) per build column], miss); a
    column's value is meaningless where `matched` is False.

    `key - base` wraps for a key far outside the range; read unsigned it
    is then never below `span` (base + span fits the signed width both
    operands share, so a wrapped difference is at least span), so one
    unsigned compare is the whole bounds check.

    `window` (dag.LookupJoin `probe_window`; 0: one gather a table, an
    index a slot): the tables are read by windows (`_window_reader`),
    `live` the rows whose result is read, `stacked` the runs the slots
    consist of; `miss` then counts the live rows whose offset fell
    outside their block's window and whose result is therefore wrong
    (the dispatcher reruns the statement at 0); else it is 0.

    `offset`: the keys' slots where they are not `key - base` (a
    sharded side's, `parallel/exchange.key_places`), checked against
    the span alike."""
    n_words, pbit, layout = packing
    meta = grp[0][0]
    kt, ut = (jnp.int32, jnp.uint32) \
        if _compare_narrow(kv if offset is None else offset, meta) \
        else (jnp.int64, jnp.uint64)
    d = kv.astype(kt) - meta[0].astype(kt) if offset is None \
        else offset.astype(kt)
    matched = lax.bitcast_convert_type(d, ut) < meta[1].astype(ut)
    idx = jnp.where(matched, d, 0).astype(jnp.int32)  # valueflow: ok - a matched row's offset is below span < 2^31
    miss = jnp.zeros((), jnp.int32)
    slots = grp[2][0].shape[0] if len(grp) > 2 else 0
    if window and window + COMPACT_COLUMNS <= slots \
            and not idx.shape[0] % COMPACT_COLUMNS:
        read, miss = _window_reader(idx, matched & live, slots, window,
                                    stacked)
    else:
        def read(table):
            return table.at[idx].get(mode="promise_in_bounds")
    words = [read(grp[2 + w][0]) for w in range(n_words)]
    if pbit >= 0:
        matched = matched & ((words[0] >> pbit) & 1).astype(bool)
    mins = grp[1][0]
    apart = iter(grp[2 + n_words:])
    out = []
    for j, (w, shift, bits, vbit, wide) in enumerate(layout):
        if w == KEY_ITSELF:
            out.append((kv.astype(jnp.int64 if wide else jnp.int32), True))
            continue
        if w == UNREAD:         # nothing reads it: any value will do
            out.append((jnp.zeros(kv.shape, bool), True))
            continue
        if w == APART:
            tv, tm = next(apart)
            out.append((read(tv), True if tm is True else read(tm)))
            continue
        vt = jnp.int64 if wide else jnp.int32
        v = ((words[w] >> shift) & ((1 << bits) - 1)).astype(vt) \
            + mins[j].astype(vt)
        out.append((v, True if vbit < 0
                    else ((words[w] >> vbit) & 1).astype(bool)))
    return matched, out, miss


def _window_reader(idx, ok, slots: int, window: int, stacked: int):
    """(read, miss) for a probe whose offsets `idx` never decrease along
    a run of slots (the probe key is stored in key order: ANALYZE's
    `ColumnStats.ordered`, a hint): `read(table)` is `table[idx]` at
    every row of `ok` whose offset lies inside its block's window, and
    `miss` counts the rows of `ok` where it does not (their values are
    wrong: zero).

    The slots are viewed as blocks of COMPACT_COLUMNS in the order they
    lie in memory (`_tile_order`: a block never straddles two stacked
    runs), and a block of consecutive rows in key order reads a
    contiguous stretch of the table.  The table is viewed as rows of
    COMPACT_COLUMNS words; the row that holds the block's least offset
    over its `ok` rows and the `window / COMPACT_COLUMNS` rows after it
    (clamped to the table) are the block's window, which holds any
    stretch of `window` + 1 slots however it is aligned; ONE gather of
    whole rows fetches the windows, and which word of its window a slot
    takes is arithmetic: `sum_w select(idx - start == w, window[w], 0)`,
    one reduce whose terms are all zero but one, exact at any width.
    (On a v5e a gather of single words costs 7.1 ns an index: 2^23 of
    them 59.8 ms; 196,608 whole rows 0.36 and 384 selects a slot 6.8;
    windows fetched as slices of any alignment become a `while` of
    65,536 steps, 119 ms: PERF.md sections 5 and 6, PR 34.)"""
    cols = COMPACT_COLUMNS
    n, k = idx.shape[0], window // cols + 1
    at = _tile_order(idx, stacked).reshape(n // cols, cols)
    okb = _tile_order(ok, stacked).reshape(n // cols, cols)
    rows = -(-slots // cols)
    row0 = jnp.clip(jnp.min(jnp.where(okb, at, slots), axis=1) // cols,
                    0, rows - k)
    local = at - (row0 * cols)[:, None]
    miss = jnp.sum(okb & ((local < 0) | (local >= k * cols)),
                   dtype=jnp.int32)
    fetch = row0[None, :] + jnp.arange(k, dtype=jnp.int32)[:, None]
    # (k, 1, w, 1) against (1, blocks, 1, slot): a slot's word is the one
    # whose place in the window is the slot's offset from its start
    place = lax.broadcasted_iota(jnp.int32, (k, 1, cols, 1), 0) * cols \
        + lax.broadcasted_iota(jnp.int32, (k, 1, cols, 1), 2)
    pick = local[None, :, None, :] == place

    def read(table):
        bits = _as_bits(table)
        if slots % cols:
            bits = jnp.pad(bits, (0, rows * cols - slots))
        fetched = bits.reshape(rows, cols).at[fetch].get(
            mode="promise_in_bounds")
        got = jnp.sum(jnp.where(pick, fetched[:, :, :, None],
                                jnp.zeros((), bits.dtype)),
                      axis=(0, 2), dtype=bits.dtype)
        got = _run_order(got.reshape(n), stacked)
        return got.astype(bool) if table.dtype == bool \
            else got.view(table.dtype)
    return read, miss


def _as_bits(x):
    """`x` as unsigned integers of its own width (a bool as 0 / 1): the
    form in which a sum of zeros and one value is that value, bit for
    bit (a float's -0.0 and NaNs too)."""
    if x.dtype == bool:
        return x.astype(jnp.uint8)  # valueflow: ok - bool lane, [0, 1]
    return x.view(jnp.dtype(f"uint{8 * x.dtype.itemsize}"))


def _tile_order(x, stacked: int):
    """The flat slots `x` of `stacked` equal runs in the order they lie
    in a TPU's memory: tile by tile across the runs (a (runs, slots)
    array is tiled (8, 128)), not run by run.  There the view is the
    array's own bytes; read run by run instead, eight stacked shards
    cost a relayout of every array so read, 0.13 ms and 0.2 MB of a
    program's executable each (PERF.md section 6, PR 28).  Which slots a
    column of `live_rows` holds does not change."""
    n = x.shape[0]
    if stacked == 1 or n % (stacked * COMPACT_COLUMNS):
        return x
    return x.reshape(stacked, n // stacked // COMPACT_COLUMNS,
                     COMPACT_COLUMNS).transpose(1, 0, 2).reshape(n)


def _run_order(x, stacked: int):
    """`_tile_order`'s inverse: slots in memory order back run by run."""
    n = x.shape[0]
    if stacked == 1 or n % (stacked * COMPACT_COLUMNS):
        return x
    return x.reshape(n // stacked // COMPACT_COLUMNS, stacked,
                     COMPACT_COLUMNS).transpose(1, 0, 2).reshape(n)


def _slot_places(n: int, stacked: int, wt):
    """Every slot's place (its index among the n slots in `_tile_order`)
    as `wt`, slot by slot in the order the callers' masks come in (run by
    run), computed where the slot is: the words made of it, not a
    narrower mask, are what `_tile_order` then views for free."""
    cols = COMPACT_COLUMNS
    if stacked == 1 or n % (stacked * cols):
        stacked = 1
    run, tile, lane = (lax.broadcasted_iota(
        wt, (stacked, n // stacked // cols, cols), d) for d in range(3))
    return ((tile * stacked + run) * cols + lane).reshape(n)


def live_rows(sel, capacity: int, stacked: int = 1):
    """(rows, ok, need): `capacity` slots that hold the place of every
    live row of `sel` (`ok`: the slot holds one; the others hold places
    of dead rows, in bounds), provided `need` <= `capacity`; `need` is
    the capacity this selection takes, at least its live count.  Where
    `need` exceeds `capacity` live rows are missing (the caller reports
    it and the statement is rerun uncompacted).  A place is an index
    into the slots in `_tile_order`, which `gather_rows` reads.

    The n slots are viewed as COMPACT_COLUMNS interleaved columns (slot
    i in column i mod COMPACT_COLUMNS, so a run of live rows spreads
    over all of them) and every column is sorted on its own, ONE
    single-lane unstable `lax.sort` along the long axis of `place | dead
    bit` words: a column's live rows come first, and its first
    capacity / COMPACT_COLUMNS slots are kept.  The kept slots are in no
    order between columns: only a consumer that does not read the order
    of its rows (an aggregation) may sit above.  `need` is the fullest
    column's live rows times the columns.

    `stacked`: the equal runs the flat slots consist of
    (exec.DeviceBatch.stacked).

    (On a v5e, 2^23 slots, one row in 84 live, four probe columns: this
    compaction with its gather 4.1 ms; with one sort of all slots, which
    also keeps the row order, 7.6 ms; a variadic sort that carries the
    columns 37 ms and a minute to compile; a scatter (`nonzero`) 751 ms:
    PERF.md section 6, PR 28.)"""
    n = sel.shape[0]
    cols = COMPACT_COLUMNS
    assert n % cols == 0 and capacity % cols == 0, (n, capacity)
    bit = max(n - 1, 1).bit_length()
    wt = jnp.int32 if bit < 31 else jnp.int64
    places = _slot_places(n, stacked, wt)
    words = _tile_order(jnp.where(sel, places, places | (1 << bit)),
                        stacked).reshape(n // cols, cols)
    # the barrier keeps the flat form: without it XLA moves the reshape
    # below the masks that follow and pays three relayouts for it
    top = lax.optimization_barrier(lax.sort(
        words, dimension=0, is_stable=False)[:capacity // cols].reshape(-1))
    need = jnp.max(jnp.sum((words >> bit) == 0, axis=0,
                           dtype=jnp.int32)) * cols
    return top & ((1 << bit) - 1), (top >> bit) == 0, need


def _word_layout(cols: Sequence) -> tuple:
    """How the columns pack into 32-bit words (`gather_rows`): (placed,
    words, apart).  `placed`: (column, part, bits, word, shift) a field,
    part "v" the value (a 64-bit one as "lo" and "hi"), "m" its validity
    bit; first fit, widest first.  `apart`: columns of a dtype with no
    32-bit view, gathered each on its own."""
    fields, apart = [], []
    for i, (v, m) in enumerate(cols):
        if not (v.dtype == bool or jnp.issubdtype(v.dtype, jnp.integer)
                or v.dtype == jnp.float32):
            apart.append(i)
            continue
        if v.dtype == bool:
            fields.append((i, "v", 1))
        elif v.dtype.itemsize == 8:
            fields += [(i, "lo", 32), (i, "hi", 32)]
        else:
            fields.append((i, "v", 8 * v.dtype.itemsize))
        if m is not True:
            fields.append((i, "m", 1))
    placed, free = [], []           # free bits a word
    for i, part, bits in sorted(fields, key=lambda f: -f[2]):
        w = next((k for k, left in enumerate(free) if left >= bits), None)
        if w is None:
            free.append(32)
            w = len(free) - 1
        placed.append((i, part, bits, w, 32 - free[w]))
        free[w] -= bits
    return placed, len(free), apart


def _field_bits(v, m, part):
    """One field's bits as uint32, zero-extended."""
    if part == "m":
        return m.astype(jnp.uint32)  # valueflow: ok - bool lane, [0, 1]
    if part == "hi":
        return (v.astype(jnp.int64) >> 32).astype(jnp.uint32)  # valueflow: ok - the wrap IS the field: a word's 32 bits
    if v.dtype == jnp.float32:
        return lax.bitcast_convert_type(v, jnp.uint32)
    u = v.astype(jnp.uint32)  # valueflow: ok - the wrap IS the field: the value's low 32 bits
    bits = 1 if v.dtype == bool else 8 * v.dtype.itemsize
    return u if bits >= 32 else u & jnp.uint32((1 << bits) - 1)


def pack_rows(cols: Sequence) -> tuple:
    """`cols` [(value, mask | True)] as (words, apart, unpack): as few
    uint32 words a row as their dtypes take (`_word_layout`; a validity
    mask is one bit); `apart`, the columns with no 32-bit view; and
    `unpack(words, apart_col)`, which reads [(value, mask | True)] back
    out of the words, wherever their rows have gone since (a gather, a
    sort), with `apart_col(i)` giving column i of `apart` there."""
    placed, n_words, apart = _word_layout(cols)
    words = [jnp.uint32(0)] * n_words
    for i, part, _bits, w, shift in placed:
        words[w] = words[w] | (_field_bits(*cols[i], part) << shift)

    def unpack(got, apart_col=None) -> list:
        parts = {}
        for i, part, bits, w, shift in placed:
            f = got[w] >> shift
            parts[i, part] = f if bits == 32 \
                else f & jnp.uint32((1 << bits) - 1)
        out = []
        for i, (v, m) in enumerate(cols):
            if i in apart:
                out.append(apart_col(i))
                continue
            if v.dtype.itemsize == 8:
                gv = ((parts[i, "hi"].astype(jnp.int64) << 32)
                      | parts[i, "lo"].astype(jnp.int64)).astype(v.dtype)
            elif v.dtype == jnp.float32:
                gv = lax.bitcast_convert_type(parts[i, "v"], jnp.float32)
            else:
                gv = parts[i, "v"].astype(v.dtype)      # wraps: sign restored
            out.append((gv, True if m is True
                        else parts[i, "m"].astype(bool)))
        return out
    return words, apart, unpack


def gather_rows(cols: Sequence, rows, stacked: int = 1) -> list:
    """`cols` [(value, mask | True)] at the places `rows` (`live_rows`:
    indices into the slots in `_tile_order`, in bounds).  The columns
    are packed into as few uint32 words a row as their dtypes take
    (`pack_rows`), the words stacked (n, W) and gathered ONCE: a gather
    on a TPU costs its indices, not its bytes.  (Every column of the
    probe child is packed: the planner has pruned the scan to the
    columns the statement reads, and Q14's and Q19's programs are the
    same bytes with the unread ones left out.)"""
    def at(x):
        return _tile_order(x, stacked).at[rows].get(
            mode="promise_in_bounds")
    words, _apart, unpack = pack_rows(cols)
    got = []
    if len(words) == 1:
        got = [at(words[0])]
    elif words:
        hit = jnp.stack([_tile_order(w, stacked) for w in words],
                        axis=1).at[rows].get(mode="promise_in_bounds")
        got = [hit[:, w] for w in range(len(words))]
    return unpack(got, lambda i: (
        at(cols[i][0]), True if cols[i][1] is True else at(cols[i][1])))


def compact_rows(cols: Sequence, sel, capacity: int, stacked: int = 1):
    """The one compaction of live rows on a device where a scatter costs
    90 ns an update: (`cols` at `capacity` slots that hold every live
    row of `sel` in no order, which of the slots hold one, the capacity
    the live rows take).  One column sort (`live_rows`), one stacked
    gather (`gather_rows`).  A lookup join's probe rows
    (exec._compact_probe) and a rows-returning program's result
    (exec.compact_root) both leave through it."""
    rows, ok, need = live_rows(sel, capacity, stacked)
    return gather_rows(cols, rows, stacked), ok, need


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


__all__ = ["direct_lookup", "sorted_lookup", "live_rows", "gather_rows",
           "pack_rows", "compact_rows",
           "match_ranges", "expand_slots", "gather_expand"]
