"""Host half of the broadcast lookup join: a build side's keys and columns
-> the aux group the device program takes, in the form the keys allow
(`dag.LookupJoin` describes both forms; `copr/join.py` has the device
half that probes them).

Reference analog: the build phase of the hash join (hash_join_v2.go
build workers).  A hash table is hostile to a TPU; what the build side IS
decides instead: unique keys are addressed directly, `key - base` being
the slot, with every column that fits packed into one int32 word, so
that ONE gather a probe row fetches the whole build row (an XLA gather on
a v5e costs about 7 ns an index whatever the table and whatever it
fetches, so a table is paid in bytes and a probe in gathers: PERF.md,
PR 25); the range may be as sparse as the device's memory lets a table
be (`build_form`: TPC-H's `o_orderkey` is 1.5M keys over a range of 6M,
one market segment's orders before a date 145,000 over the same 6M, 24 MB
a word); keys over a range no table can span are sorted and
binary-searched, about log2(rows) dependent gathers a probe row;
duplicate keys make the join an expanding one.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# direct addressing (dag.LookupJoin `dense`): usable bits of one int32
# word (the sign bit stays clear, so an arithmetic shift is a logical
# one)
WORD_BITS = 31
# the share of one device's memory one build side's tables may take, and
# the memory of a device that does not say (the CPU mesh): a v5e's
DIRECT_MEMORY_SHARE = 64
DEFAULT_DEVICE_BYTES = 16 << 30
# the forms a lookup takes, as a launch reports them (copr/facts.py)
DIRECT, SORTED, EXPANDING = "direct", "sorted", "expanding"
_I32 = np.iinfo(np.int32)
# a build column's `word` in the packing where it rides in no word
APART = -1          # gathered by itself: a float, or wider than a word
KEY_ITSELF = -2     # the build key: a matched row's probe key is it
UNREAD = -3         # nothing above the join reads it: not carried at all


# Word tables lent to `_dense_group`: a table over a sparse range is
# megabytes of zeros (24 MB for TPC-H's order keys at SF1) of which a
# build made anew by every statement writes a few percent, and what a
# fresh one costs is its pages' first touch (20 ms of a 29 ms build on a
# v5e's host: PERF.md, PR 31), not the writes.  A table comes back with
# the slots it was given zeroed again, once its copy is on the device.
_POOL_BYTES = 256 << 20         # kept at most, all lengths together
_pool: dict = {}                # length -> [zeroed int32 arrays]
_pool_mu = threading.Lock()


def table_slots(span: int) -> int:
    """The length of a word table over a range of `span` with holes in
    it: `span` rounded up to five significant bits (at most 1/16 more).
    The table is a program's input, so its length is part of the
    program: the orders of one market segment before one date span
    5,999,777 or 5,999,968 keys by the segment, the date and the data,
    and every one of them would be a compile of its own (85 s for TPC-H
    Q3's); rounded they are all 6,029,312.  The bounds check reads the
    exact range; the slots past it hold no presence bit."""
    step = max(1 << max(span.bit_length() - 5, 0), 1024)
    return -(-span // step) * step


def _borrow_table(slots: int) -> np.ndarray:
    with _pool_mu:
        free = _pool.get(slots)
        if free:
            return free.pop()
    return np.zeros(slots, np.int32)


def _return_table(table: np.ndarray, pos) -> None:
    """Hand back a table whose slots `pos` were written; the caller has
    seen its copy arrive on the device."""
    table[pos] = 0
    with _pool_mu:
        held = sum(size * 4 * len(free) for size, free in _pool.items())
        if held + table.nbytes <= _POOL_BYTES:
            _pool.setdefault(len(table), []).append(table)


@dataclass
class BuildSide:
    """One broadcast-join build side as the device program takes it: the
    aux group, and the strategy fields `dag.LookupJoin` has to carry for
    a program traced against that group."""
    aux: tuple
    rows: int
    unique: bool
    n_unique: int
    dense: bool = False
    packing: tuple = ()
    # the group has a leading device axis and is sharded over the mesh:
    # each device holds the table of the keys it owns (`sharded_build`,
    # `parallel/shuffle.ShardedTableProgram`)
    sharded: bool = False

    @property
    def avg_dup(self) -> float:
        return self.rows / max(self.n_unique, 1)

    @property
    def form(self) -> str:
        return DIRECT if self.dense else SORTED if self.unique else EXPANDING

    @property
    def slots(self) -> int:
        """The length of a direct-addressed side's word tables (its key
        range, rounded: `table_slots`); the rows of another form."""
        return int(self.aux[2][0].shape[-1]) \
            if self.dense and len(self.aux) > 2 else self.rows


def _fits_i32(lo: int, hi: int) -> bool:
    return _I32.min <= lo and hi <= _I32.max


def build_form(rows: int, span: int, columns: int,
               device_bytes: int = DEFAULT_DEVICE_BYTES) -> str:
    """The form the build side of a unique lookup takes: DIRECT or
    SORTED.  Pure: `rows` unique keys over a range of `span`, `columns`
    build columns beside the key, a device of `device_bytes`.

    What each form costs on a TPU: a direct-addressed table its bytes
    (a slot is 4 bytes a word; a column takes a word at most, and is
    carried apart where it does not pack) and a probe ONE gather a word;
    the sorted form next to no bytes and a probe a binary search, about
    log2(rows) gathers that wait for each other, every one the price of
    the direct form's one (2^23 probes in 2^17 keys: seconds against
    59 ms).  So a table is made wherever one can be: its bytes, were
    every column to take a word of its own, within a
    1/DIRECT_MEMORY_SHARE of the device's memory (256 MB of a v5e's
    16 GB: 67M slots of one word), and its slots indexed by an int32.
    How sparse the range is does not enter: the sorted form is for
    ranges no table can span."""
    del rows                    # a table costs its range, not its keys
    words = max(columns, 1)     # the presence bit rides in a word
    if span < 1 << 31 and 4 * span * words \
            <= device_bytes // DIRECT_MEMORY_SHARE:
        return DIRECT
    return SORTED


def prepare_build(keys: np.ndarray, cols: list, dense_ok: bool = True,
                  key_col: int = -1,
                  device_bytes: int = DEFAULT_DEVICE_BYTES,
                  read=None) -> BuildSide:
    """Host half of the lookup join: build keys (int64, NULLs already
    dropped, at least one) and the row-aligned build columns [(data,
    validity)] -> the aux group in the form the keys allow.

    Unique keys over a range a table can span (`build_form`) are
    addressed directly (`_dense_group`); anything else is sorted and
    binary-searched, and duplicate keys make the join an expanding one
    (`unique` False: the caller switches the DAG with to_multimatch).
    Keys that fit int32 go up as int32: the program compares at that
    width where the probe key is read as narrowly, and widens them where
    it is not (an int64 lane is two emulated 32-bit lanes on a TPU).
    `dense_ok` False keeps the sorted form (semi/anti joins read no
    build column).  `key_col`: which of `cols` the keys were taken from,
    if any: where its values ARE the keys a direct-addressed side does
    not carry it, the probe key of a matched row is the same number.
    `read`: a bool a column, whether the program above the join reads it
    (dag.build_columns_read; None: all): a direct-addressed side carries
    no bit of a column nothing reads, so a join's result that comes with
    its own join keys still fits one word."""
    n = len(keys)
    lo, hi = int(keys.min()), int(keys.max())
    span = hi - lo + 1
    read = tuple(read) if read is not None else (True,) * len(cols)
    carried = sum(r for j, r in enumerate(read) if j != key_col)
    if dense_ok and build_form(n, span, carried, device_bytes) == DIRECT:
        side = _dense_group((keys - lo).astype(np.intp), lo, span, cols,
                            key_col, keys, read)
        if side is not None:        # None: a key comes twice
            return side
    n_unique = len(np.unique(keys))
    kdt = np.int32 if _fits_i32(lo, hi) else np.int64
    order = np.argsort(keys, kind="stable")
    aux = [(jnp.asarray(keys[order].astype(kdt)), None),
           (jnp.asarray(np.arange(n, dtype=np.int32)), None)]
    for data, valid in cols:
        aux.append((jnp.asarray(data[order]),
                    None if valid.all() else jnp.asarray(valid[order])))
    return BuildSide(tuple(aux), n, n_unique == n, n_unique)


def _pack_plan(cols: list, key_col: int, keys, read: tuple, room: list,
               key_fits_i32: bool, ranges=None):
    """How the build columns [(data, validity)] pack into int32 words
    (dag.LookupJoin has the layout): (layout, mins, room).  `room`: the
    free bits of the words there are already (a presence bit's word);
    first fit.  `ranges`: (least, largest, all valid) a column where the
    rows are not at hand (a table a device makes: `table_layout`), else
    read from the data."""
    layout, mins = [], []
    for j, (data, valid) in enumerate(cols):
        if j == key_col and (ranges is not None or (
                data.dtype.kind in "iu" and valid.all()
                and np.array_equal(data, keys))):
            layout.append((KEY_ITSELF, 0, 0, -1, not key_fits_i32))
            mins.append(0)
            continue
        if not read[j]:
            layout.append((UNREAD, 0, 0, -1, False))
            mins.append(0)
            continue
        if ranges is not None:
            vmin, vmax, all_valid = ranges[j]
            packable = True
        else:
            all_valid = bool(valid.all())
            packable = data.dtype.kind in "ib" or (
                data.dtype.kind == "u" and data.dtype.itemsize < 8)
            vmin = vmax = 0
            if packable and valid.any():
                live = data if all_valid else data[valid]
                vmin, vmax = int(live.min()), int(live.max())
        bits = (vmax - vmin).bit_length()
        need = bits + (0 if all_valid else 1)
        if not packable or need > WORD_BITS:
            layout.append((APART, 0, 0, -1, True))
            mins.append(0)
            continue
        w = next((i for i, r in enumerate(room) if r >= need), len(room))
        if w == len(room):
            room.append(WORD_BITS)
        shift = WORD_BITS - room[w]
        room[w] -= need
        vbit = -1 if all_valid else shift + bits
        layout.append((w, shift, bits, vbit, not _fits_i32(vmin, vmax)))
        mins.append(vmin)
    return layout, mins, room


def _row_words(layout, mins, cols: list, n_words: int, pbit: int,
               n: int) -> list:
    """A build row's words (n values a word), before they are scattered
    over the key range."""
    row_words = [np.zeros(n, np.int64) for _ in range(n_words)]
    if pbit >= 0:
        row_words[0][:] = 1
    for (w, shift, bits, vbit, _wide), vmin, (data, valid) in zip(
            layout, mins, cols):
        if w < 0:
            continue
        if bits:
            v = data.astype(np.int64) - vmin
            row_words[w] |= (v if vbit < 0 else np.where(valid, v, 0)) << shift
        if vbit >= 0:
            row_words[w] |= valid.astype(np.int64) << vbit
    return row_words


def _dense_group(pos, lo: int, span: int, cols: list, key_col: int,
                 keys, read: tuple) -> Optional[BuildSide]:
    """Scatter the build columns over the key range and pack those that
    fit into int32 words (dag.LookupJoin has the layout).  None where a
    key comes twice: what the presence bits count (a range with holes),
    or the slots hit (a range as long as the keys)."""
    n = len(pos)
    room: list = []                 # free bits of each word
    pbit = -1
    if n != span:                   # holes: one presence bit
        room.append(WORD_BITS - 1)
        pbit = 0
    else:
        seen = np.zeros(span, bool)
        seen[pos] = True
        if not seen.all():
            return None
    # holes: the tables' length is rounded (`table_slots`); none: the
    # keys' count is the tables', as it always was
    slots = table_slots(span) if pbit >= 0 else span
    layout, mins, room = _pack_plan(cols, key_col, keys, read, room,
                                    _fits_i32(lo, lo + span))
    apart = []
    for (w, *_rest), (data, valid) in zip(layout, cols):
        if w != APART:
            continue
        table = np.zeros(slots, data.dtype)
        table[pos] = data
        vtab = None
        if not valid.all():
            vtab = np.zeros(slots, bool)
            vtab[pos] = valid
        apart.append((table, vtab))
    row_words = _row_words(layout, mins, cols, len(room), pbit, n)
    tables = []
    for rw in row_words:
        table = _borrow_table(slots)
        table[pos] = rw             # below 2^31: WORD_BITS
        tables.append(table)
    if pbit >= 0 and np.count_nonzero(tables[0]) != n:
        for t in tables:
            _return_table(t, pos)
        return None                 # two rows wrote one slot's presence
    kdt = np.int32 if _fits_i32(lo, lo + span) else np.int64
    aux = [(jnp.asarray(np.array([lo, span], kdt)), None),
           (jnp.asarray(np.array(mins, np.int64).reshape(len(cols))), None)]
    on_device = [jnp.array(t) for t in tables]     # a copy, never a view
    jax.block_until_ready(on_device)    # then the tables can go back
    for t in tables:
        _return_table(t, pos)
    aux += [(w, None) for w in on_device]
    aux += [(jnp.asarray(t), None if v is None else jnp.asarray(v))
            for t, v in apart]
    return BuildSide(tuple(aux), n, True, n, dense=True,
                     packing=(len(tables), pbit, tuple(layout)))


def key_partition(firsts, devices, n_dev: int, top: int):
    """The range partition of a sharded build side over `n_dev` devices
    (`parallel/exchange.key_places` reads it): (part, slots).  The build
    rows lie in stripes of ascending keys, `firsts[s]` the least key of
    stripe s (None: it holds none) and `devices[s]` the device it lies
    on (a table's shards in row order under its placement, where the
    table is stored by the key; one stripe a device where the host deals
    the rows out); `top` a key above every build key.  Stripe s owns the
    keys from its first up to the next stripe's first (the last: up to
    `top`) and its device gives them that many slots, after the slots of
    its earlier stripes; `slots[d]` is what device d's stripes take in
    all.  `part` as `key_places` wants it: the splits, and the steps of
    the owner and of `key - slot` from one stripe to the next.  Pure."""
    n = len(firsts)
    splits, nxt = [0] * n, int(top)
    for s in range(n - 1, -1, -1):
        nxt = nxt if firsts[s] is None else int(firsts[s])
        splits[s] = nxt
    if any(a > b for a, b in zip(splits, splits[1:])):
        raise ValueError("key_partition: the stripes' keys overlap")
    slots = [0] * n_dev
    owner, adjust = [], []
    for s in range(n):
        width = (splits[s + 1] if s + 1 < n else int(top)) - splits[s]
        owner.append(int(devices[s]))
        adjust.append(splits[s] - slots[devices[s]])
        slots[devices[s]] += width
    part = np.array([splits,
                     np.diff(owner, prepend=0),
                     np.diff(adjust, prepend=0)], np.int64)
    return part, slots


def sharded_form(slots, columns: int,
                 device_bytes: int = DEFAULT_DEVICE_BYTES) -> bool:
    """May a unique build past the broadcast cap stay sharded, each
    device holding the direct-addressed table of the keys it owns
    (`slots`: the slots a device, `key_partition`)?  Where every
    device's table fits as `build_form` asks of one.  Pure."""
    return all(build_form(0, int(n), columns, device_bytes) == DIRECT
               for n in slots)


def _sharded_group(slots, mins, tables, part, n_dev: int, put) -> tuple:
    """The aux group of a sharded side: every array with a leading
    device axis (a device's table starts at its slot 0 and has `slots[d]`
    of them), the partition (the same on every device) last."""
    fits = _fits_i32(int(part.min()), int(part.max()))
    kdt = np.int32 if fits and max(slots) < _I32.max else np.int64
    meta = np.stack([np.zeros(n_dev, np.int64),
                     np.asarray(slots, np.int64)], axis=1).astype(kdt)
    return ((put(meta), None),
            (put(np.tile(np.asarray(mins, np.int64), (n_dev, 1))), None),
            *((t if isinstance(t, jax.Array) else put(t), None)
              for t in tables),
            (put(np.tile(part.astype(kdt), (n_dev, 1, 1))), None))


def table_length(slots) -> int:
    """The length of every device's word tables of a sharded side: the
    most slots a device takes, rounded as `table_slots` rounds."""
    return max(table_slots(int(max(slots))), 1024)


def sharded_build(keys: np.ndarray, cols: list, part: np.ndarray, slots,
                  put, key_col: int = -1, read=None,
                  device_bytes: int = DEFAULT_DEVICE_BYTES
                  ) -> Optional[BuildSide]:
    """Host half of a lookup join whose build stays sharded: unique keys
    (int64, NULLs dropped, at least one) and the row-aligned columns ->
    an aux group with a leading device axis, each device holding the
    direct-addressed table of the keys it owns under `part`
    (`key_partition`, `parallel/exchange.key_places`).  `put`: host
    array with a leading device axis -> device array sharded along it.
    None where a key comes twice, a device's table does not fit
    (`sharded_form`) or a column does not pack into a word: the caller
    takes another plan.  One packing for every device (it is part of the
    program); the presence bit is always there."""
    from ..parallel.exchange import key_places
    n, n_dev = len(keys), len(slots)
    read = tuple(read) if read is not None else (True,) * len(cols)
    carried = sum(r for j, r in enumerate(read) if j != key_col)
    if not sharded_form(slots, carried, device_bytes):
        return None
    own, pos = key_places(keys, part, np)
    if (pos < 0).any() or (pos >= np.asarray(slots)[own]).any():
        raise ValueError("sharded_build: a key outside the partition")
    layout, mins, room = _pack_plan(
        cols, key_col, keys, read, [WORD_BITS - 1],
        _fits_i32(int(keys.min()), int(keys.max()) + 1))
    if any(w == APART for w, *_ in layout):
        return None
    length = table_length(slots)
    tables = []
    for rw in _row_words(layout, mins, cols, len(room), 0, n):
        table = np.zeros((n_dev, length), np.int32)
        table[own, pos] = rw
        tables.append(table)
    if np.count_nonzero(tables[0]) != n:
        return None                 # two rows wrote one slot's presence
    aux = _sharded_group(slots, mins, tables, part, n_dev, put)
    return BuildSide(aux, n, True, n, dense=True, sharded=True,
                     packing=(len(tables), 0, tuple(layout)))


def table_layout(ranges: list, key_col: int, read: tuple,
                 key_fits_i32: bool) -> Optional[tuple]:
    """(packing, mins) of a sharded side whose tables a device program
    makes from a join's rows (`parallel/shuffle.ShardedTableProgram`):
    the rows are not at hand, so a column packs by the range its base
    column has in its table's snapshot, `ranges[j]` = (least, largest,
    no NULL) or None for a column that is no integer base column.  None
    where a column that is read has no range or does not pack."""
    if any(r is None and read[j] and j != key_col
           for j, r in enumerate(ranges)):
        return None
    layout, mins, room = _pack_plan(
        [(None, None)] * len(ranges), key_col, None, read,
        [WORD_BITS - 1], key_fits_i32,
        ranges=[r or (0, 0, True) for r in ranges])
    if any(w == APART for w, *_ in layout):
        return None
    return (len(room), 0, tuple(layout)), mins


def build_rows(node, grp) -> int:
    """Rows (slots, for a direct-addressed side) of the build side a
    launch carries in `grp` for the LookupJoin `node`."""
    if not node.dense:
        return int(grp[0][0].shape[0])
    # a sharded side's tables have a leading device axis: all of them
    return int(grp[2][0].size) if len(grp) > 2 else 0


__all__ = ["BuildSide", "prepare_build", "build_form", "table_slots",
           "key_partition", "sharded_form", "sharded_build", "table_layout",
           "table_length",
           "build_rows", "WORD_BITS", "APART", "KEY_ITSELF", "UNREAD",
           "DIRECT", "SORTED", "EXPANDING"]
