"""MPP exchange operators: repartition/broadcast over the mesh.

Reference analog: the MPP exchange layer — plan Fragments cut at
PhysicalExchangeSender(Broadcast|HashPartition|PassThrough)
(core/operator/physicalop/physical_exchange_sender.go:34,:109) executed as
gRPC chunk streams between TiFlash nodes (unistore analog
cophandler/mpp_exec.go exchSenderExec/exchRecvExec).

TPU redesign (SURVEY.md §2.10 P7): fragments are one shard_map program and
exchanges are ICI collectives —
- HashPartition  -> lax.all_to_all of fixed-capacity hash buckets
- Broadcast      -> lax.all_gather
- PassThrough    -> identity sharding
No serialization, no sockets: rows move as dense column arrays over the
interconnect.  Fixed bucket capacity keeps shapes static; overflow is
reported per device so the dispatcher can retry bigger (the paging
discipline again).
"""

from __future__ import annotations

from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import SHARD_AXIS

# Knuth multiplicative hashing over int64 keys (device-side hash partition)
_HASH_MULT = jnp.uint64(0x9E3779B97F4A7C15)

# --------------------------------------------------------------------- #
# exchange-payload trace recording (shardflow validation seam): when
# enabled, every all_to_all exchange TRACE records the concrete bytes of
# the send buffers it swaps — shapes are static at trace time, so this
# is pure host int arithmetic (no tracer values are read) and costs
# nothing when disabled.  tests/test_shardflow.py pins the static
# per-link prediction against these live buffer sizes, the copcost
# exact-resident-bytes precedent.
# --------------------------------------------------------------------- #

_TRACE_RECORDS: list = []
_RECORDING = False


def record_exchange(enable: bool = True) -> list:
    """Toggle trace-time payload recording; returns the (shared) record
    list of (n_dev, capacity, payload_bytes) tuples, cleared on
    enable."""
    global _RECORDING
    _RECORDING = True if enable else False
    if enable:
        _TRACE_RECORDS.clear()
    return _TRACE_RECORDS


def _note_payload(n_dev: int, capacity: int, nbytes: int) -> None:
    if _RECORDING:
        _TRACE_RECORDS.append((n_dev, capacity, nbytes))


def hash_partition_ids(keys, n_parts: int):
    """keys: int64 array -> partition id in [0, n_parts)."""
    h = keys.astype(jnp.uint64) * _HASH_MULT
    return (h >> jnp.uint64(33)).astype(jnp.int64) % n_parts


def all_to_all_exchange(cols: Sequence, valid, keys, n_dev: int,
                        capacity: int, axis: str = SHARD_AXIS):
    """HashPartition exchange inside a shard_map program.

    Each device buckets its local rows by hash(key) into a (n_dev,
    capacity) send buffer per column, then lax.all_to_all swaps bucket d of
    every device to device d.  Returns (recv_cols, recv_valid, overflow,
    max_count) where recv_* hold n_dev*capacity rows (concatenated incoming
    buckets), overflow is the per-device count of rows dropped for
    capacity, and max_count is the largest send-bucket size (what the
    dispatcher must regrow capacity to).
    """
    if valid is True:
        valid = jnp.ones(keys.shape[0], bool)
    pid = hash_partition_ids(keys, n_dev)
    pid = jnp.where(valid, pid, n_dev)           # dead rows -> dropped
    # position of each row within its destination bucket
    onehot = pid[:, None] == jnp.arange(n_dev, dtype=jnp.int64)[None, :]
    pos_in_bucket = jnp.cumsum(onehot, axis=0) - 1
    pos = jnp.take_along_axis(pos_in_bucket,
                              jnp.clip(pid, 0, n_dev - 1)[:, None],
                              axis=1)[:, 0]
    sent = valid & (pos < capacity)
    flat_idx = jnp.where(sent, jnp.clip(pid, 0, n_dev - 1) * capacity + pos,
                         n_dev * capacity)      # OOB -> dropped
    counts = jnp.sum(onehot & valid[:, None], axis=0)
    overflow = jnp.sum(jnp.maximum(counts - capacity, 0))
    max_count = jnp.max(counts)

    def scatter(v):
        buf = jnp.zeros((n_dev * capacity,), v.dtype)
        return buf.at[flat_idx].set(v, mode="drop").reshape(n_dev, capacity)

    send_valid = jnp.zeros((n_dev * capacity,), bool).at[flat_idx].set(
        sent, mode="drop").reshape(n_dev, capacity)
    recv_valid = lax.all_to_all(send_valid, axis, split_axis=0,
                                concat_axis=0, tiled=False).reshape(-1)
    payload = n_dev * capacity * send_valid.dtype.itemsize
    out_cols = []
    for v, m in cols:
        sv = scatter(v)
        payload += n_dev * capacity * sv.dtype.itemsize
        rv = lax.all_to_all(sv, axis, split_axis=0, concat_axis=0,
                            tiled=False)
        if m is True:
            rm = recv_valid      # reuse: identical to the send_valid swap
        else:
            sm = jnp.zeros((n_dev * capacity,), bool).at[flat_idx].set(
                sent & m, mode="drop").reshape(n_dev, capacity)
            payload += n_dev * capacity * sm.dtype.itemsize
            rm = lax.all_to_all(sm, axis, split_axis=0, concat_axis=0,
                                tiled=False).reshape(-1)
        out_cols.append((rv.reshape(-1), rm))
    _note_payload(n_dev, capacity, payload)
    return out_cols, recv_valid, overflow, max_count


def key_places(keys, part, xp=jnp):
    """(owner, offset) of each key of `keys` under a range partition of
    a sharded build side (copr/joinbuild.key_partition): the device that
    owns the key and the key's slot in that device's table.  `part`:
    (3, stripes) integers; the key range is cut into stripes at
    `part[0][1:]` (keys that never decrease: the least build key each
    stripe holds; a stripe that holds none repeats the next one's),
    stripe s lies on the device `sum(part[1][:s + 1])` and its keys take
    the slots from `key - sum(part[2][:s + 1])` on.  Every key has
    exactly one owner, whether a build row holds it or not: a key in
    the gap between two stripes' rows is its lower stripe's and finds an
    empty slot there; a key below the first stripe or past the last
    lands outside its owner's table (`offset` negative, or at least the
    table's length: the lookup's bounds check).  Both are sums of
    `key >= split` terms, a compare and two multiply-adds a stripe and
    no gather.  Pure; `xp` numpy or jax.numpy, one definition for the
    host that deals a table's rows out, the device program that makes a
    join's result into tables and the probe that looks both up."""
    keys = xp.asarray(keys)
    own = xp.zeros(keys.shape, keys.dtype) + part[1][0]
    adj = xp.zeros(keys.shape, keys.dtype) + part[2][0]
    for s in range(1, part.shape[-1]):
        ge = (keys >= part[0][s]).astype(keys.dtype)
        own = own + ge * part[1][s]
        adj = adj + ge * part[2][s]
    return own.astype(xp.int32), keys - adj  # valueflow: ok - a device's index, below the mesh's size


def exchange_passes(n: int, n_dev: int, capacity: int) -> int:
    """The column sorts over all n slots of a device that
    `exchange_rows` makes its `n_dev` buckets of `capacity` slots with:
    1 where the buckets to the other devices together take at most half
    of the slots (the two levels of `_bucket_places`), else one a
    destination.  Two levels cost one sort of n slots and `n_dev` of
    `(n_dev - 1) * capacity`: as much as `n_dev` sorts of n at three
    quarters of n.  Pure: shapes only, known when the program is
    traced."""
    return 1 if 2 * (n_dev - 1) * capacity <= n else n_dev


def _bucket_places(remote, dest, n_dev: int, capacity: int, stacked: int):
    """(places, oks, need): for each destination `capacity` slots that
    hold the place (copr/join.live_rows: an index into the slots in
    `_tile_order`) of every row of `remote` with that `dest`, and which
    of the slots hold one, provided `need` <= `capacity`; `need`, the
    fullest (column, destination) count times the columns.  A slot
    whose `ok` is set holds the same place whichever form fills it.

    Where the buckets are a small share of the slots
    (`exchange_passes` 1) the n slots are sorted ONCE: a slot's word is
    `place | dest << bit | dead << (bit + bits(n_dev))`, so one
    single-lane unstable column sort puts every column's remote rows
    first, grouped by destination, in place order.  The first
    `(n_dev - 1) * capacity / COMPACT_COLUMNS` rows of every column are
    kept: whenever `need` <= `capacity` a column holds no more remote
    rows than that (the device's own bucket is empty).  Over the kept
    slots alone each destination marks the other destinations' words
    dead and sorts the columns again; the words carry the original
    place, so nothing is gathered to compose the two levels.  Else
    `live_rows` once a destination, each over all n slots."""
    from ..copr.dag import COMPACT_COLUMNS as cols
    from ..copr.join import _slot_places, _tile_order, live_rows
    n = remote.shape[0]
    if exchange_passes(n, n_dev, capacity) != 1:
        places, oks, need = [], [], jnp.zeros((), jnp.int32)
        for d in range(n_dev):
            rows, ok, need_d = live_rows(remote & (dest == d), capacity,
                                         stacked)
            places.append(rows)
            oks.append(ok)
            need = jnp.maximum(need, need_d.astype(jnp.int32))  # valueflow: ok - at most the device's slots, below 2^31
        return places, oks, need
    assert n % cols == 0 and capacity % cols == 0, (n, capacity)
    bit = max(n - 1, 1).bit_length()
    dead = bit + max(n_dev - 1, 1).bit_length()
    wt = jnp.int32 if dead < 31 else jnp.int64
    at = _slot_places(n, stacked, wt)
    words = _tile_order(
        jnp.where(remote, at | (dest.astype(wt) << bit), at | (1 << dead)),
        stacked).reshape(n // cols, cols)
    kept = lax.sort(words, dimension=0,
                    is_stable=False)[:(n_dev - 1) * capacity // cols]
    to = words >> bit           # a dead word's is no device's
    need = jnp.zeros((), jnp.int32)
    places, oks = [], []
    for d in range(n_dev):
        need = jnp.maximum(need, jnp.max(jnp.sum(
            to == d, axis=0, dtype=jnp.int32)) * cols)
        mine = jnp.where((kept >> bit) == d, kept, kept | (1 << dead))
        # the barrier keeps the flat form, as in `live_rows`
        top = lax.optimization_barrier(lax.sort(
            mine, dimension=0, is_stable=False)[:capacity // cols]
            .reshape(-1))
        places.append(top & ((1 << bit) - 1))
        oks.append((top >> dead) == 0)
    return places, oks, need


def exchange_rows(cols: Sequence, live, dest, n_dev: int, capacity: int,
                  stacked: int = 1, axis: str = SHARD_AXIS):
    """Inside a shard_map program: the live rows of `cols` [(value,
    mask | True)] whose `dest` is another device travel there.  Returns
    (recv_cols, recv_ok, need, sent): `n_dev * capacity` slots of rows
    the other devices sent this one and which of them hold one; `need`,
    the capacity this device's fullest bucket takes (above `capacity`
    rows are missing: the caller reports it and the statement is
    rerun); `sent`, the rows this device sent.

    A bucket a destination, filled as a lookup join's live probe rows
    are compacted, by column sorts of `place | dead` words and no
    n-sized scatter or `cumsum` over a one-hot (`_bucket_places`: one
    sort of the device's n slots where the buckets are a small share of
    them, one a destination else), the columns packed into 32-bit words
    once and gathered at every bucket's places in ONE stacked gather
    (copr/join.pack_rows), the buckets swapped by ONE `lax.all_to_all`
    of the words and one of the slots' live bits.  The bucket to the
    device itself stays empty: its rows are looked up where they are.

    (On a v5e, 2^24 slots in two stacked runs, four destinations: the
    buckets' places for colocated keys, 196,608 slots a bucket and one
    row in 4,000 remote, 3.56 ms in two levels (the one sort of 2^24
    slots in fast memory), 40.8 ms with a sort of all slots a
    destination, 19.3 ms with one keyed sort and a gather along the
    columns for the destinations' slices; with the stacked gather of
    three words 16.4 ms against 53.8.  Keys that lie anywhere, 2,621,440
    slots a bucket and three rows in eight remote: 17.6 ms against 41.2.
    PERF.md section 6, PR 36.)"""
    from ..copr.join import _tile_order, pack_rows
    me = lax.axis_index(axis)
    remote = live & (dest != me)
    places, oks, need = _bucket_places(remote, dest, n_dev, capacity,
                                       stacked)
    at = jnp.concatenate(places)

    def taken(x):
        return x.at[at].get(mode="promise_in_bounds")

    def tiled(x):
        return _tile_order(x, stacked)

    def swapped(x):
        x = x.reshape((n_dev, capacity) + x.shape[1:])
        return lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                              tiled=False).reshape((-1,) + x.shape[2:])

    words, _apart, unpack = pack_rows(cols)
    recv = swapped(taken(jnp.stack([tiled(w) for w in words], axis=1))) \
        if words else None
    recv_ok = swapped(jnp.concatenate(oks))
    _note_payload(n_dev, capacity, n_dev * capacity * (4 * len(words) + 1))
    got = unpack([recv[:, w] for w in range(len(words))], lambda i: (
        swapped(taken(tiled(cols[i][0]))),
        True if cols[i][1] is True else swapped(taken(tiled(cols[i][1])))))
    return got, recv_ok, need, jnp.sum(remote, dtype=jnp.int32)


def broadcast_gather(cols: Sequence, valid, axis: str = SHARD_AXIS):
    """Broadcast exchange: every device receives all rows (lax.all_gather),
    the TPU analog of ExchangeType_Broadcast for small build sides."""
    out = []
    for v, m in cols:
        gv = lax.all_gather(v, axis).reshape(-1)
        gm = (lax.all_gather(m, axis).reshape(-1) if m is not True
              else True)
        out.append((gv, gm))
    gvalid = lax.all_gather(valid, axis).reshape(-1)
    return out, gvalid


__all__ = ["hash_partition_ids", "all_to_all_exchange", "broadcast_gather",
           "record_exchange", "key_places", "exchange_passes",
           "exchange_rows"]
