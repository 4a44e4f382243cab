"""Columnar shard store: the TPU-resident table representation.

Reference analog: a TiKV region holds a key range of rows; the coprocessor
scans rows from the badger LSM per request (unistore/tikv/dbreader).  The
TPU design columnarizes once at snapshot build time (the TiFlash
raft-learner columnarization role, SURVEY.md §7 "hard parts" #6): a table
snapshot is S shards of fixed capacity C, stored as stacked (S, C) numpy
arrays (host) and cached on-device as sharded jax arrays keyed by epoch —
the region-cache analog: epoch bumps invalidate device state
(pkg/store/copr/region_cache.go).

Shard boundaries are row-id ranges (the memcomparable ordering contract of
SURVEY.md §A.2 reduces to row order here; range shards by key come with the
KV path).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import numpy as np

from ..chunk.column import Column, StringDict
from ..types import dtypes as dt
from ..parallel.mesh import sharded


def _pow2_at_least(n: int) -> int:
    c = 1
    while c < n:
        c <<= 1
    return c


@dataclass
class ColumnarSnapshot:
    """Immutable columnar snapshot of one table at an epoch."""
    names: list[str]
    dtypes: list[dt.DataType]
    columns: list[Column]              # full-length host columns
    epoch: int = 0
    n_shards: int = 8
    min_capacity: int = 1024
    # shard->store topology (store/placement.py).  None = plain even
    # split.  Mutating the placement (split/exclude) bumps its epoch and
    # invalidates the device cache, so the next dispatch re-fans-out
    # under the new topology (region-cache invalidation analog).
    placement: Any = None

    _device_cache: dict = field(default_factory=dict, repr=False)
    # prepared broadcast-join build sides read from THIS snapshot
    # (executor/physical._prepared_build): a new epoch is a new object
    _join_builds: dict = field(default_factory=dict, repr=False)
    _unique_keys: dict = field(default_factory=dict, repr=False)
    _key_ranges: dict = field(default_factory=dict, repr=False)

    @property
    def num_rows(self) -> int:
        return len(self.columns[0]) if self.columns else 0

    @property
    def dictionaries(self) -> dict[int, StringDict]:
        return {i: c.dictionary for i, c in enumerate(self.columns)
                if c.dictionary is not None}

    def key_is_unique(self, offset: int) -> bool:
        """No value of column `offset` occurs twice among its non-NULL
        rows (what lets a lookup join build this table by that key).
        Worked out once a snapshot: a strictly ascending column, as a
        generated or bulk-loaded primary key is, costs one pass."""
        known = self._unique_keys.get(offset)
        if known is None:
            c = self.columns[offset]
            data = c.data if c.validity.all() else c.data[c.validity]
            if data.dtype == object:
                known = False
            else:
                known = bool((data[1:] > data[:-1]).all()) \
                    or len(np.unique(data)) == len(data)
            self._unique_keys[offset] = known
        return known

    def key_is_ascending(self, offset: int) -> bool:
        """Column `offset` has no NULL and every row's value is above
        the row's before it: the table is stored by that key, so the
        rows a device holds cover a key range no other device's do (a
        lookup join's build side may then stay where it lives:
        executor/plan.which_side_moves).  Kept with the snapshot."""
        key = ("asc", offset)
        known = self._unique_keys.get(key)
        if known is None:
            c = self.columns[offset]
            known = bool(c.data.dtype.kind in "iu" and c.validity.all()
                         and (c.data[1:] > c.data[:-1]).all())
            self._unique_keys[key] = known
        return known

    def device_stripes(self, n_dev: int) -> list:
        """(first row, end row, device) of each shard, in row order, as
        `_put` lays the table out over `n_dev` devices: under a
        placement a shard lies on its store's device (`store % n_dev`),
        else the shards are dealt in order, as many a device.  A table
        stored by a key is so many stripes of ascending keys
        (copr/joinbuild.key_partition)."""
        if self.placement is not None:
            return sorted((s.lo, s.hi, s.store % n_dev)
                          for s in self.placement.shards)
        ranges = self._even_ranges()
        per = -(-len(ranges) // n_dev)
        return [(lo, hi, i // per) for i, (lo, hi) in enumerate(ranges)]

    def key_range(self, offset: int) -> tuple[int, int]:
        """(least, largest) non-NULL value of integer column `offset`;
        (0, -1) where it has none or is no integer column.  Kept with
        the snapshot, as `key_is_unique` is."""
        known = self._key_ranges.get(offset)
        if known is None:
            c = self.columns[offset]
            data = c.data if c.validity.all() else c.data[c.validity]
            known = (int(data.min()), int(data.max())) \
                if len(data) and data.dtype.kind in "iu" else (0, -1)
            self._key_ranges[offset] = known
        return known

    # ---------------- shard plan ---------------- #

    def shard_layout(self) -> tuple[int, int, np.ndarray]:
        """(n_shards, capacity, counts[n_shards]).  Rows are split evenly;
        capacity is a power-of-two bucket so jit programs recompile only on
        bucket changes (padding buckets, SURVEY.md §7 hard part #3)."""
        s = self.n_shards
        n = self.num_rows
        per = -(-n // s) if n else 0
        cap = max(_pow2_at_least(per), self.min_capacity)
        counts = np.minimum(np.maximum(n - np.arange(s) * per, 0), per)
        return s, cap, counts.astype(np.int64)

    def _even_ranges(self) -> list:
        s = self.n_shards
        n = self.num_rows
        per = -(-n // s) if n else 0
        return [(min(i * per, n), min(i * per + per, n)) for i in range(s)]

    def _placement_ranges(self, n_dev: int) -> list:
        """Slot row-ranges in device order (D*K grid, K = max shards on
        any device; short devices pad with empty slots)."""
        per_dev = self.placement.device_slots(n_dev)
        k = max((len(lst) for lst in per_dev), default=1) or 1
        ranges = []
        for lst in per_dev:
            ranges += [(s.lo, s.hi) for s in lst]
            ranges += [(0, 0)] * (k - len(lst))
        return ranges

    def _stacked_ranges(self, ranges) -> tuple[list, np.ndarray]:
        cap = max(_pow2_at_least(max((hi - lo for lo, hi in ranges),
                                     default=0)), self.min_capacity)
        counts = np.array([hi - lo for lo, hi in ranges], np.int64)
        cols = []
        for c in self.columns:
            if c.data.dtype == object:
                # wide (19-65 digit) decimal: host-only object ints.  The
                # planner refuses to fuse any expression touching it
                # (_device_supported), so its slot only keeps TableScan
                # offsets stable — upload a 1-byte placeholder.
                cols.append((np.zeros((len(ranges), cap), np.int8), None))
                continue
            # narrow physical width on device too: H2D bytes and HBM
            # footprint drop 2-8x; the expression compiler re-widens
            # inside the fused program where the logical width matters
            # (expr/compile.py _iwiden — XLA fuses the converts)
            phys = c.narrowed()
            data = np.zeros((len(ranges), cap), dtype=phys.dtype)
            valid = np.zeros((len(ranges), cap), dtype=bool)
            for i, (lo, hi) in enumerate(ranges):
                if hi > lo:
                    data[i, : hi - lo] = phys[lo:hi]
                    valid[i, : hi - lo] = c.validity[lo:hi]
            live = np.arange(cap)[None, :] < counts[:, None]
            all_valid = bool(valid[live].all())
            cols.append((data, None if all_valid else valid))
        return cols, counts

    def stacked_host(self) -> tuple[list, np.ndarray]:
        """Stacked (S, C) host arrays [(data, validity|None), ...] + counts
        (even layout; placement-aware stacking happens in _put)."""
        return self._stacked_ranges(self._even_ranges())

    # ---------------- device cache (region cache analog) ------------- #

    def _put(self, mesh) -> tuple[list, Any]:
        n_dev = mesh.devices.size
        if self.placement is not None:
            host_cols, counts = self._stacked_ranges(
                self._placement_ranges(n_dev))
        else:
            host_cols, counts = self.stacked_host()
        # the shard axis must divide the mesh: pad with empty shards
        # (count 0) so any shard plan runs on any mesh size
        s = len(counts)
        s_pad = -(-s // n_dev) * n_dev
        if s_pad != s:
            counts = np.concatenate([counts, np.zeros(s_pad - s, np.int64)])
            host_cols = [
                (np.concatenate([d, np.zeros((s_pad - s, d.shape[1]), d.dtype)]),
                 None if v is None else
                 np.concatenate([v, np.zeros((s_pad - s, v.shape[1]), bool)]))
                for d, v in host_cols]
        sh = sharded(mesh)
        dev = []
        for data, valid in host_cols:
            d = jax.device_put(data, sh)
            v = None if valid is None else jax.device_put(valid, sh)
            dev.append((d, v))
        dev_counts = jax.device_put(counts, sh)
        return dev, dev_counts

    def device_cols(self, mesh) -> tuple[list, Any]:
        # keyed on the mesh's stable FINGERPRINT (axis names + shape +
        # device ids), not id(mesh): the resident cache must survive a
        # Domain rebuilding its Mesh object over the same chips, and an
        # id() key could false-hit when the allocator reuses a dead
        # mesh's address (the same bug PR 2 fixed for sched task keys)
        from ..sched.task import mesh_fingerprint
        p_epoch = self.placement.epoch if self.placement is not None else -1
        key = (mesh_fingerprint(mesh), self.epoch, p_epoch)
        if key in self._device_cache:
            return self._device_cache[key]
        put = self._put(mesh)
        self._device_cache.clear()     # one epoch resident at a time
        self._device_cache[key] = put
        # lifetime contract (analysis/lifetime): these arrays are
        # PERSISTENT — reused across queries and pages — so a donating
        # launch over them is rejected at sched admission pre-trace.
        # The registration also credits the live HBM ledger (obs/hbm,
        # copgauge) with the resident footprint — array METADATA only,
        # never a device sync — and the ledger's weakref death callback
        # debits it when the cache entry is collected.
        from ..analysis.lifetime import register_resident
        nbytes = sum(
            int(d.nbytes) + (int(v.nbytes) if v is not None else 0)
            for d, v in put[0]) + int(put[1].nbytes)
        register_resident(put[1], nbytes=nbytes, fingerprint=key[0])
        return self._device_cache[key]

    def device_put_uncached(self, mesh) -> tuple[list, Any]:
        """Device placement WITHOUT the resident cache — the streaming
        (rows >> HBM) path places one batch at a time and lets it free as
        soon as its program consumed it (SURVEY.md §5.7 paging analog)."""
        return self._put(mesh)

    # ---------------- streaming batches (rows >> device memory) ------ #

    def device_bytes(self) -> int:
        """Stacked device footprint: S x capacity x (itemsize + validity),
        at the narrow physical width actually placed on device."""
        s, cap, _ = self.shard_layout()
        return s * cap * sum(c.narrowed().dtype.itemsize + 1
                             for c in self.columns)

    def view(self, lo: int, hi: int, min_capacity: int = 0) -> "ColumnarSnapshot":
        """Zero-copy row-range view (same shard count; forced capacity so
        every batch of a stream compiles to ONE program shape)."""
        return ColumnarSnapshot(
            self.names, self.dtypes,
            [c.slice(lo, hi) for c in self.columns], epoch=self.epoch,
            n_shards=self.n_shards,
            min_capacity=max(min_capacity, self.min_capacity))

    def row_batches(self, max_bytes: int) -> Optional[list]:
        """Split into row-range views whose device footprint fits
        max_bytes, or None when the whole snapshot already fits."""
        if max_bytes <= 0 or self.device_bytes() <= max_bytes or \
                not self.num_rows:
            return None
        # device_bytes() above already narrowed every column, so views
        # sliced off here inherit one shared physical width per column
        per_row = sum(c.narrowed().dtype.itemsize + 1 for c in self.columns)
        # pow2 capacity rounding can inflate a batch up to 2x: size for it
        rows = max(int(max_bytes // (2 * per_row)), self.n_shards)
        per_shard_cap = max(_pow2_at_least(-(-rows // self.n_shards)),
                            self.min_capacity)
        rows = per_shard_cap * self.n_shards
        return [self.view(lo, min(lo + rows, self.num_rows), per_shard_cap)
                for lo in range(0, self.num_rows, rows)]


def snapshot_from_columns(names: Sequence[str], cols: Sequence[Column],
                          n_shards: int = 8, epoch: int = 0,
                          min_capacity: int = 1024,
                          placement=None) -> ColumnarSnapshot:
    return ColumnarSnapshot(list(names), [c.dtype for c in cols], list(cols),
                            epoch=epoch, n_shards=n_shards,
                            min_capacity=min_capacity, placement=placement)


__all__ = ["ColumnarSnapshot", "snapshot_from_columns"]
