"""Repartition (shuffle) hash join: one shard_map program over the mesh.

Reference analog: the MPP HashPartition plan cut + distributed hash join —
PhysicalExchangeSender(HashPartition) (core/operator/physicalop/
physical_exchange_sender.go:109), executed as gRPC chunk streams between
TiFlash nodes, plus the intra-node ShuffleExec (executor/shuffle.go:86).

TPU redesign (SURVEY.md §2.10 P3/P4/P7): the whole fragment graph —
  scan(left) -> filter -> exchange(hash k) ──┐
  scan(right) -> filter -> exchange(hash k) ─┴─ join -> top chain -> merge
is ONE jit-compiled shard_map program.  Exchanges are lax.all_to_all over
the ICI mesh axis (parallel/exchange.py); the per-partition join is the
sorted-range expand join (copr/join.py); partial aggregates still merge
via psum.  No RPC, no serialization: rows cross chips as dense columns.

Static shapes: exchange buckets, the join output, and group tables all have
fixed capacities; every true size is reported via extras so the dispatcher
can regrow and retry (the paging discipline, SURVEY.md §5.7).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..analysis.compilekey import named_jit
from ..copr import dag as D
from ..copr.exec import (DeviceBatch, _agg_partial_states, _ensure_array,
                         _exec_node, _sel_array, agg_states, compact)
from ..copr.join import gather_expand, match_ranges
from ..expr.compile import Evaluator
from ..ops.sortkeys import INT64_MAX
from .exchange import all_to_all_exchange
from .mesh import SHARD_AXIS, mesh_platform, shard_map
from .spmd import _collective_merge, _flatten_block


@dataclass(frozen=True)
class ShuffleCaps:
    """Static capacities of one compiled shuffle-join program (part of the
    jit cache key; regrown by the dispatcher on overflow)."""
    left: int          # exchange send-bucket rows per (device, dest)
    right: int
    out: int           # join output rows per device
    rows: int = 0      # compacted result rows per device (rows-kind only)


class ShardedShuffleJoinProgram:
    """Compiled repartition-join program over a mesh.

    kind 'agg':  __call__ -> (merged/per-device states, extras)
    kind 'rows': __call__ -> ((cols, counts), extras) per device
    extras: per-device {'lmax','rmax','join_total'} true sizes.
    """

    def __init__(self, spec: D.ShuffleJoinSpec, mesh, caps: ShuffleCaps):
        self.spec = spec
        self.mesh = mesh
        self.caps = caps
        self.n_dev = len(mesh.devices.reshape(-1))
        self.agg = spec.top if isinstance(spec.top, D.Aggregation) else None
        self.kind = "agg" if self.agg is not None else "rows"
        # same host-merge policy as ShardedCopProgram (see spmd.py): only
        # SORT group tables merge on host; MIN/MAX merge
        # in-program via the psum-gather trick
        self.host_merge = self.agg is not None and self.agg.host_merged
        # same limb-exactness fence as spmd.py: int/decimal SUM (hi, lo)
        # limb psum stays int64-exact only below 2^31 contributing rows
        from ..types.dtypes import TypeKind as _K
        self._psum_limb_fence = (
            self.agg is not None and not self.host_merge and any(
                a.func == D.AggFunc.SUM and a.arg is not None
                and a.arg.dtype.kind not in (_K.FLOAT64, _K.FLOAT32)
                for a in self.agg.aggs))

        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS),
                    P(SHARD_AXIS), P(SHARD_AXIS), P())
        if self.kind == "agg":
            out_specs = P(SHARD_AXIS) if self.host_merge else P()
        else:
            out_specs = (P(SHARD_AXIS), P(SHARD_AXIS))
        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh, in_specs=in_specs,
            out_specs=(out_specs, P(SHARD_AXIS))), "shuffle", spec)
        self.name = self._fn.__name__

    # ------------------------------------------------------------- #

    def _side(self, chain, key_expr, cols, counts, aux, ev, cap,
              drop_null_keys: bool):
        """Scan chain + key eval + hash-partition exchange for one side.
        Returns (recv_cols, recv_valid, recv_keys, recv_key_ok, max_count)."""
        flat, base_sel = _flatten_block([(v, m) for v, m in cols], counts)
        flat = [(v, True if m is None else m) for v, m in flat]
        batch = _exec_node(chain, flat, base_sel, ev, aux)
        n = len(batch.cols[0][0]) if batch.cols else 0
        sel = _sel_array(batch.sel, n)
        kv, km = ev.eval(key_expr, batch.cols, {})
        kv = _ensure_array(kv, n).astype(jnp.int64)
        key_ok = sel if km is True else (sel & km)
        live = key_ok if drop_null_keys else sel
        send = [( _ensure_array(v, n), True if m is True else m)
                for v, m in batch.cols]
        send.append((kv, key_ok))
        out_cols, recv_valid, _ovf, max_count = all_to_all_exchange(
            send, live, jnp.where(key_ok, kv, 0), self.n_dev, cap)
        rkeys, rkey_ok = out_cols[-1]
        return out_cols[:-1], recv_valid, rkeys, rkey_ok, max_count

    def _device_fn(self, lcols, lcounts, rcols, rcounts, aux):
        ev = Evaluator(jnp, platform=mesh_platform(self.mesh))
        aux = tuple(tuple((v, True if m is None else m) for v, m in grp)
                    for grp in aux)
        spec, caps = self.spec, self.caps
        semi = spec.kind in ("semi", "anti")

        pcols, pvalid, pkeys, pkey_ok, lmax = self._side(
            spec.left, spec.left_key, lcols, lcounts, aux, ev, caps.left,
            drop_null_keys=(spec.kind == "inner" or spec.kind == "semi"))
        bcols, bvalid, bkeys, bkey_ok, rmax = self._side(
            spec.right, spec.right_key, rcols, rcounts, aux, ev, caps.right,
            drop_null_keys=True)

        # sort build partition by key; dead rows park at the end with an
        # INT64_MAX fill so match_ranges' n_live clamp excludes them
        nb = bkeys.shape[0]
        bdead = (~(bvalid & bkey_ok)).astype(jnp.int32)  # valueflow: ok - bool lane, [0, 1]
        _sdead, skey, perm = lax.sort(
            (bdead, bkeys, jnp.arange(nb, dtype=jnp.int64)), num_keys=2)
        n_live = jnp.sum(1 - bdead)
        skey = jnp.where(jnp.arange(nb, dtype=jnp.int64) < n_live,
                         skey, INT64_MAX)

        probe_ok = pvalid & pkey_ok
        lo, _hi, cnt = match_ranges(skey, n_live, pkeys, probe_ok)

        if semi:
            keep = (cnt > 0) if spec.kind == "semi" else (cnt == 0)
            joined = DeviceBatch(list(pcols), pvalid & keep,
                                 {"join_total": jnp.sum(pvalid & keep)})
        else:
            probe = [(v, True if m is True else m) for v, m in pcols]
            build = [(v, True if m is True else m) for v, m in bcols]
            out_cols, out_sel, total = gather_expand(
                probe, pvalid, probe_ok, build, perm, lo, cnt,
                spec.kind, caps.out)
            joined = DeviceBatch(out_cols, out_sel, {"join_total": total})

        njoin = len(joined.cols[0][0]) if joined.cols else 0
        sel_mask = _sel_array(joined.sel, njoin)
        extras = {"lmax": lmax[None], "rmax": rmax[None],
                  "join_total": jnp.asarray(joined.extras["join_total"])[None]}

        if self.agg is not None:
            states, batch = agg_states(self.agg, joined.cols, sel_mask, ev,
                                       aux)
            if self.host_merge:
                out = jax.tree_util.tree_map(lambda a: a[None], states)
            else:
                out = _collective_merge(states, SHARD_AXIS,
                                        len(self.mesh.devices.reshape(-1)))
            return out, extras
        batch = _exec_node(spec.top, joined.cols, sel_mask, ev, aux)
        out_cols, n = compact(batch, caps.rows)
        return ([(v[None], m[None]) for v, m in out_cols], n[None]), extras

    def transfer_breakdown(self, topo=None):
        """Per-link bytes of this compiled program's two exchange edges
        from its static caps (parallel/topology.TransferBreakdown;
        default topology: the mesh's declared host view) — the runtime
        twin of shardflow's plan-time attribution, sized by the SAME
        row-payload formula so the two can be compared directly."""
        from ..analysis import copcost as C
        from .topology import topology_for
        if topo is None:
            topo = topology_for(self.mesh)
        lb = self.caps.left * (C._schema_width(self.spec.left_dtypes)
                               + 8 + 2)          # cols + key + mask lanes
        rb = self.caps.right * (C._schema_width(self.spec.right_dtypes)
                                + 8 + 2)
        return topo.split_all_to_all(lb).combined(
            topo.split_all_to_all(rb))

    def __call__(self, lcols, lcounts, rcols, rcounts, aux_cols=()):
        if self._psum_limb_fence:
            # global joined-row bound: every device may emit caps.out rows
            if self.n_dev * self.caps.out >= 2 ** 31:
                raise OverflowError(
                    f"global join capacity {self.n_dev}x{self.caps.out} "
                    "exceeds the 2^31 limb-exact SUM bound for in-program "
                    "psum merge")
        return self._fn(tuple(lcols), lcounts, tuple(rcols), rcounts,
                        tuple(aux_cols))


@functools.lru_cache(maxsize=128)
def _cached(spec, mesh, caps):
    return ShardedShuffleJoinProgram(spec, mesh, caps)


def get_shuffle_program(spec: D.ShuffleJoinSpec, mesh,
                        caps: ShuffleCaps) -> ShardedShuffleJoinProgram:
    return _cached(spec, mesh, caps)


@dataclass(frozen=True)
class TableSpec:
    """What a `ShardedTableProgram` is compiled for: the rows program's
    output columns (`key_col` the build key among them, the slots' live
    mask last), the words' `packing` and the columns' `mins`
    (copr/joinbuild.table_layout), the tables' `slots`."""
    key_col: int
    packing: tuple
    mins: tuple
    slots: int

    def children(self):
        return ()


class ShardedTableProgram:
    """The sharded build side of a lookup join from a join's result that
    never leaves its devices: the compacted rows a rows-returning
    program put out on each device (`copr/exec.compact_root`: columns
    with a leading device axis, the slots' live mask last) -> that
    device's direct-addressed word tables over the keys it owns (`meta`:
    (devices, 2), a table's first slot and its slots; `part`: the
    partition, `parallel/exchange.key_places`), packed as
    copr/joinbuild.`_row_words` packs a host-made side.

    ONE scatter a word, of the rows the join kept and not of the rows
    scanned (a scatter on a v5e costs 90 ns an update: PERF.md, PR 28).
    Beside the tables, per device: the rows written, the slots that hold
    one (fewer: a key came twice) and the live rows whose key lies
    outside the device's range (the dispatcher takes another plan where
    either is off)."""

    def __init__(self, spec: TableSpec, mesh):
        self.spec = spec
        self.mesh = mesh
        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh,
            in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS)),
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS))), "table", spec)
        self.name = self._fn.__name__

    def _device_fn(self, cols, meta, part):
        from .exchange import key_places
        spec = self.spec
        n_words, _pbit, layout = spec.packing
        cols = [(v[0], m[0]) for v, m in cols]
        live = cols[-1][0].astype(bool)
        kv, km = cols[spec.key_col]
        kt = jnp.int32 if part.dtype == jnp.int32 \
            and kv.dtype.itemsize <= 4 else jnp.int64
        own, d = key_places(kv.astype(kt), part[0].astype(kt))
        inside = (own == lax.axis_index(SHARD_AXIS)) & (d >= 0) \
            & (d < meta[0, 1].astype(kt))
        ok = live & km & inside
        at = jnp.where(ok, d, spec.slots).astype(jnp.int32)  # valueflow: ok - a written row's offset is below the table's length < 2^31
        words = [jnp.zeros(kv.shape, jnp.int32) for _ in range(n_words)]
        words[0] = words[0] | 1             # the presence bit
        for (w, shift, bits, vbit, _wide), vmin, (v, m) in zip(
                layout, spec.mins, cols):
            if w < 0:
                continue
            if bits:
                f = (v.astype(jnp.int64) - vmin).astype(jnp.int32) & ((1 << bits) - 1)  # valueflow: ok - the column's range in its table takes `bits`
                words[w] = words[w] | (jnp.where(m, f, 0) << shift)
            if vbit >= 0:
                words[w] = words[w] | (m.astype(jnp.int32) << vbit)  # valueflow: ok - bool lane, [0, 1]
        tables = [jnp.zeros((spec.slots,), jnp.int32).at[at].set(
            word, mode="drop") for word in words]
        said = jnp.stack([
            jnp.sum(ok, dtype=jnp.int32),
            jnp.sum(tables[0] & 1, dtype=jnp.int32),
            jnp.sum(live & km & ~inside, dtype=jnp.int32)])
        return tuple(t[None] for t in tables), said[None]

    def __call__(self, out_cols, meta, part):
        return self._fn(tuple(out_cols), meta, part)


@functools.lru_cache(maxsize=64)
def get_table_program(spec: TableSpec, mesh) -> ShardedTableProgram:
    return ShardedTableProgram(spec, mesh)


__all__ = ["ShuffleCaps", "ShardedShuffleJoinProgram", "get_shuffle_program",
           "TableSpec", "ShardedTableProgram", "get_table_program"]
