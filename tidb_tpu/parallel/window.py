"""Device window functions: hash-repartition + per-device sort + segment ops.

Reference analog: TiFlash MPP window execution — ExchangeSender
(HashPartition on PARTITION BY) into per-node Sort + Window operators
(executor/window.go semantics, mpp_exec.go plumbing).  The TPU program:

1. run the scan chain per device (fused, like every cop program),
2. lax.all_to_all rows to the device owning hash(partition keys) —
   equal keys land together, so every partition is device-local,
3. ONE multi-operand lax.sort by (live, partition keys, order keys),
4. window values from segment primitives over the sorted batch:
   - partition boundaries -> segment first-index via cummax,
   - row_number / rank / dense_rank from boundary + peer-change flags,
   - whole-partition COUNT/SUM/MIN/MAX/AVG via scatter-reduce into a
     per-segment table gathered back to rows.

Output rows are sharded like any row-returning program; order is
unspecified (SQL without ORDER BY).
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
from ..copr.exec import (Evaluator, _ensure_array, _exec_node, _sel_array,
                         compact)
from ..ops.sortkeys import sortable_int64
from ..types import dtypes as dt
from .exchange import all_to_all_exchange
from .mesh import SHARD_AXIS, mesh_platform, shard_map
from .spmd import _flatten_block

K = dt.TypeKind

RANK_FUNCS = ("row_number", "rank", "dense_rank")
AGG_FUNCS = ("count", "sum", "min", "max", "avg")


def _key_operands(vals_masks, descs=None):
    """(nullflag, sortable key) operand pairs for lax.sort, MySQL NULL
    ordering (first ASC / last DESC)."""
    ops = []
    for i, ((v, m), e) in enumerate(vals_masks):
        desc = descs[i] if descs is not None else False
        key = sortable_int64(jnp, v, e.dtype.is_float,
                             e.dtype.kind == K.UINT64)
        if desc:
            key = ~key
        if m is True:
            nf = jnp.zeros(v.shape[0], jnp.int32)
        else:
            flag = jnp.where(m, 1, 0) if not desc else jnp.where(m, 0, 1)
            nf = flag.astype(jnp.int32)  # valueflow: ok - literal 0/1 lanes
        ops += [nf, key]
    return ops


class ShardedWindowProgram:
    def __init__(self, spec: D.WindowShuffleSpec, mesh, capacity: int):
        self.spec = spec
        self.mesh = mesh
        self.capacity = capacity        # per-device per-bucket rows
        self.n_dev = len(mesh.devices.reshape(-1))
        self.out_dtypes = (D.output_dtypes(spec.child)
                           + tuple(it[2] for it in spec.items))
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), P())  # aux replicated
        out_specs = ((P(SHARD_AXIS), P(SHARD_AXIS)), P(SHARD_AXIS))
        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs), "window", spec)
        self.name = self._fn.__name__

    # -- device program ------------------------------------------------ #

    def _device_fn(self, cols, counts, aux):
        spec = self.spec
        ev = Evaluator(jnp, platform=mesh_platform(self.mesh))
        flat, base_sel = _flatten_block([(v, m) for v, m in cols], counts)
        flat = [(v, True if m is None else m) for v, m in flat]
        aux = tuple(tuple((v, True if m is None else m) for v, m in grp)
                    for grp in aux)
        batch = _exec_node(spec.child, flat, base_sel, ev, aux)
        n = len(batch.cols[0][0])
        live = _sel_array(batch.sel, n)
        memo: dict = {}

        # routing key: hash-combine of partition keys (collisions only
        # co-locate extra partitions — correctness unaffected)
        route = jnp.zeros(n, jnp.uint64)
        pk_vm = []
        for e in spec.partition_keys:
            v, m = ev.eval(e, batch.cols, memo)
            v = _ensure_array(v, n)
            pk_vm.append(((v, m), e))
            hv = v.astype(jnp.int64).astype(jnp.uint64)
            hv = jnp.where(m if m is not True else True, hv,
                           jnp.uint64(0x9E3779B9))
            route = route * jnp.uint64(1099511628211) + hv
        ok_vm = []
        for e, _desc in spec.order_keys:
            v, m = ev.eval(e, batch.cols, memo)
            ok_vm.append(((_ensure_array(v, n), m), e))
        arg_vm = []
        for _f, arg, _t in spec.items:
            if arg is None:
                arg_vm.append(None)
            else:
                v, m = ev.eval(arg, batch.cols, memo)
                arg_vm.append((_ensure_array(v, n), m))

        # ship: child output cols + pkey/okey/arg raw values + masks
        send = list(batch.cols)
        send += [vm for vm, _e in pk_vm]
        send += [vm for vm, _e in ok_vm]
        send += [vm for vm in arg_vm if vm is not None]
        send = [(_ensure_array(v, n),
                 jnp.ones(n, bool) if m is True else m) for v, m in send]
        recv, rvalid, ovf, max_cnt = all_to_all_exchange(
            send, live, route.astype(jnp.int64), self.n_dev, self.capacity)
        m_rows = rvalid.shape[0]
        nc = len(batch.cols)
        np_, no_ = len(pk_vm), len(ok_vm)
        r_child = recv[:nc]
        r_pk = [((recv[nc + i][0], recv[nc + i][1]), pk_vm[i][1])
                for i in range(np_)]
        r_ok = [((recv[nc + np_ + i][0], recv[nc + np_ + i][1]),
                 ok_vm[i][1]) for i in range(no_)]
        r_args = []
        j = nc + np_ + no_
        for vm in arg_vm:
            if vm is None:
                r_args.append(None)
            else:
                r_args.append(recv[j])
                j += 1

        # ONE sort: dead rows last, then partitions, then order keys
        dead = (~rvalid).astype(jnp.int32)  # valueflow: ok - bool lane, [0, 1]
        pk_ops = _key_operands(r_pk)
        ok_ops = _key_operands(r_ok, [d for _e, d in spec.order_keys])
        operands = [dead] + pk_ops + ok_ops
        nk = len(operands)
        *_, order = lax.sort(
            tuple(operands) + (jnp.arange(m_rows, dtype=jnp.int64),),
            num_keys=nk)
        valid_s = rvalid[order]
        iota = jnp.arange(m_rows, dtype=jnp.int64)

        def changed(ops):
            """Row differs from its predecessor on any sorted operand."""
            if not ops:
                return jnp.zeros(m_rows, bool)
            ch = jnp.zeros(m_rows, bool)
            for o in ops:
                os_ = o[order]
                ch = ch | jnp.concatenate(
                    [jnp.ones(1, bool), os_[1:] != os_[:-1]])
            return ch

        part_b = changed(pk_ops) | jnp.concatenate(
            [jnp.ones(1, bool), (~valid_s[1:]) & valid_s[:-1]])
        part_b = part_b.at[0].set(True)
        peer_b = part_b | changed(ok_ops)
        first_idx = lax.cummax(jnp.where(part_b, iota, -1))
        first_peer = lax.cummax(jnp.where(peer_b, iota, -1))
        seg = jnp.cumsum(part_b.astype(jnp.int64)) - 1   # 0-based segment
        n_seg_cap = m_rows

        out_items = []
        for (fname, arg, out_t), rvm in zip(spec.items, r_args):
            if fname == "row_number":
                val = iota - first_idx + 1
                out_items.append((val.astype(jnp.int64), valid_s))
                continue
            if fname == "rank":
                val = first_peer - first_idx + 1
                out_items.append((val.astype(jnp.int64), valid_s))
                continue
            if fname == "dense_rank":
                sps = jnp.cumsum(peer_b.astype(jnp.int64))
                val = sps - sps[first_idx] + 1
                out_items.append((val.astype(jnp.int64), valid_s))
                continue
            # whole-partition aggregates
            if arg is None:      # COUNT(*)
                av = jnp.ones(m_rows, jnp.int64)
                am = valid_s
            else:
                av = rvm[0][order]
                am = rvm[1][order] & valid_s
            cnt_tab = jnp.zeros(n_seg_cap, jnp.int64).at[seg].add(
                jnp.where(am, 1, 0), mode="drop")
            cnt = cnt_tab[seg]
            if fname == "count":
                out_items.append((cnt, valid_s))
                continue
            if fname in ("sum", "avg"):
                if jnp.issubdtype(av.dtype, jnp.floating):
                    z = av.astype(jnp.float64)
                else:
                    z = av.astype(jnp.int64)
                tab = jnp.zeros(n_seg_cap, z.dtype).at[seg].add(
                    jnp.where(am, z, 0), mode="drop")
                tot = tab[seg]
                if fname == "avg":
                    val = tot.astype(jnp.float64) / jnp.maximum(cnt, 1)
                    if arg is not None and arg.dtype.kind == K.DECIMAL:
                        # scaled-int decimal representation -> real value
                        val = val / (10 ** arg.dtype.scale)
                else:
                    val = tot
                out_items.append((val, valid_s & (cnt > 0)))
                continue
            # min / max
            isf = jnp.issubdtype(av.dtype, jnp.floating)
            big = jnp.inf if isf else jnp.iinfo(jnp.int64).max
            small = -jnp.inf if isf else jnp.iinfo(jnp.int64).min
            z = av.astype(jnp.float64 if isf else jnp.int64)
            init = big if fname == "min" else small
            neutral = jnp.where(am, z, jnp.asarray(init, z.dtype))
            tab = jnp.full(n_seg_cap, init, z.dtype)
            tab = (tab.at[seg].min(neutral, mode="drop") if fname == "min"
                   else tab.at[seg].max(neutral, mode="drop"))
            out_items.append((tab[seg], valid_s & (cnt > 0)))

        # send normalization made every mask a concrete array already
        out_cols = [(v[order], m[order] & valid_s) for v, m in r_child]
        out_cols += out_items
        from ..copr.exec import DeviceBatch
        packed, cnt_out = compact(
            DeviceBatch(tuple(out_cols), valid_s, {}), m_rows)
        extras = {"wmax": max_cnt[None] if max_cnt.ndim == 0 else max_cnt,
                  "ovf": ovf[None] if ovf.ndim == 0 else ovf}
        return ([(v[None], m[None]) for v, m in packed], cnt_out[None]), \
            extras


    def transfer_breakdown(self, topo=None):
        """Per-link bytes of this program's PARTITION BY repartition
        from its static bucket capacity (parallel/topology; default:
        the mesh's declared host view)."""
        from ..analysis import copcost as C
        from .topology import topology_for
        if topo is None:
            topo = topology_for(self.mesh)
        w = C._schema_width(self.out_dtypes) + 1   # cols + valid lane
        return topo.split_all_to_all(self.capacity * w)

    def __call__(self, cols, counts, aux_cols=()):
        return self._fn(tuple(cols), counts, tuple(aux_cols))


@functools.lru_cache(maxsize=64)
def _cached(spec, mesh, capacity):
    return ShardedWindowProgram(spec, mesh, capacity)


def get_window_program(spec: D.WindowShuffleSpec, mesh,
                       capacity: int) -> ShardedWindowProgram:
    return _cached(spec, mesh, capacity)


__all__ = ["ShardedWindowProgram", "get_window_program"]
