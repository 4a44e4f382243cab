"""SPMD coprocessor fan-out: shard_map + collectives.

Reference analog: the region-parallel scan fan-out
(pkg/store/copr/coprocessor.go:337 buildCopTasks + copIterator worker pool,
tidb_distsql_scan_concurrency=15) and the root-side partial-agg merge
(agg_hash_final_worker.go).  The TPU redesign collapses both into ONE
program: every device runs the identical fused cop kernel over its shards,
then partial aggregates merge in-program via psum/pmin/pmax over the ICI
mesh axis — no per-task RPCs, no merge workers (SURVEY.md §2.10 P1+P2).

Shard layout: stacked (S, C) arrays, S shards of capacity C, sharded along
the mesh 'shard' axis.  Each device flattens its (S/D, C) block into one
batch of S/D·C rows with a precomputed live-row mask, so one kernel pass
covers all local shards regardless of S/D.
"""

from __future__ import annotations

import functools
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from ..analysis.compilekey import named_jit
from ..analysis.lifetime import donation_plan, verify_donation
from ..compilecache import cached_call
from ..copr import dag as D
from ..copr import facts as F
from ..copr.aggregate import _MERGE
from ..copr.exec import (DeviceBatch, _agg_partial_states, _exec_node,
                         agg_states, compact, compact_root, dense_limb_form,
                         dense_view)
from ..copr.joinbuild import DIRECT, EXPANDING, SORTED, build_rows
from ..expr.compile import Evaluator
from .mesh import SHARD_AXIS, mesh_platform, shard_map


def _donation_argnums(dag, program: str, donate: bool,
                      override) -> tuple:
    """The builder-side donation seam: ``donate_argnums`` comes ONLY
    from the DAG's DonationPlan (analysis/lifetime) — literals in
    traced modules fail the TPU-DONATE lint rule — and any explicit
    override is re-verified pre-trace, so a seeded unsafe plan raises
    DonationError before jax.jit could bake the aliasing in."""
    if override is not None:
        argnums = tuple(override)
        verify_donation(dag, argnums, program)
        return argnums
    if not donate:
        return ()
    return donation_plan(dag, program).donate_argnums


def _psum_gather(arr, axis: str, n_dev: int):
    """all_gather built from psum alone: each device deposits its partial
    into its own slot of a zeros (D, ...) array, psum fills every slot
    exactly once.  MIN/MAX then merge in-program with the one collective
    the static analyses price (shardflow, copcost) — cost is a Dx state
    blow-up, negligible for agg partials."""
    idx = lax.axis_index(axis)
    slot = jnp.zeros((n_dev,) + arr.shape, arr.dtype).at[idx].set(arr)
    return lax.psum(slot, axis)


def _collective_merge(states: dict, axis: str, n_dev: int) -> dict:
    """Merge partial-state pytrees across the mesh axis.  This is the exact
    seam BASELINE.json names: `psum` replaces the final-agg merge workers.
    MIN/MAX ride the same psum via _psum_gather + in-program reduce."""
    def go(name, arr):
        how = _MERGE[name]
        if how == "sum":
            return lax.psum(arr, axis)
        g = _psum_gather(arr, axis, n_dev)
        return jnp.min(g, axis=0) if how == "min" else jnp.max(g, axis=0)

    out: dict = {}
    for k, v in states.items():
        if isinstance(v, dict):
            out[k] = {f: go(f, a) for f, a in v.items()}
        else:
            out[k] = go(k, v)
    return out


def _flatten_block(cols, counts, view=None):
    """(S_local, C) blocks -> one (S_local*C,) batch + live-row mask.

    `view`: the (S_local, blocks, tiles, lanes) shape the program's
    reduction will view the flat rows as again (copr/exec.dense_view).
    Every column is then pinned to that view where it enters the
    program, by a select on the live-row mask built in that shape: XLA
    moves a reshape across element-wise operations but not across that
    select, so the whole row pipeline is computed in the view and fuses
    into the reduction.  (Reshaped only where the reduction starts, the
    first 32-bit value of every column is written to HBM and read back:
    10.4 ms against 3.3 for TPC-H Q1 at SF10, PERF.md section 6.)"""
    s, c = cols[0][0].shape
    if view is None:
        base_sel = (jnp.arange(c, dtype=jnp.int64)[None, :]
                    < counts[:, None]).reshape(-1)
        flat = [(v.reshape(-1), None if m is None else m.reshape(-1))
                for v, m in cols]
        return flat, base_sel
    _s, blocks, tiles, lanes = view
    idx = jnp.int32 if c < 2 ** 31 else jnp.int64
    row = ((jnp.arange(blocks, dtype=idx)[:, None, None] * tiles
            + jnp.arange(tiles, dtype=idx)[None, :, None]) * lanes
           + jnp.arange(lanes, dtype=idx)[None, None, :])
    live = row[None] < counts.astype(idx)[:, None, None, None]
    flat = [(jnp.where(live, v.reshape(view),
                       jnp.zeros((), v.dtype)).reshape(-1),
             None if m is None else (live & m.reshape(view)).reshape(-1))
            for v, m in cols]
    return flat, live.reshape(-1)


class ShardedCopProgram:
    """Compiled SPMD coprocessor program over a mesh.

    kind 'agg':  __call__(stacked_cols, counts) -> replicated merged states
    kind 'rows': -> per-device compacted (cols, count) stacked along shard
                   axis (host concatenates; TopN re-merged at root)
    """

    def __init__(self, dag_root: D.CopNode, mesh, row_capacity: int = 0,
                 donate: bool = False, donate_argnums=None):
        self.root = dag_root
        self.mesh = mesh
        # what the lowerings are traced for (Evaluator.platform)
        self.platform = mesh_platform(mesh)
        self.row_capacity = row_capacity
        # buffer donation (analysis/lifetime): the donating variant is
        # requested only for launch-unique inputs (streamed HBM batches);
        # the plan forbids donation outright for loop-carried regrow
        # state, and overrides are verified pre-trace
        self.donation = donation_plan(dag_root, "solo")
        self._donate_argnums = _donation_argnums(
            dag_root, "solo", donate, donate_argnums)
        self.agg = dag_root if isinstance(dag_root, D.Aggregation) else None
        self.kind = "agg" if self.agg is not None else "rows"
        # per-device input shape -> what its trace's lowerings wrote
        # into `DeviceBatch.facts`, as far as it counts as this program's
        self._traced: dict = {}
        # MIN/MAX merge IN-PROGRAM via _psum_gather (psum-only all_gather +
        # reduce), so the whole merge stays on device behind one kind of
        # collective.  Only SORT-strategy group tables merge host-side:
        # per-device group sets aren't aligned, so there is no
        # elementwise collective merge (the repartition-exchange path is
        # the in-program alternative).
        self.host_merge = self.agg is not None and self.agg.host_merged
        # int/decimal SUMs produce (hi, lo) limb states whose in-program
        # psum is int64-exact only below 2^31 global rows; float sums,
        # counts, host-merged (object-int) programs, and valueflow-proven
        # narrow SUMs (single int64 word, whole-table no-wrap proof — the
        # row fence is subsumed by the value proof) are exempt
        from ..types.dtypes import TypeKind as _K
        self._psum_limb_fence = (
            self.agg is not None and not self.host_merge and any(
                a.func == D.AggFunc.SUM and a.arg is not None
                and a.arg.dtype.kind not in (_K.FLOAT64, _K.FLOAT32)
                and i not in self.agg.narrow_sums
                for i, a in enumerate(self.agg.aggs)))

        # programs containing an expanding or a compacting join also
        # return a per-device extras dict (the true join output size, the
        # live probe rows) for the dispatcher's rerun
        self.has_extras = D.has_extras(dag_root)

        # shardflow introspection: which collective the merge rides and
        # over which axis — the layout facts the out_specs below encode,
        # exposed so the static analyses/tests can pin them without
        # re-deriving spec structure
        self.collective_axis = SHARD_AXIS
        self.merge_kind = "host" if self.host_merge else "psum"

        # aux replicated, but the group of a build that stays sharded
        # where it lives (dag.LookupJoin.sharded): a leading device axis
        joins = D.lookup_joins(dag_root)
        self._sharded_aux = frozenset(j.aux_slot for j in joins if j.sharded)
        aux_specs = P() if not self._sharded_aux else tuple(
            P(SHARD_AXIS) if slot in self._sharded_aux else P()
            for slot in range(max(j.aux_slot for j in joins) + 1))
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), aux_specs)
        if self.kind == "agg":
            # per-device states when min/max present; replicated post-psum
            # otherwise
            out_specs = P(SHARD_AXIS) if self.host_merge else P()
        else:
            out_specs = (P(SHARD_AXIS), P(SHARD_AXIS))
        if self.has_extras:
            out_specs = (out_specs, P(SHARD_AXIS))

        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs), "solo", dag_root,
            donate_argnums=self._donate_argnums)
        self.name = self._fn.__name__
        # copforge (compilecache): calls resolve through the AOT program
        # cache — warm-pool/persisted executables serve without tracing,
        # misses stage via jit.lower(...).compile() and persist.  The
        # raw jit object stays on _fn for AOT introspection.
        self._cached = cached_call(self._fn, dag_root, mesh, "solo",
                                   row_capacity=row_capacity,
                                   donate_argnums=self._donate_argnums)

    def _device_fn(self, cols, counts, aux):
        cols = [(v, m) for v, m in cols]
        # the flat columns are the device's S stacked shards, one run
        # each (DeviceBatch.stacked)
        stacked, cap = cols[0][0].shape
        view = None
        # a join's gather writes its columns out whatever the view, and
        # XLA:TPU compiles a gather pinned to it 14x as long (90 s
        # against 6 for chip_smoke's join with a group-by)
        if self.agg is not None \
                and dense_limb_form(self.agg, self.platform) \
                and not D.lookup_joins(self.agg):
            view, pad = dense_view(stacked * cap, stacked)
            if pad:
                view = None
        flat, base_sel = _flatten_block(cols, counts, view)
        flat = [(v, True if m is None else m) for v, m in flat]
        aux = tuple(tuple((v, True if m is None else m) for v, m in grp)
                    for grp in aux)
        if self._sharded_aux:       # the device's own group: its one row
            aux = tuple(tuple((v[0], m if m is True else m[0])
                              for v, m in grp)
                        if slot in self._sharded_aux else grp
                        for slot, grp in enumerate(aux))
        ev = Evaluator(jnp, platform=self.platform)
        if self.agg is not None:
            states, batch = agg_states(self.agg, flat, base_sel, ev, aux,
                                       stacked)
            if self.host_merge:
                # add a leading per-device axis; host reduces across it
                out = jax.tree_util.tree_map(lambda a: a[None], states)
            else:
                with jax.named_scope("merge"):
                    out = _collective_merge(
                        states, SHARD_AXIS,
                        len(self.mesh.devices.reshape(-1)))
        else:
            batch = _exec_node(self.root, flat, base_sel, ev, aux, stacked)
            # a TopN's or a Limit's few rows leave in their order, at
            # the front; any other root's by `compact_root`, which adds
            # the slots' live mask as a last column (store/client
            # `_assemble_rows` reads it)
            if isinstance(self.root, (D.TopN, D.Limit)):
                out_cols, n = compact(batch, self.row_capacity)
            else:
                out_cols, n = compact_root(batch, self.row_capacity,
                                           self.platform)
            # keep a leading per-device axis so out_specs can shard it
            out = ([(v[None], m[None]) for v, m in out_cols], n[None])
        self._traced[(stacked, cap)] = F.of_program(batch.facts, self.root)
        if self.has_extras:
            extras = {k: jnp.asarray(v)[None] for k, v in batch.extras.items()}
            return out, extras
        return out

    def facts(self, stacked_cols: Sequence, counts, aux_cols=()) -> dict:
        """What one launch with these inputs says of itself
        (copr/facts.py): what the lowerings wrote in the trace for this
        input shape (`_device_fn` keeps it), and what the DAG and the
        inputs say of its lookup joins.  Where no trace ran in this
        process (copforge served the executable from its disk store) a
        program that can have a fact of the first kind is traced
        abstractly, once."""
        s, c = stacked_cols[0][0].shape[:2]
        shape = (s // len(self.mesh.devices.reshape(-1)), c)
        if shape not in self._traced and F.says_in_trace(self.root):
            jax.eval_shape(self._fn, tuple(stacked_cols), counts,
                           tuple(aux_cols))
        out = dict(self._traced.get(shape, ()))
        joins = D.lookup_joins(self.root)
        if joins:
            out["join"] = "unique" if all(j.unique for j in joins) \
                else "multimatch"
            out["join_form"] = ",".join(
                DIRECT if j.dense else EXPANDING if not j.unique
                and j.kind in ("inner", "left") else SORTED
                for j in joins)
            out["probe_rows"] = s * c
            out["build_rows"] = sum(build_rows(j, aux_cols[j.aux_slot])
                                    for j in joins)
            if out["join"] == "unique":
                out["probe_capacity"] = max(j.probe_capacity for j in joins)
                out["match_capacity"] = max(j.match_capacity for j in joins)
                out["probe_window"] = max(j.probe_window for j in joins)
            if self._sharded_aux:
                out["build_sharded"] = len(self._sharded_aux)
            moved = [j for j in joins if j.exchange]
            if moved:
                # only the probe's rows travel: the build stays where
                # it lives (a program that re-buckets both sides is
                # parallel/shuffle.py's, and says "both")
                out["exchange"] = "probe_to_build"
                out["exchange_capacity"] = max(j.exchange for j in moved)
        return out

    def __call__(self, stacked_cols: Sequence, counts, aux_cols=()):
        if self._psum_limb_fence and stacked_cols:
            s, c = stacked_cols[0][0].shape[:2]
            # limb-exactness fence at the psum seam: the in-program psum of
            # (hi, lo) SUM limbs stays int64-exact only while the global
            # row capacity is < 2^31 (see copr/exec._agg_partial_states)
            if s * c >= 2 ** 31:
                raise OverflowError(
                    f"global capacity {s}x{c} exceeds the 2^31 limb-exact "
                    "SUM bound for in-program psum merge")
        return self._cached(tuple(stacked_cols), counts, tuple(aux_cols))


@functools.lru_cache(maxsize=256)
def _cached(dag_root, mesh, row_capacity, donate):
    return ShardedCopProgram(dag_root, mesh, row_capacity, donate)


def get_sharded_program(dag_root: D.CopNode, mesh, row_capacity: int = 0,
                        donate: bool = False) -> ShardedCopProgram:
    # the donating variant caches apart: donation is baked into the
    # jitted executable's input aliasing
    return _cached(dag_root, mesh, row_capacity, True if donate else False)


def _abstract(a, slots: int = 0):
    """One argument's shape, dtype and sharding, holding no array; with
    ``slots``, what ``_stack_slots`` makes of that many such arrays."""
    shape = a.shape if not slots else (a.shape[0], slots) + a.shape[1:]
    return jax.ShapeDtypeStruct(shape, a.dtype,
                                sharding=getattr(a, "sharding", None))


def _abstract_args(self, stacked_cols: Sequence, counts):
    """The arguments of this fused program's call as shapes: what the
    compile cache is asked about and a background compile is given."""
    return jax.tree_util.tree_map(
        _abstract, (tuple(stacked_cols), counts, ()))


def _abstract_slot_args(self, cols_list: Sequence, counts_list: Sequence):
    """The same for a batched program: the stacked arguments' shapes,
    worked out from one slot's, nothing stacked."""
    return jax.tree_util.tree_map(
        functools.partial(_abstract, slots=self.n_slots),
        (tuple((v, m) for v, m in cols_list[0]), counts_list[0], ()))


def _members_facts(self, stacked_cols: Sequence, counts) -> dict:
    """`ShardedCopProgram.facts` of a fused program: its members', merged
    as copr/facts.py says of each."""
    return F.merged([p.facts(stacked_cols, counts) for p in self.members])


class FusedCopProgram:
    """N compatible cop chains over ONE shared scan as a single launch.

    The admission scheduler (sched/) groups queued tasks whose chains
    read the SAME stacked device inputs (one snapshot scan, one mesh) but
    differ in filters/aggregates — the cross-query fusion seam ROADMAP
    names.  Each member chain is traced over the shared inputs inside one
    shard_map; XLA CSEs the scan loads, live-row masks, and any common
    predicate subtrees across members, so the table's HBM pass is paid
    once and every member's merged states come back as a separate output
    leaf, demultiplexed to its waiter by the scheduler.

    Agg members qualify when they are extras-free (an expanding join's
    regrow loop re-runs programs per task — the contract class of
    analysis.contracts.fusion_signature).  In-program members
    (SCALAR/DENSE) come back replicated post-psum; host-merge members
    (SORT group tables) keep their per-device leading axis via a
    per-member out_spec, so fused leaves never interact either way.
    SORT members additionally share one table shape — the fusion
    signature carries group_capacity, so incompatible capacities never
    reach this constructor."""

    def __init__(self, fused: D.FusedDag, mesh, donate: bool = False,
                 donate_argnums=None):
        if len(fused.members) < 2:
            raise ValueError("fusion needs at least two member chains")
        self.fused = fused
        self.mesh = mesh
        # donation over the FUSED dag: the plan re-derives from every
        # member (one loop-carried member forbids the group) and the
        # shared-aux rule (a slot two members read must survive the
        # unfused fallback) — see analysis/lifetime.aux_lifetime;
        # verified before any member program builds
        self.donation = donation_plan(fused, "fused")
        self._donate_argnums = _donation_argnums(
            fused, "fused", donate, donate_argnums)
        self.members = tuple(get_sharded_program(m, mesh)
                             for m in fused.members)
        for p in self.members:
            if p.kind != "agg" or p.has_extras:
                raise ValueError(
                    "only extras-free agg chains fuse (member "
                    f"{type(p.root).__name__} is {p.kind}"
                    f"{'+extras' if p.has_extras else ''})")
        # the fence is the OR of the members': same capacity inputs, so
        # one limb-overflow bound covers every leaf
        self._psum_limb_fence = any(p._psum_limb_fence
                                    for p in self.members)
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), P())
        # per-member out_specs: a host-merge member's states carry a
        # per-device leading axis, an in-program member's are replicated
        out_specs = tuple(P(SHARD_AXIS) if p.host_merge else P()
                          for p in self.members)
        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs), "fused", fused,
            donate_argnums=self._donate_argnums)
        self.name = self._fn.__name__
        self._cached = cached_call(self._fn, fused, mesh, "fused",
                                   donate_argnums=self._donate_argnums)

    def _device_fn(self, cols, counts, aux):
        # each member re-traces its chain over the SAME input refs; XLA
        # common-subexpression-eliminates the shared scan/flatten work
        return tuple(p._device_fn(cols, counts, aux)
                     for p in self.members)

    facts = _members_facts
    abstract_args = _abstract_args

    def __call__(self, stacked_cols: Sequence, counts, aux_cols=()):
        if self._psum_limb_fence and stacked_cols:
            s, c = stacked_cols[0][0].shape[:2]
            if s * c >= 2 ** 31:
                raise OverflowError(
                    f"global capacity {s}x{c} exceeds the 2^31 limb-exact "
                    "SUM bound for in-program psum merge")
        return self._cached(tuple(stacked_cols), counts, tuple(aux_cols))


@functools.lru_cache(maxsize=64)
def _cached_fused(fused, mesh, donate):
    return FusedCopProgram(fused, mesh, donate)


def get_fused_program(fused: D.FusedDag, mesh,
                      donate: bool = False) -> FusedCopProgram:
    return _cached_fused(fused, mesh, True if donate else False)


class FusedRowsProgram:
    """N compatible ROW-returning cop chains over ONE shared scan
    (ROADMAP fusion-breadth follow-on): rows-kind plans reading the same
    snapshot residents fuse into one launch with PER-MEMBER output
    capacities — each member keeps its own cumsum-compaction buffer and
    live count, so every waiter's paging (regrow-on-overflow) loop still
    sees its own counts.  Only extras-free chains qualify (an expanding
    join re-runs programs per task); XLA CSEs the shared scan loads and
    masks across members exactly as in the agg fusion."""

    def __init__(self, fused: D.FusedDag, mesh, row_capacities: tuple,
                 donate_argnums=None):
        if len(fused.members) < 2:
            raise ValueError("fusion needs at least two member chains")
        if len(row_capacities) != len(fused.members):
            raise ValueError("one row capacity per member chain")
        self.fused = fused
        self.mesh = mesh
        # rows members keep per-member paging loops: the plan is
        # loop-carried across the board, so the derived argnums are
        # always empty — the parameter exists so a seeded override is
        # still verified (and rejected) before ANY member program builds
        self.donation = donation_plan(fused, "fused-rows")
        self._donate_argnums = _donation_argnums(
            fused, "fused-rows", False, donate_argnums)
        self.members = tuple(
            get_sharded_program(m, mesh, cap)
            for m, cap in zip(fused.members, row_capacities))
        for p in self.members:
            if p.kind != "rows" or p.has_extras:
                raise ValueError(
                    "only extras-free row chains fuse (member "
                    f"{type(p.root).__name__} is {p.kind}"
                    f"{'+extras' if p.has_extras else ''})")
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), P())
        out_specs = tuple((P(SHARD_AXIS), P(SHARD_AXIS))
                          for _ in self.members)
        self._fn = named_jit(shard_map(
            self._device_fn, mesh=mesh, in_specs=in_specs,
            out_specs=out_specs), "fused-rows", fused,
            donate_argnums=self._donate_argnums)
        self.name = self._fn.__name__
        # member output capacities live OUTSIDE the fused dag: they ride
        # the key's extra slot so capacity variants never collide
        self._cached = cached_call(self._fn, fused, mesh, "fused-rows",
                                   donate_argnums=self._donate_argnums,
                                   extra=tuple(row_capacities))

    def _device_fn(self, cols, counts, aux):
        return tuple(p._device_fn(cols, counts, aux)
                     for p in self.members)

    facts = _members_facts
    abstract_args = _abstract_args

    def __call__(self, stacked_cols: Sequence, counts, aux_cols=()):
        return self._cached(tuple(stacked_cols), counts, tuple(aux_cols))


@functools.lru_cache(maxsize=64)
def _cached_fused_rows(fused, mesh, row_capacities):
    return FusedRowsProgram(fused, mesh, row_capacities)


def get_fused_rows_program(fused: D.FusedDag, mesh,
                           row_capacities: tuple) -> FusedRowsProgram:
    return _cached_fused_rows(fused, mesh, tuple(row_capacities))


def _stack_slots(cols_list, counts_list, n_slots):
    """Stack K tasks' (S, C) inputs along a batch-slot dim -> (S, K, C),
    padding short batches by repeating the last slot: one compiled
    program per pow2 slot count instead of one per K."""
    k = len(cols_list)
    pads = list(cols_list) + [cols_list[-1]] * (n_slots - k)
    cnts = list(counts_list) + [counts_list[-1]] * (n_slots - k)
    stacked = []
    for j in range(len(pads[0])):
        v = jnp.stack([c[j][0] for c in pads], axis=1)
        m = None if pads[0][j][1] is None else \
            jnp.stack([c[j][1] for c in pads], axis=1)
        stacked.append((v, m))
    return stacked, jnp.stack(list(cnts), axis=1)


class BatchedCopProgram:
    """K compatible dense-agg cop tasks as ONE vmapped SPMD launch.

    The admission scheduler (sched/) coalesces concurrent tasks that
    compile to the same program but carry distinct inputs: their stacked
    (S, C) column arrays stack again along a batch-slot dim -> (S, K, C),
    the base program's device fn runs under jax.vmap over that dim inside
    one shard_map, and the replicated merged states split back per slot.
    Only programs whose whole merge happens in-program qualify (kind
    'agg', no host merge, no extras) — vmapping a psum batches the
    collective, it does not mix slots."""

    def __init__(self, dag_root: D.CopNode, mesh, n_slots: int,
                 donate: bool = True):
        self.base = get_sharded_program(dag_root, mesh)
        if self.base.kind != "agg" or self.base.host_merge \
                or self.base.has_extras:
            raise ValueError("only fully in-program agg plans batch")
        self.n_slots = n_slots
        # the stacked (S, K, C) inputs are FRESH copies _stack_slots
        # builds per launch (jnp.stack of the member arrays), so the
        # lifetime plan donates them unconditionally: K tasks' worth of
        # stacked input stops coexisting with the outputs
        self.donation = donation_plan(dag_root, "batched")
        self._donate_argnums = _donation_argnums(
            dag_root, "batched", donate, None)
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), P())
        fn = jax.vmap(self.base._device_fn, in_axes=(1, 1, None),
                      out_axes=0)
        self._fn = named_jit(shard_map(fn, mesh=mesh, in_specs=in_specs,
                                       out_specs=P()),
                             "batched", dag_root,
                             donate_argnums=self._donate_argnums)
        self.name = self._fn.__name__
        self._cached = cached_call(self._fn, dag_root, mesh, "batched",
                                   n_slots=n_slots,
                                   donate_argnums=self._donate_argnums)

    abstract_args = _abstract_slot_args

    def __call__(self, cols_list: Sequence, counts_list: Sequence) -> list:
        k = len(cols_list)
        if self.base._psum_limb_fence and cols_list[0]:
            s, c = cols_list[0][0][0].shape[:2]
            if s * c >= 2 ** 31:
                raise OverflowError(
                    f"global capacity {s}x{c} exceeds the 2^31 limb-exact "
                    "SUM bound for in-program psum merge")
        stacked, counts = _stack_slots(cols_list, counts_list, self.n_slots)
        out = self._cached(tuple(stacked), counts, ())
        return [jax.tree_util.tree_map(lambda a, i=i: a[i], out)
                for i in range(k)]


@functools.lru_cache(maxsize=32)
def _cached_batched(dag_root, mesh, n_slots):
    return BatchedCopProgram(dag_root, mesh, n_slots)  # donates stacks


def get_batched_program(dag_root: D.CopNode, mesh,
                        n_slots: int) -> BatchedCopProgram:
    n_slots = max(2, 1 << (n_slots - 1).bit_length())   # pow2 slot counts
    return _cached_batched(dag_root, mesh, n_slots)


class BatchedRowsProgram:
    """K same-program ROW-returning cop tasks as ONE vmapped launch.

    Closes the ROADMAP launch-shape gap: compacted row outputs carry a
    per-device (1, capacity) buffer + live count, so stacking them needs
    per-slot capacity handling — the vmapped device fn keeps each slot's
    own cumsum-compaction and count, the slot axis rides BEHIND the
    device axis (out_axes=1) so the shard out_specs still shard axis 0,
    and the demux hands every task its own (cols, counts) pair with the
    counts it needs for the paging (regrow-on-overflow) loop.  Tasks in
    one batch share a task key, hence one dag digest and one row
    capacity; only extras-free plans qualify (an expanding join's regrow
    loop re-runs programs per task)."""

    def __init__(self, dag_root: D.CopNode, mesh, row_capacity: int,
                 n_slots: int, donate: bool = True):
        self.base = get_sharded_program(dag_root, mesh, row_capacity)
        if self.base.kind != "rows" or self.base.has_extras:
            raise ValueError("only extras-free row plans batch")
        self.n_slots = n_slots
        # per-launch stacked copies: ephemeral by construction, exactly
        # as in BatchedCopProgram — each waiter's paging loop resubmits
        # with a NEW stack, never re-reading a donated one
        self.donation = donation_plan(dag_root, "batched-rows")
        self._donate_argnums = _donation_argnums(
            dag_root, "batched-rows", donate, None)
        in_specs = (P(SHARD_AXIS), P(SHARD_AXIS), P())
        # slot axis at position 1: per-device leading axis stays axis 0
        fn = jax.vmap(self.base._device_fn, in_axes=(1, 1, None),
                      out_axes=1)
        self._fn = named_jit(shard_map(
            fn, mesh=mesh, in_specs=in_specs,
            out_specs=(P(SHARD_AXIS), P(SHARD_AXIS))),
            "batched-rows", dag_root,
            donate_argnums=self._donate_argnums)
        self.name = self._fn.__name__
        self._cached = cached_call(
            self._fn, dag_root, mesh, "batched-rows",
            row_capacity=row_capacity, n_slots=n_slots,
            donate_argnums=self._donate_argnums)

    abstract_args = _abstract_slot_args

    def __call__(self, cols_list: Sequence, counts_list: Sequence) -> list:
        k = len(cols_list)
        stacked, counts = _stack_slots(cols_list, counts_list, self.n_slots)
        out_cols, out_counts = self._cached(tuple(stacked), counts, ())
        # leaves: (D, K, cap) values / (D, K) counts -> per-slot (D, cap)
        return [([(v[:, i], m[:, i]) for v, m in out_cols],
                 out_counts[:, i]) for i in range(k)]


@functools.lru_cache(maxsize=32)
def _cached_batched_rows(dag_root, mesh, row_capacity, n_slots):
    return BatchedRowsProgram(dag_root, mesh, row_capacity, n_slots)


def get_batched_rows_program(dag_root: D.CopNode, mesh, row_capacity: int,
                             n_slots: int) -> BatchedRowsProgram:
    n_slots = max(2, 1 << (n_slots - 1).bit_length())   # pow2 slot counts
    return _cached_batched_rows(dag_root, mesh, row_capacity, n_slots)


__all__ = ["ShardedCopProgram", "get_sharded_program",
           "BatchedCopProgram", "get_batched_program",
           "BatchedRowsProgram", "get_batched_rows_program",
           "FusedCopProgram", "get_fused_program",
           "FusedRowsProgram", "get_fused_rows_program"]
