"""Coprocessor DAG — the serialized pushdown plan.

Reference analog: tipb.DAGRequest / tipb.Executor (the protobuf executor
tree TiDB ships to TiKV/TiFlash coprocessors; see SURVEY.md §A.1 for the
exact node set the in-repo engine handles: TableScan, Selection, Projection,
Aggregation, StreamAgg, TopN, Limit, ExchangeSender/Receiver...).

The TPU build keeps the same tree shape as the unit of pushdown, but the
"coprocessor" compiles the whole tree into ONE fused XLA program per plan
digest (the closure-executor analog, unistore/cophandler/closure_exec.go:468)
instead of interpreting operators row-batch by row-batch.  Nodes are frozen
dataclasses so a DAG hashes to a jit-cache key (analog of the cop cache,
pkg/store/copr/coprocessor_cache.go).
"""

from __future__ import annotations

import enum
import functools
from dataclasses import dataclass, field
from typing import Optional, Tuple

from ..expr.ir import Expr
from ..types import dtypes as dt


class AggFunc(enum.Enum):
    COUNT = "count"          # COUNT(expr): non-null count; arg None = COUNT(*)
    SUM = "sum"
    MIN = "min"
    MAX = "max"
    FIRST = "first"          # group key passthrough
    # AVG never reaches the coprocessor: the planner splits it into
    # SUM + COUNT exactly like the reference (SURVEY.md §A.4).  The
    # variance/stddev family is likewise rewritten to SUM/SUM(x^2)/COUNT.
    # Host-side aggregates (aggfuncs breadth; _bind_agg keeps them off the
    # device program):
    BIT_AND = "bit_and"
    BIT_OR = "bit_or"
    BIT_XOR = "bit_xor"
    GROUP_CONCAT = "group_concat"
    ANY_VALUE = "any_value"
    JSON_ARRAYAGG = "json_arrayagg"


@dataclass(frozen=True)
class AggDesc:
    """Aggregate function descriptor (expression/aggregation analog)."""
    func: AggFunc
    arg: Optional[Expr]          # None only for COUNT(*)
    out_dtype: dt.DataType

    def __str__(self) -> str:
        return f"{self.func.value}({self.arg if self.arg is not None else '*'})"


@dataclass(frozen=True)
class CopNode:
    def children(self) -> Tuple["CopNode", ...]:
        return ()


@dataclass(frozen=True)
class TableScan(CopNode):
    """Reads columns of one shard (region analog).  `col_offsets` index into
    the shard's stored column order; the scan's output schema is exactly
    these columns in this order (tipb.TableScan carries ColumnInfos)."""
    col_offsets: Tuple[int, ...]
    col_dtypes: Tuple[dt.DataType, ...]


@dataclass(frozen=True)
class Selection(CopNode):
    child: CopNode = None  # type: ignore[assignment]
    conditions: Tuple[Expr, ...] = ()

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class Projection(CopNode):
    child: CopNode = None  # type: ignore[assignment]
    exprs: Tuple[Expr, ...] = ()

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class Expand(CopNode):
    """Grouping-sets row replication (GROUP BY ... WITH ROLLUP).

    Reference analog: tipb ExecType_TypeExpand executed at
    unistore/cophandler/mpp.go:638, planned by logical_expand.go:32.
    Output schema: child columns ++ one nullable column per rollup key ++
    gid (int64).  Level l of `levels` replicates every live row keeping
    the first len(keys)-l keys (rolled keys masked NULL); gid = l, so
    GROUPING() lowers to bit tests over gid and rolled NULLs stay
    distinguishable from natural NULLs.
    """
    child: CopNode = None  # type: ignore[assignment]
    keys: Tuple[Expr, ...] = ()
    levels: int = 0

    def children(self):
        return (self.child,)


class GroupStrategy(enum.Enum):
    SCALAR = "scalar"    # no GROUP BY: one output row
    DENSE = "dense"      # small known key domain -> dense group ids
    SORT = "sort"        # device sort by the group key + a reduce of the
                         # runs into a per-device group table, merged on
                         # the host (`Aggregation.host_merged`)


# metadata of a node's field that came after programs were named by their
# DAG's digest: at its default it is no part of `compilekey.stable_digest`,
# so every program that does not use it keeps the name it had
DIGEST_IF_SET = {"digest": "if_set"}

# the most groups a device ranks for a `GroupTopN` (a reduce a group:
# copr/runagg._first_groups); a longer LIMIT is ranked by the host
GROUP_TOPN_MAX = 64


@dataclass(frozen=True)
class GroupTopN:
    """What the consumer of a host-merged aggregation will keep of its
    groups: the first `limit` in the order `keys` give, ("key", j, desc)
    a group key and ("agg", i, desc) a COUNT's or a SUM's value, MySQL's
    NULL order (first ascending, last descending).  The planner writes
    it where a TopN sits above the aggregation and reads nothing else
    (`executor/plan._push_group_topn`); the host still ranks what comes
    back.  `on_device`: the device's groups are whole (no group has rows
    on another device or in another batch), so it may rank its table and
    send the first `limit` groups alone; the dispatcher clears it where
    they are not (`store/client`)."""
    keys: Tuple = ()
    limit: int = 0
    on_device: bool = True


@dataclass(frozen=True)
class Aggregation(CopNode):
    """Partial (per-shard) hash aggregation.

    DENSE strategy: every group-by item must have a known finite code domain
    (dict-encoded string column, or planner-bounded int).  `domain_sizes[i]`
    is that size **including** a NULL slot when nullable; the fused kernel
    reduces into a dense (prod(domain_sizes),) state vector — the psum seam.
    SORT strategy handles unbounded domains via multi-key sort +
    segment-reduce into a fixed-capacity group table.
    `narrow_sums` (SCALAR/DENSE): agg indexes whose int/decimal SUM the
    planner PROVED (analysis/valueflow, from ANALYZEd column stats) can
    never escape int64 across the whole table — those states accumulate
    a single int64 word instead of (hi, lo) limbs.  Part of the frozen
    hash, so narrow and limb programs key, cache, and fuse apart.
    `pack_words` (SORT, read by the TPU's lowering alone: copr/runagg):
    the 32-bit words of a row's sort record in the exact form, 1 or 2;
    0 is the wide form, which always fits.  The planner's guess from the
    columns' statistics; a launch whose record did not fit says the bits
    it takes (`__bits__`) and the dispatcher reruns the statement wider.
    `topn` (host-merged strategies): see `GroupTopN`.
    `dependent` (indexes into `group_by`): group keys that are functions
    of the other group keys, so every row of a group holds the same
    value: they decide no group (copr/runagg leaves them out of the sort
    record's key part and reads them at a run's end).  A finding of the
    run, written by the executor (`with_dependent_keys`), never by the
    planner.
    """
    child: CopNode = None  # type: ignore[assignment]
    group_by: Tuple[Expr, ...] = ()
    aggs: Tuple[AggDesc, ...] = ()
    strategy: GroupStrategy = GroupStrategy.SCALAR
    domain_sizes: Tuple[int, ...] = ()   # DENSE only, aligned with group_by
    group_capacity: int = 0              # SORT only: max distinct groups/shard
    narrow_sums: Tuple[int, ...] = ()    # SCALAR/DENSE: agg indexes with a
                                         # valueflow-proven single-word SUM
    pack_words: int = field(             # SORT: words of the exact record
        default=0, metadata=DIGEST_IF_SET)
    topn: Optional[GroupTopN] = field(   # the groups the consumer keeps
        default=None, metadata=DIGEST_IF_SET)
    dependent: Tuple[int, ...] = field(  # keys the other keys determine
        default=(), metadata=DIGEST_IF_SET)

    def children(self):
        return (self.child,)

    @property
    def num_groups(self) -> int:
        n = 1
        for s in self.domain_sizes:
            n *= s
        return n

    @property
    def host_merged(self) -> bool:
        """The devices' group tables merge on the host: their group sets
        are not aligned, so no elementwise collective merges them (what
        spmd/shuffle hand back, the client's regrow loop, the contracts
        and the cost model all turn on)."""
        return self.strategy is GroupStrategy.SORT


def wide_groups(agg: Aggregation) -> Aggregation:
    """`agg` for a dispatcher that reruns nothing for a record that did
    not fit and whose launches do not hold a group's rows whole (a
    streamed batch, an exchange's output): the wide record, which always
    fits, and its groups ranked by the host."""
    import dataclasses
    topn = agg.topn and dataclasses.replace(agg.topn, on_device=False)
    return dataclasses.replace(agg, pack_words=0, topn=topn)


@dataclass(frozen=True)
class TopN(CopNode):
    """Per-shard TopN (root merges shard tops, reference cophandler/topn.go).
    `sort_key`/`desc` is the single-key form; `sort_keys` (a tuple of
    (expr, desc) pairs, priority order) carries multi-column ORDER BY —
    all keys ride one multi-key comparator (cophandler/topn.go
    multi-ByItem analog).  The device finds the first `limit` rows of
    that order exactly without sorting every row: per-block minima
    prune all but `limit` blocks, and only those are sorted
    (`topn_block_len`, copr/exec._exec_topn)."""
    child: CopNode = None  # type: ignore[assignment]
    sort_key: Expr = None  # type: ignore[assignment]
    desc: bool = False
    limit: int = 0
    nulls_last: bool = False  # MySQL: NULLs first ASC, last DESC
    sort_keys: Tuple = ()     # ((Expr, desc), ...): overrides sort_key/desc

    def children(self):
        return (self.child,)


# a TopN block is at least one (8, 128) int32 lane tile
TOPN_MIN_BLOCK = 1024


def topn_block_len(n: int, k: int) -> int:
    """Block length `L` of the device TopN over `n` slots with limit `k`:
    the rows are viewed as `n // L` blocks, the per-block minima are
    sorted, and only the `min(k, n // L)` blocks that can hold the
    answer are sorted in full.  About 8 * sqrt(n / k) rounded to a power
    of two, floored at the lane tile: on the chip a block costs the
    streaming pass what ~64 rows cost the final sort (each block ends in
    a cross-lane reduce, ~85 ns against ~2 ns a sorted row, v5e
    trace, PERF.md section 6), and n / L blocks against k * L sorted
    rows balance there.  `L == n` is ONE block — the plain full sort —
    returned when pruning cannot pay: `n` not divisible into such blocks
    (toy tables, join outputs at out_capacity, streamed batches) or the
    two sorts not under a quarter of `n` (small `n`, a large LIMIT).
    The kernel and its admission cost (analysis/copcost) share this
    one formula."""
    k = min(k, n)
    if k <= 0:
        return n
    bits = (n // k).bit_length() - 1
    length = max(8 << ((bits + 1) // 2), TOPN_MIN_BLOCK)
    blocks = n // length
    if n % length or blocks + min(k, blocks) * length > n // 4:
        return n
    return length


# the probe compaction (copr/join.live_rows) views a batch's slots as
# this many interleaved columns; a capacity is whole rows of that view
COMPACT_COLUMNS = 128


def probe_capacity_for(est_rows: float, rows: int) -> int:
    """The slots a device compacts the live probe rows of a lookup join
    to (LookupJoin.probe_capacity), from the `est_rows` of its `rows`
    the filters beneath the join are estimated to leave; 0 where
    compacting does not pay.  Pure, like `topn_block_len`.

    The estimate, a quarter more for its error, and six standard
    deviations of what one of the compaction's interleaved columns gets
    of rows that fall at random (the fullest column decides whether a
    launch fits), rounded up to an eighth of its power of two, not to
    the power: the lookup costs its slots, and an estimate near a power
    of two would double it from one ANALYZE to the next.  At most an
    eighth of the rows: above that the compaction's own pass over every
    slot is no longer small beside the lookups it saves."""
    if est_rows <= 0:
        return 0
    need = int(1.25 * est_rows + 6 * (COMPACT_COLUMNS * est_rows) ** 0.5) + 1
    step = max(1 << max(need.bit_length() - 3, 0), 8 * COMPACT_COLUMNS)
    cap = -(-need // step) * step
    return cap if cap * 8 <= rows else 0


# a lookup read by windows fetches no more than this many table slots a
# block of COMPACT_COLUMNS probe rows (`probe_window_for`)
PROBE_WINDOW_MAX = 512


def probe_window_for(rows: int, span: int, ordered: bool) -> int:
    """The table slots a block of COMPACT_COLUMNS probe rows is sure to
    find its matches within (LookupJoin.probe_window), for a probe key
    column of `rows` rows over a value range of `span` that ANALYZE
    found `ordered` (its values never decrease in storage order); 0
    where the lookup should gather an index a slot.  Pure, like
    `probe_capacity_for`.

    Rows in key order read the table front to back, so a block's
    offsets lie within about COMPACT_COLUMNS * span / rows slots of its
    least: twice that mean, in whole lanes, is the window (TPC-H's
    `l_orderkey`, 1..7 rows for 8 keys of every 32: 128.0 slots a block
    on average, 191 at most, a window of 256).  Rounded up from a
    sixty-fourth less: a key with as many rows as its range, give or
    take the data, must not flip between two windows, two programs,
    from one ANALYZE to the next.  The select-reduce that reads a
    window costs its length a row (2.3 ms a 128 slots at 2^23 rows on a
    v5e, the gather 60): none above PROBE_WINDOW_MAX."""
    if not ordered or rows <= 0 or span <= 0:
        return 0
    twice = 2 * COMPACT_COLUMNS * span / rows * (1 - 1 / 64)
    window = max(-(-int(twice) // COMPACT_COLUMNS), 1) * COMPACT_COLUMNS
    return window if window <= PROBE_WINDOW_MAX else 0


def exchange_capacity_for(est_rows: float, n_dev: int,
                          colocated: bool) -> int:
    """The slots of one bucket of a lookup join's exchange
    (LookupJoin.exchange): `est_rows` live probe rows a device, `n_dev`
    devices, `colocated` whether the probe key is stored in key order as
    the build key is (then a device's probe rows and the build rows they
    match lie on the same device but for the stragglers where the two
    tables' shards end at different keys).  Pure, like
    `probe_capacity_for`; 0 on one device.

    Keys that lie anywhere send a device's rows evenly: a bucket takes
    1/n_dev of them, a quarter more and six standard deviations.
    Colocated keys send next to nothing: a sixty-fourth of the rows.
    Whole rows of the compaction's view, rounded up to an eighth of the
    power of two.  A guess either way: a bucket that does not fit costs
    one rerun with what the devices found, and the digest remembers
    (store/client `_exchange_regrown`)."""
    if n_dev <= 1:
        return 0
    share = est_rows / 64 if colocated else est_rows / n_dev
    return exchange_capacity_round(
        int(1.25 * share + 6 * (COMPACT_COLUMNS * share) ** 0.5) + 1)


def exchange_capacity_round(need: int) -> int:
    """`need` slots as a bucket's capacity: whole rows of the
    compaction's view, up to an eighth of its power of two."""
    step = max(1 << max(int(need).bit_length() - 3, 0), 8 * COMPACT_COLUMNS)
    return max(-(-int(need) // step), 1) * step


def probe_scan_column(join: "LookupJoin") -> Optional[int]:
    """Which column of its `TableScan` the probe key of `join` IS (the
    index into `col_offsets`), through the Selections, ColumnRef
    Projections and joins beneath it; None where it is computed or a
    build side's column.  Rows of such a key lie in the order the scan
    stores them: what `probe_window_for` asks of."""
    from ..expr.ir import ColumnRef
    e, cur = join.probe_key, join.child
    while isinstance(e, ColumnRef):
        if isinstance(cur, TableScan):
            return e.index if e.index < len(cur.col_offsets) else None
        if isinstance(cur, Projection):
            e = cur.exprs[e.index] if e.index < len(cur.exprs) else None
        elif isinstance(cur, LookupJoin):
            if e.index >= len(output_dtypes(cur.child)):
                return None             # a column the join brought
        elif not isinstance(cur, Selection):
            return None
        cur = cur.child
    return None


def window_ok(join: "LookupJoin") -> bool:
    """May `join` read its table by windows: a unique direct-addressed
    inner/left lookup whose probe key is a column of the scan and whose
    rows reach it in the scan's order (nothing beneath has compacted
    them: `live_rows` leaves its rows in no order)."""
    return join.unique and join.dense and join.kind in ("inner", "left") \
        and not join.probe_capacity \
        and compacting_join(join.child) is None \
        and probe_scan_column(join) is not None


@dataclass(frozen=True)
class Limit(CopNode):
    child: CopNode = None  # type: ignore[assignment]
    limit: int = 0

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class LookupJoin(CopNode):
    """Broadcast lookup join against a host-materialized build side.

    Reference analog: the MPP broadcast join (ExchangeType_Broadcast +
    HashJoinProbeExec, cophandler/mpp_exec.go).  Two device strategies:

    - `unique=True` (FK->unique-PK): each probe row matches at most one
      build row, so the join is a sorted-lookup gather with NO output
      expansion — static shapes, MXU/VPU-friendly (SURVEY.md §2.10 P3).
    - `unique=False` (m:n): sorted-range lookup (lo/hi searchsorted) +
      cumsum slot assignment expands matches into an `out_capacity`-row
      batch; the true output size is reported in the program's extras so
      the dispatcher can regrow and retry (the paging discipline,
      SURVEY.md §5.7).  This replaces the reference's multi-match hash
      probe (join/hash_join_v2.go) — range-gather beats hash tables on TPU.

    The build side arrives as auxiliary program inputs (host-materialized,
    replicated to every device), in one of two forms that copr/joinbuild.py
    `prepare_build` chooses from what the build side IS:

    - sorted (`dense=False`): aux[0] = build keys ascending (int32 where
      both sides prove it, else int64), aux[1] = permutation into build
      rows (the identity: the columns come sorted too; only the expanding
      path reads it), aux[2:] = build columns.  A probe is a binary
      search plus one gather a column.
    - direct-addressed (`dense=True`): the unique build keys cover a
      range little longer than their count (every TPC-H primary key), so
      `key - base` IS the build row.  aux[0] = base, aux[1] = each
      column's minimum, then `packing[0]` int32 word tables over the key
      range that hold every build column that fits (value - minimum, a
      validity bit where it has NULLs, one presence bit where the range
      has holes), then the columns too wide to pack.  A probe is one
      subtraction, one bounds check and ONE gather a word: `packing` =
      (n_words, presence bit | -1, ((word, shift, bits, validity bit |
      -1, wide), ...) per build column); `word` -1 for a column carried
      apart, -2 for the key column and -3 for a column nothing above the
      join reads, which are not carried at all.

    Output schema = probe schema ++ build columns (probe schema only for
    semi/anti); `kind` inner|left|semi|anti."""
    child: CopNode = None  # type: ignore[assignment]
    probe_key: Expr = None  # type: ignore[assignment]
    kind: str = "inner"
    build_dtypes: Tuple[dt.DataType, ...] = ()
    unique: bool = True
    out_capacity: int = 0          # unique=False only
    null_aware: bool = False       # anti only: NOT IN semantics
    # which aux GROUP carries this join's build side: a fused program may
    # chain several broadcast joins (the fragment tree cut at broadcast
    # exchanges, physicalop/fragment.go analog) — each join level reads
    # its own (sorted keys, perm, build columns) group
    aux_slot: int = 0
    # runtime strategy, like `unique`: set by the executor once the build
    # side is in hand (rewrite_lookup), never by the planner
    dense: bool = False
    packing: tuple = ()
    # unique inner/left under an aggregation only, set by the executor
    # from the planner's row estimate of the probe child: the slots a
    # device compacts its live probe rows to before the lookup, in no
    # order (copr/join.live_rows), so the gather costs the rows a filter
    # left and not the rows scanned.  0 = every slot is looked up.  Live
    # rows that do not fit are never dropped: the program reports the
    # capacity they take (extras `join_need`) and the dispatcher reruns
    # the statement at 0.
    probe_capacity: int = 0
    # unique inner under an aggregation only, set by the executor where
    # the filters beneath the join keep too much for `probe_capacity`
    # but the build side keeps little (a filtered dimension: TPC-H Q3
    # looks up 54 % of `lineitem` in a tenth of `orders`): the slots a
    # device compacts the join's OUTPUT, its matched rows, to, after
    # the lookup, so that what is above (the next filter, a sorted
    # GROUP BY) costs the rows that joined and not the rows scanned.
    # The same compaction, reports and rerun as `probe_capacity`; a
    # program has one or the other.
    match_capacity: int = field(default=0, metadata=DIGEST_IF_SET)
    # unique direct-addressed inner/left only, set by the executor where
    # ANALYZE found the probe key's column in key order and nothing has
    # compacted the probe rows (`probe_window_for`): the table is read
    # by windows of this many slots (and a lane), one a block of
    # COMPACT_COLUMNS probe rows, not by an index a row
    # (copr/join._window_reader).  0 = the gather.  The order is a hint:
    # the program counts the rows whose offset fell outside their window
    # (extras `join_window_miss`) and the dispatcher reruns the
    # statement at 0 where there is one.
    probe_window: int = field(default=0, metadata=DIGEST_IF_SET)
    # a build past the planner's broadcast cap that stays where it lives,
    # set by the executor (unique direct-addressed inner/left only): the
    # aux group has a leading device axis and is sharded over the mesh,
    # each device holding the table of the keys it owns, and after the
    # tables the partition that says which device owns a key and where
    # its slot is (copr/joinbuild.key_partition,
    # `parallel/exchange.key_places`).  False = the group is replicated.
    sharded: bool = field(default=False, metadata=DIGEST_IF_SET)
    # `sharded` on a mesh of several devices: the slots of one bucket a
    # destination.  A live probe row whose key another device owns
    # travels there (`parallel/exchange.exchange_rows`: one column sort
    # of the device's slots where the buckets together take at most half
    # of them, 3.6 ms for 2^24 slots on a v5e, else one a destination,
    # 40.8; then a short sort a destination over the slots kept, one
    # stacked gather, one all-to-all) and is looked up where the table
    # is; a row whose key the device owns itself is looked up in place.
    # Rows that do not fit are never dropped: the program reports the
    # capacity they take (extras `exchange_need`) and the dispatcher
    # reruns the statement with it.  0 = nothing is exchanged (one
    # device).
    exchange: int = field(default=0, metadata=DIGEST_IF_SET)

    def children(self):
        return (self.child,)


@dataclass(frozen=True)
class FusedDag(CopNode):
    """Multi-payload device program root: N member chains sharing one scan.

    Reference analog: shared-scan / multi-query optimization in compiled
    engines (Flare compiles shared work into one native kernel instead of
    re-executing it per query).  The admission scheduler groups queued
    cop tasks whose chains read the SAME snapshot scan (identical stacked
    device inputs, same mesh) but differ in filters/aggregates, and fuses
    them into ONE program whose output is a tuple with one leaf per
    member — the scan's HBM pass is paid once and XLA CSEs the shared
    subtrees (flatten, masks, common predicates) across members.

    Members must each be fully in-program aggregation chains (the
    contract class checked by analysis.contracts.fusion_signature); the
    node is frozen so the fused program caches on its digest exactly
    like any other cop DAG."""
    members: Tuple[CopNode, ...] = ()

    def children(self):
        return self.members


@dataclass(frozen=True)
class WindowShuffleSpec:
    """Device window-function program spec.

    Reference analog: TiFlash's MPP window execution — an exchange hash-
    partitioned on PARTITION BY feeds per-node sort + window operators
    (cophandler/mpp_exec.go window path, executor/window.go semantics).
    TPU redesign: the scan chain runs per device, rows hash-partition
    over the mesh by PARTITION BY keys via lax.all_to_all, each device
    multi-key-sorts its partitions once and computes every window item
    with segment ops — ONE shard_map program, exchange bytes on ICI.

    `items` is a tuple of (func, arg_expr_or_None, out_dtype);
    supported funcs: row_number | rank | dense_rank (need ORDER BY) and
    count | sum | min | max | avg over the WHOLE partition (no ORDER BY,
    default unbounded frame).  Output schema: child columns ++ one
    column per item (row order unspecified, like any unordered SELECT)."""
    child: CopNode
    partition_keys: Tuple = ()      # (Expr, ...) over child output
    order_keys: Tuple = ()          # ((Expr, desc), ...)
    items: Tuple = ()               # ((func, arg, out_dtype), ...)


@dataclass(frozen=True)
class ShuffleJoinSpec:
    """Cross-device repartition (shuffle) hash join program spec.

    Reference analog: the MPP HashPartition exchange + hash join
    (physicalop/physical_exchange_sender.go:109, executor/shuffle.go:86).
    TPU redesign: both sides' scan chains run per device, rows hash-
    partition over the mesh via lax.all_to_all (parallel/exchange.py), then
    each device runs the sorted-range expand join on its partition and the
    `top` chain (selection/projection/agg/topn/limit) over the join output
    — all inside ONE shard_map program, so exchange bytes ride ICI.

    `left`/`right` are CopNode chains rooted at their own TableScans;
    `left_key`/`right_key` are int64-comparable exprs over each chain's
    output.  `top`'s leaf TableScan reads the joined schema
    (left_dtypes ++ right_dtypes; probe side only for semi/anti)."""
    left: CopNode
    right: CopNode
    left_key: Expr
    right_key: Expr
    kind: str                       # inner | left | semi | anti
    left_dtypes: Tuple[dt.DataType, ...]
    right_dtypes: Tuple[dt.DataType, ...]
    top: CopNode


def output_dtypes(node: CopNode) -> Tuple[dt.DataType, ...]:
    """Schema of a node's output batch/states."""
    if isinstance(node, TableScan):
        return node.col_dtypes
    if isinstance(node, (Selection, Limit)):
        return output_dtypes(node.child)
    if isinstance(node, TopN):
        return output_dtypes(node.child)
    if isinstance(node, Expand):
        return (output_dtypes(node.child)
                + tuple(k.dtype.with_nullable(True) for k in node.keys)
                + (dt.bigint(False),))
    if isinstance(node, Projection):
        return tuple(e.dtype for e in node.exprs)
    if isinstance(node, Aggregation):
        return tuple(a.out_dtype for a in node.aggs)
    if isinstance(node, LookupJoin):
        if node.kind in ("semi", "anti"):
            return output_dtypes(node.child)
        return output_dtypes(node.child) + node.build_dtypes
    if isinstance(node, FusedDag):
        # one payload per member; the scheduler demuxes leaves, nothing
        # downstream consumes a concatenated schema
        return tuple(t for m in node.members for t in output_dtypes(m))
    raise TypeError(node)


def iter_nodes(node: CopNode):
    """Every node of a pushed DAG, root first (pre-order).  The static
    passes (analysis/contracts, copcost, lifetime) walk DAGs constantly;
    one shared iterator keeps their traversal order identical."""
    stack = [node]
    while stack:
        n = stack.pop()
        yield n
        stack.extend(n.children())


@functools.lru_cache(maxsize=1024)
def lookup_joins(node) -> tuple:
    """Every LookupJoin of a pushed DAG (a FusedDag's members included),
    root first; empty for a program that joins nothing.  Cached: the
    scheduler asks on every launch, the program namer on every build."""
    if not isinstance(node, CopNode):
        return ()
    return tuple(n for n in iter_nodes(node) if isinstance(n, LookupJoin))


def build_columns_read(root: CopNode, join: LookupJoin):
    """Which of `join`'s build columns the DAG above it reads, as a
    tuple of bools a build column; None where all of them leave the
    program (a rows root, or nothing above the join that names its
    columns).  What is not read need not ride in a build side's words
    (copr/joinbuild `UNREAD`): TPC-H Q3's `orders` build comes with the
    two customer keys it was joined on, which nothing above reads."""
    from ..expr.ir import referenced_columns
    path = []                   # root .. the join's parent

    def find(node):
        if node == join:        # (`lookup_joins` hands out equal nodes)
            return True
        if isinstance(node, FusedDag):
            return False
        for c in node.children():
            path.append(node)
            if find(c):
                return True
            path.pop()
        return False
    if not find(root) or join.kind in ("semi", "anti"):
        return None
    if not path or not isinstance(path[0], Aggregation):
        return None             # rows leave the program as they are
    # liveness, from the root down to the join: the columns of each
    # node's input that what is above it reads
    root_agg = path[0]
    read = set()
    for e in tuple(root_agg.group_by) + tuple(
            a.arg for a in root_agg.aggs if a.arg is not None):
        read |= referenced_columns(e)
    for node in path[1:]:
        if isinstance(node, Selection):
            for e in node.conditions:
                read |= referenced_columns(e)
        elif isinstance(node, Projection):
            below: set = set()
            for i in read:
                below |= referenced_columns(node.exprs[i])
            read = below
        else:                   # a join above, a TopN, a Limit, an Expand
            return None
    n_probe = len(output_dtypes(join.child))
    return tuple(n_probe + j in read for j in range(len(join.build_dtypes)))


def _same_expr(a: Expr, b: Expr) -> bool:
    """Are the two expressions the same structure over the same columns
    (a ColumnRef's name is for reading, and its dtype's NULL flag is
    what the node beneath it says)?"""
    from ..expr.ir import ColumnRef, Func
    if isinstance(a, ColumnRef) or isinstance(b, ColumnRef):
        return isinstance(a, ColumnRef) and isinstance(b, ColumnRef) \
            and a.index == b.index
    if isinstance(a, Func) and isinstance(b, Func):
        return a.op == b.op and a.dtype == b.dtype \
            and len(a.args) == len(b.args) \
            and all(_same_expr(x, y) for x, y in zip(a.args, b.args))
    return type(a) is type(b) and a == b


def with_dependent_keys(agg: CopNode) -> CopNode:
    """`agg` with the group keys marked that the other group keys
    determine (`Aggregation.dependent`), or `agg` itself where there is
    none or it is no grouped aggregation.  Pure, like `topn_block_len`.

    Group key j is dependent when every column it reads is a build
    column of a LookupJoin beneath the aggregation that is unique (inner
    or left: a probe row meets one build row or none, and then a NULL)
    and whose probe key is, structurally, another group key that is not
    itself dependent: TPC-H Q3 groups by `l_orderkey, o_orderdate,
    o_shippriority` above `l_orderkey = o_orderkey`.  An expression over
    such columns is dependent too.  A multimatch, semi or anti join, a
    probe key that is not grouped, and anything but Selections,
    Projections and joins between the aggregation and its scan mark
    nothing.  `unique` has to be what the run found of the build side
    (`copr/joinbuild`), not a schema's word: the executor calls this
    once it knows."""
    import dataclasses

    from ..expr.ir import referenced_columns, substitute_columns
    if not isinstance(agg, Aggregation) or not agg.group_by:
        return agg
    # walking down from the aggregation: a key as an expression over the
    # node's output until it has read a build column (None from then
    # on), the columns of the node's output it still reads, the joins
    # (as places in `joins`) whose build columns it read; a join: itself
    # and the keys that are its probe key
    exprs: list = list(agg.group_by)
    cols = [referenced_columns(e) for e in exprs]
    reads: list = [[] for _ in exprs]
    joins: list = []
    node = agg.child
    while not isinstance(node, TableScan):
        if isinstance(node, Projection):
            cols = [set().union(*(referenced_columns(node.exprs[i])
                                  for i in c)) for c in cols]
            exprs = [None if e is None
                     else substitute_columns(e, node.exprs)
                     for e in exprs]
        elif isinstance(node, LookupJoin):
            n_probe = len(output_dtypes(node.child))
            for j, c in enumerate(cols):
                if any(i >= n_probe for i in c):
                    reads[j].append(len(joins))
                    exprs[j] = None
                    cols[j] = {i for i in c if i < n_probe}
            joins.append((node, [j for j, e in enumerate(exprs)
                                 if e is not None
                                 and _same_expr(e, node.probe_key)]))
        elif not isinstance(node, Selection):
            return agg
        node = node.child

    known: dict = {}

    def dependent(j: int) -> bool:
        if j not in known:
            known[j] = bool(reads[j]) and not cols[j] and all(
                join.unique and join.kind in ("inner", "left")
                and any(not dependent(m) for m in probed)
                for join, probed in (joins[at] for at in reads[j]))
        return known[j]
    marked = tuple(j for j in range(len(exprs)) if dependent(j))
    if marked == agg.dependent:
        return agg
    return dataclasses.replace(agg, dependent=marked)


def groups_whole(agg: CopNode) -> bool:
    """Does every device of a launch of `agg` hold its groups whole (no
    group has rows on another device), because the one join of the DAG
    that exchanges its probe rows sends every row to the device that
    owns its probe key, and that key is, structurally, a group key?
    TPC-H Q3 groups by `l_orderkey` above `l_orderkey = o_orderkey`
    with `orders` sharded: a device may then rank its own groups
    (`GroupTopN.on_device`) and the host merges the devices' first
    groups.  An inner join only: a left join's NULL keys stay where
    they were scanned.  Pure."""
    from ..expr.ir import referenced_columns, substitute_columns
    if not isinstance(agg, Aggregation) or not agg.group_by \
            or sum(1 for j in lookup_joins(agg) if j.exchange) != 1:
        return False
    exprs: list = list(agg.group_by)
    node = agg.child
    while not isinstance(node, TableScan):
        if isinstance(node, Projection):
            exprs = [None if e is None else substitute_columns(e, node.exprs)
                     for e in exprs]
        elif isinstance(node, LookupJoin):
            n_probe = len(output_dtypes(node.child))
            exprs = [None if e is None or any(
                i >= n_probe for i in referenced_columns(e)) else e
                for e in exprs]
            if node.exchange:
                return node.kind == "inner" and any(
                    e is not None and _same_expr(e, node.probe_key)
                    for e in exprs)
        elif not isinstance(node, Selection):
            return False
        node = node.child
    return False


def find_expand_join(node: CopNode):
    """The (at most one) non-unique LookupJoin in a pushed DAG, or None —
    programs containing one report true join output size via extras."""
    if isinstance(node, LookupJoin) and not node.unique \
            and node.kind in ("inner", "left"):
        return node
    for c in node.children():
        found = find_expand_join(c)
        if found is not None:
            return found
    return None


def compacting_join(node: CopNode):
    """The LookupJoin of a pushed DAG that compacts its probe rows or
    its matched rows (`probe_capacity` or `match_capacity` > 0; the
    executor sets one, on one join), or None."""
    # walked, not `lookup_joins`: its cache hashes the whole DAG, and the
    # dispatcher asks this of a DAG the executor has just rebuilt
    return next((n for n in iter_nodes(node) if isinstance(n, LookupJoin)
                 and compact_capacity(n)), None)


def compact_capacity(join: LookupJoin) -> int:
    """The slots the join compacts to, before or after its lookup."""
    return join.probe_capacity or join.match_capacity


def uncompacted(node: CopNode) -> CopNode:
    """The DAG with its compacting join, wherever in a chain it sits,
    looking up every slot and keeping every slot: today's exact
    program."""
    return rewrite_lookup(node, pred=lambda j: compact_capacity(j) > 0,
                          probe_capacity=0, match_capacity=0)


def windowed_join(node: CopNode):
    """A LookupJoin of a pushed DAG that reads its table by windows
    (`probe_window` > 0), or None."""
    return next((n for n in iter_nodes(node) if isinstance(n, LookupJoin)
                 and n.probe_window), None)


def unwindowed(node: CopNode) -> CopNode:
    """The DAG with every lookup a gather: the exact program whatever
    order the probe rows lie in."""
    while windowed_join(node) is not None:      # one join a rewrite
        node = rewrite_lookup(node, pred=lambda j: j.probe_window > 0,
                              probe_window=0)
    return node


def exchanging_join(node: CopNode):
    """A LookupJoin of a pushed DAG whose probe rows travel to the
    device that owns their key (`exchange` > 0), or None."""
    return next((n for n in iter_nodes(node) if isinstance(n, LookupJoin)
                 and n.exchange), None)


def has_extras(node: CopNode) -> bool:
    """Does a program of this DAG return an extras dict after its result
    (DeviceBatch.extras): the true size of an expanding join's output
    (`join_total`), the live rows a compacting join found and the
    capacity they take (`join_live`, `join_need`), the rows a lookup
    read by windows found outside theirs (`join_window_miss`), the slots
    an exchange's fullest bucket takes (`exchange_need`, with
    `exchange_sent`, the rows a device sent)?  The
    dispatcher reruns the statement where a size exceeds its capacity
    or a row was missed."""
    return find_expand_join(node) is not None \
        or compacting_join(node) is not None \
        or windowed_join(node) is not None \
        or exchanging_join(node) is not None


def to_multimatch(node: CopNode, out_capacity: int) -> CopNode:
    """Rebuild the DAG with its LookupJoin switched to the non-unique
    (expanding) strategy — the dispatcher's runtime answer to discovering
    duplicate build keys (the reference decides hash-probe shape from NDV
    the same way, join/hash_join_v2.go build-side stats)."""
    import dataclasses
    if isinstance(node, LookupJoin):
        return dataclasses.replace(node, unique=False,
                                   out_capacity=out_capacity,
                                   probe_capacity=0, match_capacity=0,
                                   probe_window=0)
    if not node.children():
        return node
    kids = tuple(to_multimatch(c, out_capacity) for c in node.children())
    if isinstance(node, (Selection, Projection, Expand, Limit, TopN,
                         Aggregation)):
        return dataclasses.replace(node, child=kids[0])
    return node


def rewrite_lookup(node: CopNode, pred=None, **changes) -> CopNode:
    """Rebuild the DAG with the (pred-matching) LookupJoin's fields
    replaced (runtime strategy switches: multi-match regrow etc.)."""
    import dataclasses
    if isinstance(node, LookupJoin) and (pred is None or pred(node)):
        return dataclasses.replace(node, **changes)
    if not node.children():
        return node
    kids = tuple(rewrite_lookup(c, pred, **changes)
                 for c in node.children())
    if isinstance(node, (Selection, Projection, Expand, Limit, TopN,
                         Aggregation, LookupJoin)):
        return dataclasses.replace(node, child=kids[0])
    return node


def drop_lookup(node: CopNode, keep: bool) -> CopNode:
    """Replace the semi/anti LookupJoin with its probe chain outright:
    `keep=True` passes every probe row (anti vs an empty build),
    `keep=False` passes none (NOT IN with a NULL build key) via a
    constant-false Selection.  Exact — no sentinel keys that could
    collide with real data."""
    import dataclasses

    from ..expr.ir import Const
    if isinstance(node, LookupJoin):
        if keep:
            return node.child
        return Selection(node.child, (Const(dt.bigint(False), 0),))
    if not node.children():
        return node
    kids = tuple(drop_lookup(c, keep) for c in node.children())
    if isinstance(node, (Selection, Projection, Expand, Limit, TopN,
                         Aggregation, LookupJoin)):
        return dataclasses.replace(node, child=kids[0])
    return node


def rewrite_expand_capacity(node: CopNode, new_cap: int) -> CopNode:
    """Rebuild the DAG with the non-unique LookupJoin's out_capacity
    replaced (the dispatcher's regrow-and-retry step)."""
    return rewrite_lookup(node, pred=lambda j: not j.unique,
                          out_capacity=new_cap)


def chain_str(node: CopNode) -> str:
    """Compact fragment chain for EXPLAIN, leaf first:
    'TableScan>Selection>Expand>Aggregation[sort]'."""
    parts = []
    cur = node
    while cur is not None:
        name = type(cur).__name__
        if isinstance(cur, Aggregation):
            name += f"[{cur.strategy.value}]"
        parts.append(name)
        kids = cur.children()
        cur = kids[0] if kids else None
    return ">".join(reversed(parts))


def dag_digest(node: CopNode) -> int:
    """Stable-ish digest used as the jit-compile cache key together with the
    shard capacity bucket (SURVEY.md §A.6)."""
    return hash(node)


__all__ = [
    "AggFunc", "AggDesc", "CopNode", "TableScan", "Selection", "Projection",
    "Expand", "GroupStrategy", "Aggregation",
    "TopN", "TOPN_MIN_BLOCK", "topn_block_len",
    "COMPACT_COLUMNS", "probe_capacity_for", "exchange_capacity_for",
    "exchange_capacity_round", "exchanging_join", "groups_whole",
    "PROBE_WINDOW_MAX", "probe_window_for", "probe_scan_column",
    "window_ok", "windowed_join", "unwindowed",
    "Limit", "LookupJoin",
    "FusedDag", "ShuffleJoinSpec", "output_dtypes", "dag_digest",
    "iter_nodes", "lookup_joins", "find_expand_join", "compacting_join",
    "compact_capacity", "build_columns_read", "with_dependent_keys",
    "uncompacted", "has_extras",
    "rewrite_lookup",
    "drop_lookup",
    "chain_str", "rewrite_expand_capacity",
]
