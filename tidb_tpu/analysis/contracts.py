"""Plan-contract verification: typed-IR checks BEFORE tracing.

Reference analog: the typed IR verification compiler-first query engines
run between planning and codegen (Flare's native pipeline for Spark; the
LA-rewrite checks of "Accelerating Machine Learning Queries with Linear
Algebra Query Processing").  Here the "IR" is the physical plan tree
(executor/physical.py operators) plus the pushed cop DAG (copr/dag.py);
the contract of each operator is its declared output schema, locality
(traceable-dense device program vs host numpy operator), sharding spec,
and static capacity shape.

The verifier walks a built plan edge-by-edge and rejects inconsistencies
with a structured PlanContractError — a PlanError subclass, so the
session surfaces it like any other planner rejection, crucially *before*
`jax.jit` tracing starts (where the same bug would surface as a shape
error five layers deep, or not at all):

- column references must be in range and dtype-consistent with the child
  operator's declared output schema,
- dtype changes only through declared `cast` nodes (no silent promotion
  riding jnp broadcasting rules),
- device DAG nodes must be traceable-dense: no host-object (wide
  decimal / vector) columns, no unlowered string constants, only
  device-whitelisted ops,
- aggregation capacity shapes must be well-formed (DENSE domain sizes
  aligned with group keys, SORT group capacity sane),
- exchange boundaries must agree: a shuffle-join spec's per-side schemas
  and its post-join `top` chain's leaf scan must describe the same
  columns (the mesh/sharding handshake of an MPP exchange),
- sched admission (verify_task): stacked device input shapes must match
  the task key's capacity signature and divide over the mesh — the
  precondition for batch-slot coalescing to be shape-safe.

Checks are structural and cheap (no device touch, no jax import); DAG
verification is memoized on the frozen DAG node itself.
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

from ..copr import dag as D
from ..expr.ir import ColumnRef, Const, Expr, Func
from ..planner.build import PlanError
from ..types import dtypes as dt

K = dt.TypeKind


class PlanContractError(PlanError):
    """A built plan violates an operator contract.

    Raised by the verifier before any tracing/compilation; carries the
    violated rule, the operator path from the plan root, and a detail
    message so tests and EXPLAIN can assert on structure, not text."""

    def __init__(self, rule: str, path: Sequence[str], detail: str):
        self.rule = rule
        self.path = tuple(path)
        self.detail = detail
        super().__init__(
            f"plan contract violation [{rule}] at "
            f"{' > '.join(self.path) or '<root>'}: {detail}")


def _fail(rule: str, path, detail: str):
    raise PlanContractError(rule, path, detail)


# --------------------------------------------------------------------- #
# dtype compatibility
# --------------------------------------------------------------------- #

def _family(t: Optional[dt.DataType]) -> str:
    """Coarse representation family: what the value IS on device/host.
    Promotion across families without a declared cast is the silent-
    promotion hazard this gate exists to catch."""
    if t is None:
        return "?"
    if t.is_host_object:
        return "obj"
    if t.is_string:
        return "str"
    if t.kind in (K.FLOAT64, K.FLOAT32):
        return "float"
    return "int"    # ints, scaled decimal64, temporal, enum/set/bit, null


def _compatible(declared: dt.DataType, actual: dt.DataType) -> bool:
    """A ColumnRef's declared dtype vs the producing schema slot.
    Nullability and collation may legitimately drift through rewrites
    (outer-join null extension, collation coercion); kind and physical
    representation may not."""
    if declared.kind == K.NULL or actual.kind == K.NULL:
        return True       # untyped NULL literal slots match anything
    if declared.kind == actual.kind:
        if declared.kind == K.DECIMAL:
            # scaled-int encoding: a scale mismatch reads 1.00 as 100
            return (declared.scale == actual.scale
                    or declared.is_wide_decimal != actual.is_wide_decimal)
        return True
    # distinct kinds: allowed only within one physical family (e.g.
    # DATE read as bigint by a fold) — never int<->float or <->object
    return (_family(declared) == _family(actual)
            and declared.np_dtype() == actual.np_dtype())


# arithmetic ops: int/float/decimal mixing IS declared in this engine —
# the evaluator rescales/promotes from the arg dtypes and the inferred
# result dtype (expr/builders._arith_result_type + expr/compile.py).
# The undeclared promotion the verifier rejects is arithmetic that
# consumes STRING-family args while producing a NON-string: dictionary
# codes are arbitrary ordinals, and the planner routes string operands
# into numeric arithmetic only through a declared cast (or dict_lut).
# String-OUT code arithmetic is the legitimate dictionary-lowering idiom
# (lower_strings combines codes as code1*K+code2 with a derived output
# dictionary) and passes.
_ARITH_OPS = frozenset({"add", "sub", "mul", "div", "intdiv", "mod"})


def _check_expr(e: Expr, schema: Tuple[dt.DataType, ...], path,
                device: bool = False, lowered: bool = False) -> None:
    """One expression tree against its input schema.  `device=True` adds
    the traceable-dense rules (whitelisted ops, lowered strings, no
    host-object values).  `lowered=True` marks a subtree under a
    dict_map/dict_lut (or a node carrying a derived dictionary): there
    the dictionary-lowering idiom legitimately treats codes as ints."""
    if isinstance(e, ColumnRef):
        if not (0 <= e.index < len(schema)):
            _fail("column-ref", path,
                  f"{e} references column {e.index} of a "
                  f"{len(schema)}-column input")
        if not _compatible(e.dtype, schema[e.index]):
            _fail("dtype-mismatch", path,
                  f"{e} declares {e.dtype} but the input schema produces "
                  f"{schema[e.index]} at column {e.index}")
        if device and e.dtype.is_host_object:
            _fail("host-object-on-device", path,
                  f"{e} ({e.dtype}) is a host object array and cannot be "
                  "stacked into device shards")
        return
    if isinstance(e, Const):
        if device and isinstance(e.value, str):
            _fail("unlowered-string", path,
                  f"raw string constant {e.value!r} reached a device "
                  "expression (dictionary lowering did not apply)")
        return
    if isinstance(e, Func):
        if device:
            from ..executor.physical import DEVICE_OPS
            if e.op not in DEVICE_OPS:
                _fail("op-not-device", path,
                      f"op {e.op!r} is not in the device capability "
                      "registry but was pushed into a cop DAG")
            if e.dtype is not None and e.dtype.is_host_object:
                _fail("host-object-on-device", path,
                      f"{e.op} produces {e.dtype}, a host-object type")
        if e.op in _ARITH_OPS and not lowered \
                and _family(e.dtype) != "str":
            for a in e.args:
                if a.dtype is not None and _family(a.dtype) == "str":
                    _fail("undeclared-promotion", path,
                          f"{e.op} produces {e.dtype} from a string-"
                          f"family argument ({a.dtype}) without a "
                          "declared cast — dictionary codes are not "
                          "numbers")
        sub_lowered = (lowered or e.op in ("dict_map", "dict_lut")
                       or getattr(e, "_derived_dict", None) is not None)
        for a in e.args:
            _check_expr(a, schema, path, device, sub_lowered)


# --------------------------------------------------------------------- #
# device DAG verification (memoized on the frozen DAG)
# --------------------------------------------------------------------- #

def verify_dag(root: D.CopNode) -> None:
    """Verify a pushed cop DAG bottom-up.  Memoized: DAG nodes are frozen
    dataclasses (they already key the jit-program cache), so repeated
    admission of the same program costs one dict hit."""
    _verify_dag_cached(root)


@functools.lru_cache(maxsize=1024)
def _verify_dag_cached(root: D.CopNode) -> bool:
    _verify_dag(root, ())
    return True


def _verify_dag(node: D.CopNode, path) -> None:
    p = path + (type(node).__name__,)
    for c in node.children():
        if c is None:
            _fail("arity", p, "missing child node")
        _verify_dag(c, p)

    if isinstance(node, D.TableScan):
        if len(node.col_offsets) != len(node.col_dtypes):
            _fail("arity", p,
                  f"{len(node.col_offsets)} column offsets vs "
                  f"{len(node.col_dtypes)} dtypes")
        if any(o < 0 for o in node.col_offsets):
            _fail("column-ref", p, "negative column offset")
        for t in node.col_dtypes:
            if t.is_host_object:
                _fail("host-object-on-device", p,
                      f"scan reads {t}, a host-object column that never "
                      "ships to device")
        return

    schema = D.output_dtypes(node.children()[0]) if node.children() else ()

    if isinstance(node, D.Selection):
        for cond in node.conditions:
            _check_expr(cond, schema, p, device=True)
    elif isinstance(node, D.Projection):
        if not node.exprs:
            _fail("arity", p, "projection with no expressions")
        for e in node.exprs:
            _check_expr(e, schema, p, device=True)
    elif isinstance(node, D.Expand):
        if node.levels < 1 or node.levels > len(node.keys) + 1:
            _fail("capacity-shape", p,
                  f"levels={node.levels} out of range for "
                  f"{len(node.keys)} rollup keys")
        for e in node.keys:
            _check_expr(e, schema, p, device=True)
    elif isinstance(node, D.Aggregation):
        for g in node.group_by:
            _check_expr(g, schema, p, device=True)
        for a in node.aggs:
            if a.arg is not None:
                _check_expr(a.arg, schema, p, device=True)
            elif a.func not in (D.AggFunc.COUNT,):
                _fail("agg-arg", p, f"{a.func.value} requires an argument")
        if node.strategy == D.GroupStrategy.SCALAR:
            if node.group_by:
                _fail("capacity-shape", p,
                      "SCALAR aggregation with group-by keys")
        elif node.strategy == D.GroupStrategy.DENSE:
            if len(node.domain_sizes) != len(node.group_by):
                _fail("capacity-shape", p,
                      f"DENSE domain_sizes arity {len(node.domain_sizes)} "
                      f"!= group_by arity {len(node.group_by)}")
            if any(s <= 0 for s in node.domain_sizes):
                _fail("capacity-shape", p,
                      f"non-positive dense domain size in "
                      f"{node.domain_sizes}")
        elif node.strategy == D.GroupStrategy.SORT:
            if not node.group_by:
                _fail("capacity-shape", p, "SORT aggregation without keys")
            if node.group_capacity < 0:
                _fail("capacity-shape", p,
                      f"negative group capacity {node.group_capacity}")
        if node.pack_words not in ((0, 1, 2) if node.strategy
                                   == D.GroupStrategy.SORT else (0,)):
            _fail("capacity-shape", p,
                  f"pack_words {node.pack_words} on a "
                  f"{node.strategy.value} aggregation: a SORT record is "
                  "one or two words, or wide (0)")
        if node.dependent and not (
                set(node.dependent) < set(range(len(node.group_by)))
                and tuple(sorted(set(node.dependent))) == node.dependent):
            # a key the others determine: some key is left to determine
            _fail("capacity-shape", p,
                  f"dependent {node.dependent} of "
                  f"{len(node.group_by)} group keys: ascending indexes "
                  "of some of them, never all")
        if node.topn is not None:
            # which groups the consumer keeps: read by the lowering of a
            # host-merged table alone, over its own keys and aggregates
            if not node.host_merged:
                _fail("capacity-shape", p,
                      f"topn on a {node.strategy.value} aggregation")
            for kind, i, _desc in node.topn.keys:
                n = len(node.group_by if kind == "key" else node.aggs)
                if kind not in ("key", "agg") or not 0 <= i < n or (
                        kind == "agg" and node.aggs[i].func not in (
                            D.AggFunc.COUNT, D.AggFunc.SUM)):
                    _fail("capacity-shape", p,
                          f"topn key ({kind}, {i}) names no group key "
                          "and no COUNT or SUM of this aggregation")
            if node.topn.limit <= 0:
                _fail("capacity-shape", p,
                      f"topn limit {node.topn.limit}")
        if node.narrow_sums:
            # valueflow-proven single-word SUM states: only in-program
            # (psum-merged) strategies carry them, and only int/decimal
            # SUM slots qualify — a narrow float or COUNT slot would
            # trace a program whose state layout disagrees with the
            # merge/finalize contract
            if node.strategy not in (D.GroupStrategy.SCALAR,
                                     D.GroupStrategy.DENSE):
                _fail("capacity-shape", p,
                      f"narrow_sums on a {node.strategy.value} "
                      "aggregation: only SCALAR/DENSE (in-program psum) "
                      "states take the single-word layout")
            from ..types.dtypes import TypeKind as _K
            for i in node.narrow_sums:
                if i < 0 or i >= len(node.aggs):
                    _fail("capacity-shape", p,
                          f"narrow_sums index {i} out of range for "
                          f"{len(node.aggs)} aggregates")
                a = node.aggs[i]
                if a.func != D.AggFunc.SUM or a.arg is None \
                        or a.arg.dtype is None \
                        or a.arg.dtype.kind in (_K.FLOAT64, _K.FLOAT32):
                    _fail("capacity-shape", p,
                          f"narrow_sums index {i} is not an int/decimal "
                          "SUM: only limb-split SUM states have a narrow "
                          "twin")
    elif isinstance(node, D.TopN):
        keys = node.sort_keys or (((node.sort_key, node.desc),)
                                  if node.sort_key is not None else ())
        if not keys:
            _fail("arity", p, "TopN without sort keys")
        for e, _desc in keys:
            _check_expr(e, schema, p, device=True)
        if node.limit < 0:
            _fail("capacity-shape", p, f"negative limit {node.limit}")
    elif isinstance(node, D.Limit):
        if node.limit < 0:
            _fail("capacity-shape", p, f"negative limit {node.limit}")
    elif isinstance(node, D.LookupJoin):
        if node.kind not in ("inner", "left", "semi", "anti"):
            _fail("arity", p, f"unknown join kind {node.kind!r}")
        _check_expr(node.probe_key, schema, p, device=True)
        if not node.unique and node.out_capacity <= 0:
            _fail("capacity-shape", p,
                  "expanding (non-unique) lookup join without a positive "
                  "out_capacity")
        if node.aux_slot < 0:
            _fail("capacity-shape", p, f"negative aux_slot {node.aux_slot}")
        if node.dense:
            _verify_packing(node, p)
        elif node.packing:
            _fail("capacity-shape", p,
                  "a sorted lookup join that carries a packing")
        if node.probe_capacity:
            _verify_probe_capacity(node, p)
        if node.match_capacity:
            _verify_match_capacity(node, p)
        if node.probe_window:
            _verify_probe_window(node, p)
        if node.kind in ("inner", "left"):
            for t in node.build_dtypes:
                if t.is_host_object:
                    _fail("host-object-on-device", p,
                          f"broadcast build column of type {t}")


def _verify_probe_capacity(node: D.LookupJoin, p) -> None:
    """Contract of the probe compaction (executor/physical `_compacted`
    sets it, copr/exec `_compact_probe` reads it): only a unique
    inner/left lookup keeps one output row a probe row, so only it can
    look up its live rows alone; the capacity is whole rows of the
    compaction's column view; the kept rows come in no order, so the
    chain ends in an aggregation; and one join of a chain compacts, the
    lowest: the joins above it run on its slots already."""
    if node.probe_capacity < 0 or node.probe_capacity % D.COMPACT_COLUMNS:
        _fail("capacity-shape", p,
              f"probe_capacity {node.probe_capacity} is not a positive "
              f"multiple of {D.COMPACT_COLUMNS}")
    if not node.unique or node.kind not in ("inner", "left"):
        _fail("capacity-shape", p,
              "probe compaction on a join that is not a unique "
              "inner/left lookup")
    if next(name for name in p if name != "FusedDag") != "Aggregation":
        _fail("capacity-shape", p,
              "probe compaction under a root that reads the order of its "
              "rows: only an aggregation may sit above")
    if D.lookup_joins(node.child):
        _fail("capacity-shape", p,
              "probe compaction above another lookup join: the lowest "
              "join of a chain compacts")


def _verify_match_capacity(node: D.LookupJoin, p) -> None:
    """Contract of the compaction of a join's matched rows (executor/
    physical `_compacted` sets it, copr/exec `_exec_lookup_join` reads
    it): only an inner unique lookup drops the rows that found nothing;
    whole rows of the compaction's column view; the kept rows come in
    no order, so the chain ends in an aggregation; and a program
    compacts once, so its reports name one capacity."""
    if node.match_capacity < 0 or node.match_capacity % D.COMPACT_COLUMNS:
        _fail("capacity-shape", p,
              f"match_capacity {node.match_capacity} is not a positive "
              f"multiple of {D.COMPACT_COLUMNS}")
    if not node.unique or node.kind != "inner":
        _fail("capacity-shape", p,
              "match compaction on a join that is not a unique inner "
              "lookup")
    if next(name for name in p if name != "FusedDag") != "Aggregation":
        _fail("capacity-shape", p,
              "match compaction under a root that reads the order of its "
              "rows: only an aggregation may sit above")
    if node.probe_capacity or D.compacting_join(node.child) is not None:
        _fail("capacity-shape", p,
              "multiple compactions in one program: one join compacts, "
              "before its lookup or after it")


def _verify_probe_window(node: D.LookupJoin, p) -> None:
    """Contract of the window form (executor/physical `_windowed` sets
    it, copr/join `_window_reader` reads it): whole lanes, none above
    the cap; and only a lookup that `dag.window_ok` lets read by
    windows: unique, direct-addressed, probed with a column of the scan
    whose rows nothing has compacted out of their order."""
    if node.probe_window < 0 or node.probe_window % D.COMPACT_COLUMNS \
            or node.probe_window > D.PROBE_WINDOW_MAX:
        _fail("capacity-shape", p,
              f"probe_window {node.probe_window} is not a multiple of "
              f"{D.COMPACT_COLUMNS} up to {D.PROBE_WINDOW_MAX}")
    if not D.window_ok(node):
        _fail("capacity-shape", p,
              "a window-form lookup that is not a unique direct-addressed "
              "inner/left join probed, in scan order, with a scan column")


def _verify_packing(node: D.LookupJoin, p) -> None:
    """Contract of the direct-addressed build side (copr/joinbuild
    `_dense_group` writes it, copr/join `direct_lookup` reads it): only a unique
    inner/left join is addressed directly; one layout entry a build
    column; inside each int32 word no two fields overlap and none
    reaches the sign bit."""
    from ..copr.joinbuild import APART, KEY_ITSELF, UNREAD, WORD_BITS
    if not node.unique or node.kind not in ("inner", "left"):
        _fail("capacity-shape", p,
              "direct addressing on a join that is not a unique "
              "inner/left lookup")
    if len(node.packing) != 3:
        _fail("arity", p, "direct-addressed lookup join without a packing")
    n_words, pbit, layout = node.packing
    if len(layout) != len(node.build_dtypes):
        _fail("arity", p,
              f"packing lays out {len(layout)} build columns, the join "
              f"has {len(node.build_dtypes)}")
    used = [0] * n_words
    fields = [(0, pbit, 1)] if pbit >= 0 else []
    for w, shift, bits, vbit, _wide in layout:
        if w in (APART, KEY_ITSELF, UNREAD):
            continue
        fields.append((w, shift, bits))
        if vbit >= 0:
            fields.append((w, vbit, 1))
    for w, shift, bits in fields:
        if not 0 <= w < n_words or shift < 0 or shift + bits > WORD_BITS:
            _fail("capacity-shape", p,
                  f"packed field (word {w}, shift {shift}, {bits} bits) "
                  f"outside {n_words} words of {WORD_BITS} bits")
        mask = ((1 << bits) - 1) << shift
        if used[w] & mask:
            _fail("capacity-shape", p,
                  f"packed fields overlap in word {w}")
        used[w] |= mask


# --------------------------------------------------------------------- #
# physical-plan verification
# --------------------------------------------------------------------- #

def verify_plan(plan) -> int:
    """Walk a built physical plan and check every operator's declared
    contract against its children's.  Returns the number of operators
    checked; raises PlanContractError on the first violation.  Called
    from the session plan path (before any execute/trace) and from the
    analysis gate over the TPC-H plan corpus."""
    from ..executor import physical as X
    return _verify_op(plan, (), X)


def _schema_of(op) -> Tuple[dt.DataType, ...]:
    return tuple(op.out_dtypes)


def _verify_op(op, path, X) -> int:
    c = op.contract() if hasattr(op, "contract") else {}
    p = path + (c.get("op", type(op).__name__),)
    n = 1
    for child in getattr(op, "children", []) or []:
        if child is not None:
            n += _verify_op(child, p, X)

    out = tuple(c.get("out_dtypes", ()))
    names = tuple(c.get("out_names", ()))
    if names and out and len(names) != len(out):
        _fail("arity", p,
              f"{len(names)} output names vs {len(out)} output dtypes")

    if isinstance(op, X.CopTaskExec):
        verify_dag(op.dag)
        if isinstance(op.dag, D.Aggregation):
            want = len(op.key_meta) + len(op.dag.aggs)
            if names and len(names) != want:
                _fail("arity", p,
                      f"aggregation produces {want} columns "
                      f"({len(op.key_meta)} keys + {len(op.dag.aggs)} "
                      f"aggs) but the contract declares {len(names)}")
        else:
            dag_out = D.output_dtypes(op.dag)
            if out and len(out) != len(dag_out):
                _fail("arity", p,
                      f"DAG emits {len(dag_out)} columns but the "
                      f"contract declares {len(out)}")
            for i, (a, b) in enumerate(zip(out, dag_out)):
                if not _compatible(a, b):
                    _fail("dtype-mismatch", p,
                          f"output column {i}: contract declares {a}, "
                          f"DAG produces {b}")
    elif isinstance(op, X.CopJoinTaskExec):
        verify_dag(op.dag)
        builds = (op.builds if op.builds
                  else [{"exec": op.build_exec,
                         "key_index": op.build_key_index}])
        for b in builds:
            bx = b["exec"]
            if bx is None:
                _fail("arity", p, "broadcast join without a build plan")
            ki = b.get("key_index", 0)
            if not (0 <= ki < len(bx.out_dtypes)):
                _fail("column-ref", p,
                      f"build key index {ki} out of range for the "
                      f"{len(bx.out_dtypes)}-column build side")
        if op.fallback is not None:
            n += _verify_op(op.fallback, p, X)
    elif isinstance(op, X.CopShuffleJoinExec):
        n += _verify_shuffle_spec(op.spec, p)
    elif isinstance(op, X.HostSelection):
        schema = _schema_of(op.child)
        for cond in op.conditions:
            _check_expr(cond, schema, p)
    elif isinstance(op, X.HostProjection):
        schema = _schema_of(op.child)
        for e in op.exprs:
            _check_expr(e, schema, p)
    elif isinstance(op, (X.HostSort, X.HostTopN)):
        schema = _schema_of(op.child)
        for e, _desc in op.keys:
            _check_expr(e, schema, p)
    elif isinstance(op, X.HostHashJoin):   # + merge/index-lookup subclasses
        ls, rs = _schema_of(op.left), _schema_of(op.right)
        for lk, rk in op.eq_keys:
            if not (0 <= lk < len(ls)):
                _fail("column-ref", p, f"left join key {lk} out of range")
            if not (0 <= rk < len(rs)):
                _fail("column-ref", p, f"right join key {rk} out of range")
            lf, rf = _family(ls[lk]), _family(rs[rk])
            if lf != rf and "?" not in (lf, rf) \
                    and ls[lk].kind != K.NULL and rs[rk].kind != K.NULL:
                _fail("dtype-mismatch", p,
                      f"join keys disagree on representation family: "
                      f"{ls[lk]} vs {rs[rk]}")
        if out:
            if op.kind in ("semi", "anti"):
                want = len(ls)
            elif op.kind in ("inner", "left", "right", "cross"):
                want = len(ls) + len(rs)
            else:
                want = len(out)
            if len(out) != want:
                _fail("arity", p,
                      f"{op.kind} join of {len(ls)}+{len(rs)} columns "
                      f"declares {len(out)} outputs (expected {want})")
    elif isinstance(op, X.HostSetOp):
        kids = [k for k in op.children if k is not None]
        widths = {len(k.out_dtypes) for k in kids}
        if len(widths) > 1:
            _fail("arity", p,
                  f"set-operation children disagree on column count: "
                  f"{sorted(widths)}")
    return n


def _verify_shuffle_spec(spec: D.ShuffleJoinSpec, path) -> int:
    """Exchange-boundary agreement: both sides' chains, their declared
    schemas, the key exprs, and the post-exchange `top` chain must all
    describe the same columns — the mesh handshake of an MPP shuffle.
    The schema/boundary half lives in analysis/shardflow (the single
    source both this pass and the sharding-flow pass consume — thin
    delegation so the two passes report the same rule and never
    drift)."""
    p = path + ("ShuffleJoinSpec",)
    verify_dag(spec.left)
    verify_dag(spec.right)
    ls, rs = D.output_dtypes(spec.left), D.output_dtypes(spec.right)
    from .shardflow import verify_shuffle_boundary
    verify_shuffle_boundary(spec, path)
    _check_expr(spec.left_key, ls, p, device=True)
    _check_expr(spec.right_key, rs, p, device=True)
    verify_dag(spec.top)
    return 1


# --------------------------------------------------------------------- #
# sched admission verification (capacity-shape handshake)
# --------------------------------------------------------------------- #

def verify_task(task) -> None:
    """Admission-time contract check for a structured CopTask: the
    stacked device inputs must match the task key's capacity signature
    (the precondition for in-flight dedup and batch-slot coalescing to
    be shape-safe) and divide evenly over the mesh's shard axis.  Cheap:
    tuple/shape comparisons plus a memoized DAG walk — runs before the
    scheduler resolves (and thus traces/compiles) the program."""
    if task.key is None or task.dag is None:
        return
    p = ("sched", type(task.dag).__name__)
    verify_dag(task.dag)
    from ..sched.task import _shape_sig, mesh_fingerprint
    if task.key[1] != mesh_fingerprint(task.mesh):
        _fail("mesh-mismatch", p,
              "task key was built against a different mesh than the one "
              "it is being admitted to")
    if task.row_capacity < 0:
        _fail("capacity-shape", p,
              f"negative row capacity {task.row_capacity}")
    sig = _shape_sig(task.cols, task.counts)
    if task.key[3] != sig:
        _fail("capacity-shape", p,
              f"stacked input shapes {sig} disagree with the task key's "
              f"capacity signature {task.key[3]}")
    n_dev = int(task.mesh.devices.size)
    shapes = {tuple(v.shape[:2]) for v, _m in task.cols
              if getattr(v, "ndim", 0) >= 2}
    if len(shapes) > 1:
        _fail("capacity-shape", p,
              f"stacked columns disagree on (shards, capacity): "
              f"{sorted(shapes)}")
    for s, _cap in shapes:
        if n_dev and s % n_dev != 0:
            _fail("capacity-shape", p,
                  f"{s} shards do not divide over {n_dev} devices on the "
                  "shard axis")
    # sharding-flow handshake (analysis/shardflow): the task's mesh must
    # carry the exchange axis and its DAG must flow clean against the
    # mesh's typed-link topology (implicit reshards, merge routing,
    # psum limb-fence bound) — still pre-trace, still memoized
    from .shardflow import verify_task_sharding
    verify_task_sharding(task)
    # value-range handshake (analysis/valueflow): the task's DAG must
    # flow finite, int64-safe intervals — a digest the session proved at
    # plan time is a registry hit; an unknown digest re-flows from type
    # domains.  Still pre-trace, still memoized.
    from .valueflow import verify_task_values
    verify_task_values(task)
    if getattr(task, "donate", False):
        # donation-safety handshake (analysis/lifetime): a donating
        # task must be in an EPHEMERAL program class and its inputs
        # must not be live snapshot-cache residents
        from .lifetime import verify_task_donation
        verify_task_donation(task)


# --------------------------------------------------------------------- #
# cross-query fusion verification (the scheduler's fusion-group seam)
# --------------------------------------------------------------------- #

# rows-chain node kinds that may join a rows fusion group: pure scan
# chains only — joins bring aux inputs / extras the fused launch cannot
# carry per member
_ROWS_FUSABLE_NODES = (D.TableScan, D.Selection, D.Projection, D.Expand,
                       D.TopN, D.Limit)


def _rows_fusable(node: D.CopNode) -> bool:
    if not isinstance(node, _ROWS_FUSABLE_NODES):
        return False
    return all(_rows_fusable(c) for c in node.children())


def fusion_signature(dag: D.CopNode) -> Optional[tuple]:
    """Contract-level fusion class of a pushed cop DAG, or None when the
    plan cannot join a cross-query fusion group.  Structural only — no
    trace, no jax import: this is exactly the "checkable without tracing"
    substrate PR 2's contracts were built for.

    Fusable classes (all members of one group share the returned tuple):

    - ``('inprog-agg',)`` — an Aggregation whose whole merge happens
      in-program (SCALAR/DENSE) with no expanding join in the chain
      (extras drive a per-task regrow loop).
    - ``('sort-agg', group_capacity)`` — a SORT aggregation whose
      group-table capacity is a concrete power of two (the capacity-
      bucketed shape classes of the fusion-breadth follow-on: the
      client's regrow discipline only ever produces pow2 capacities,
      so regrow-sized tasks land in shared classes instead of none).
      Capacity 0 (planner left sizing to the client) or a non-pow2
      capacity has no static shape class and stays unfusable.  So does
      one with an exact sort record (`pack_words`, a TPU's planner
      alone sets it: copr/runagg): every member sorts its own records,
      so a fused program shares a scan of under a millisecond, takes
      the members' 25 ms each all the same, and compiles every sort
      again, tens of seconds a member in the serving path's
      background (PERF.md section 6, PR 29).
    - ``('rows',)`` — an extras-free pure scan chain returning rows
      (fusion-breadth follow-on): members fuse with per-member output
      capacities (spmd.FusedRowsProgram)."""
    if not isinstance(dag, D.Aggregation):
        if not _rows_fusable(dag):
            return None
        try:
            verify_dag(dag)
        except PlanContractError:
            return None
        return ("rows",)
    if D.find_expand_join(dag) is not None:
        return None
    if dag.strategy == D.GroupStrategy.SORT:
        cap = dag.group_capacity
        if cap <= 0 or (cap & (cap - 1)) != 0 or dag.pack_words:
            return None     # no static shape class to share
    try:
        verify_dag(dag)
    except PlanContractError:
        return None
    if dag.strategy == D.GroupStrategy.SORT:
        return ("sort-agg", dag.group_capacity)
    if dag.narrow_sums:
        # proven-narrow members only fuse with members proving the SAME
        # slots narrow: the fused leaves' state layouts (single word vs
        # limb pair) are baked into the traced program
        return ("agg-narrow", dag.narrow_sums)
    return ("inprog-agg",)


def verify_fusion_group(tasks: Sequence) -> None:
    """Pre-launch contract check of a fusion group: every member must be
    individually fusable and all members must agree on mesh fingerprint,
    capacity signature (stacked input shapes + dtypes), shared scan
    inputs, and empty aux — the preconditions for computing N payloads
    from one scan pass to be shape-safe AND bit-identical to N solo
    runs.  Raises PlanContractError; the scheduler falls back to
    unfused per-program launches on refusal."""
    p = ("sched", "FusedDag")
    if len(tasks) < 2:
        _fail("fusion-group", p, "fusion group needs >= 2 members")
    lead = tasks[0]
    lead_sig = fusion_signature(lead.dag) if lead.dag is not None else None
    for t in tasks:
        if t.key is None or t.dag is None:
            _fail("fusion-group", p, "opaque task in a fusion group")
        sig = fusion_signature(t.dag)
        if sig is None:
            _fail("fusion-class", p,
                  f"member {type(t.dag).__name__} is not in a fusable "
                  "contract class")
        if sig != lead_sig:
            # e.g. a SORT member whose capacity differs from the group's:
            # refuse loudly instead of silently degrading
            _fail("fusion-class", p,
                  f"member fusion signature {sig} disagrees with the "
                  f"group's {lead_sig} (incompatible strategy or "
                  "capacity)")
        if t.key[1] != lead.key[1]:
            _fail("mesh-mismatch", p,
                  "fusion group members were keyed against different "
                  "meshes")
        if t.key[3] != lead.key[3]:
            _fail("capacity-shape", p,
                  f"member capacity signature {t.key[3]} disagrees with "
                  f"the group's {lead.key[3]} (shapes/dtypes must be "
                  "byte-identical to share one scan)")
        if t.input_token != lead.input_token:
            _fail("fusion-input", p,
                  "members read different snapshot residents — a fused "
                  "program computes every payload from ONE scan")
        if t.aux != ():
            _fail("fusion-input", p,
                  "host-materialized aux inputs (join builds) do not "
                  "fuse across queries")


__all__ = ["PlanContractError", "verify_plan", "verify_dag", "verify_task",
           "fusion_signature", "verify_fusion_group"]
