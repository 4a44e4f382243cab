"""Cardinality estimation: predicate selectivity from table statistics.

Reference analog: pkg/planner/cardinality/ (selectivity.go, row_count_*.go)
with the pseudo-stats fallbacks of pseudoEqualRate/pseudoLessRate/
pseudoBetweenRate.  Works over the CNF condition lists the optimizer
collects at each DataSource; values are compared in the column's
order-preserving int64 encoding (stats/build.py).
"""

from __future__ import annotations

from typing import Optional

from ..expr.ir import ColumnRef, Const, Expr, Func
from ..stats.handle import TableStats, encode_value
from ..types.dtypes import TypeKind as K
from .ranger import _cmp_parts, _const_for

# reference: pkg/planner/cardinality/pseudo.go
PSEUDO_LESS_RATE = 3.0
PSEUDO_EQUAL_RATE = 1000.0
PSEUDO_BETWEEN_RATE = 40.0


def _col_meta(ds, ci: int):
    """(name, col_type, dictionary) for schema column ci of a DataSource."""
    name = ds.schema.cols[ci].name
    tbl = ds.table
    ti = tbl.col_names.index(name) if name in tbl.col_names else -1
    if ti < 0:
        return name, None, None
    col_type = tbl.col_types[ti]
    dictionary = None
    if col_type.is_string:
        try:
            dictionary = tbl.snapshot().columns[ti].dictionary
        except Exception:
            dictionary = None
    return name, col_type, dictionary


def _encoded(col_type, cst, dictionary) -> Optional[int]:
    """A constant in the column's order-preserving int64 domain, or None.
    The histogram of a DECIMAL column holds its SCALED ints; a decimal
    const carries its own scale and an integer literal none, so the const
    is brought to the column's scale exactly as the scan path does."""
    value = _const_for(col_type, cst) if col_type.kind == K.DECIMAL \
        else cst.value
    return encode_value(col_type, value, dictionary)


def _in_list(cond: Expr):
    """`col IN (const, ...)` as (column ref, constants), or None."""
    if isinstance(cond, Func) and cond.op == "in" \
            and isinstance(cond.args[0], ColumnRef) \
            and all(isinstance(a, Const) for a in cond.args[1:]):
        return cond.args[0], cond.args[1:]
    return None


def cond_selectivity(stats: Optional[TableStats], cond: Expr, ds) -> float:
    """Selectivity in (0, 1] of a single CNF conjunct."""
    listed = _in_list(cond)
    if listed is not None:
        ref, consts = listed
        return min(sum(cond_selectivity(
            stats, Func(cond.dtype, "eq", (ref, c)), ds)
            for c in consts), 1.0)
    p = _cmp_parts(cond)
    if p is None:
        return 0.8           # reference selectionFactor for opaque filters
    op, ci, cst = p
    name, col_type, dictionary = _col_meta(ds, ci)
    cs = stats.col(name) if stats is not None else None
    total = cs.count + cs.null_count if cs is not None else 0
    if cs is None or total == 0 or col_type is None:
        return (1.0 / PSEUDO_EQUAL_RATE if op == "eq"
                else 1.0 / PSEUDO_LESS_RATE)
    enc = _encoded(col_type, cst, dictionary)
    if enc is None:
        return 1.0 / PSEUDO_LESS_RATE
    if op == "eq" and dictionary is not None \
            and isinstance(cst.value, str) \
            and dictionary.code_of(cst.value) < 0:
        rows = 0.0      # a string no row holds ('AIR REG'): `enc` is its
        #                 place in the order, which serves the ranges
    elif op == "eq":
        rows = cs.equal_rows(enc)
    elif op in ("lt", "le"):
        rows = cs.range_rows(None, False, enc, op == "le")
    else:
        rows = cs.range_rows(enc, op == "ge", None, False)
    return min(max(rows / total, 1e-9), 1.0)


def _range_selectivity(stats: Optional[TableStats], ci: int, lows, highs,
                       ds) -> Optional[float]:
    """Selectivity of the interval the lower and upper bounds on column
    `ci` ((op, const) lists) leave, from the column's histogram; None
    where it cannot be read (no statistics, a constant that does not
    encode): the caller multiplies the sides instead."""
    name, col_type, dictionary = _col_meta(ds, ci)
    cs = stats.col(name) if stats is not None else None
    total = cs.count + cs.null_count if cs is not None else 0
    if cs is None or total == 0 or col_type is None:
        return None

    def tightest(side, pick):
        enc = []
        for op, cst in side:
            e = _encoded(col_type, cst, dictionary)
            if e is None:
                return None
            enc.append((e, op in ("ge", "le")))
        # of equal bounds the exclusive one is the tighter
        return pick(enc, key=lambda b: (b[0], b[1] if pick is min
                                        else not b[1]))
    lo, hi = tightest(lows, max), tightest(highs, min)
    if lo is None or hi is None:
        return None
    rows = cs.range_rows(lo[0], lo[1], hi[0], hi[1])
    return min(max(rows / total, 1e-9), 1.0)


def conds_selectivity(stats: Optional[TableStats], conds, ds) -> float:
    """Combined selectivity of a CNF list (independence assumption
    between columns, like the reference before its exponential-backoff
    correlation fix).  A lower and an upper bound on ONE column are one
    interval of its histogram, not two independent halves (`l_shipdate
    >= d and l_shipdate < d + 1 month` keeps a month, not a quarter of
    the table)."""
    sides: dict = {}
    rest = []
    for c in conds:
        p = _cmp_parts(c)
        if p is not None and p[0] != "eq":
            lows, highs = sides.setdefault(p[1], ([], []))
            (lows if p[0] in ("gt", "ge") else highs).append((p[0], p[2]))
            rest.append((c, p[1]))
        else:
            rest.append((c, None))
    s = 1.0
    merged = set()
    for ci, (lows, highs) in sides.items():
        if lows and highs:
            r = _range_selectivity(stats, ci, lows, highs, ds)
            if r is not None:
                s *= r
                merged.add(ci)
    for c, ci in rest:
        if ci not in merged:
            s *= cond_selectivity(stats, c, ds)
    return s


def est_scan_rows(stats: Optional[TableStats], conds, ds) -> float:
    n = ds.table.num_rows
    return n * conds_selectivity(stats, conds, ds)
