"""Expression IR — the serialized pushdown expression tree.

Reference analog: tipb.Expr (the protobuf expression tree TiDB ships to
coprocessors, built by pkg/expression `ToPB`) plus pkg/expression's
ScalarFunction/Column/Constant (expression.go:118).  Nodes are immutable and
hashable so a whole DAG digests to a cache key (the jit-compile cache analog
of copr/coprocessor_cache.go — SURVEY.md §A.6).

Types are resolved at construction time (planner-side), so the device
compiler (expr/compile.py) never guesses: every node carries its DataType,
decimal nodes carry (prec, scale).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Tuple

import numpy as np

from ..types import dtypes as dt


@dataclass(frozen=True)
class Expr:
    dtype: dt.DataType

    def children(self) -> Tuple["Expr", ...]:
        return ()


@dataclass(frozen=True)
class ColumnRef(Expr):
    """Reference to the i-th column of the executor's input schema
    (tipb ColumnRef carries an offset the same way)."""
    index: int = 0
    name: str = ""  # debug only

    def __str__(self) -> str:
        return self.name or f"col#{self.index}"


@dataclass(frozen=True)
class Const(Expr):
    """Literal, already encoded in device representation:
    DECIMAL → scaled int, DATE → days, STRING → raw str (lowered to dict
    codes / LUTs by copr binding, see expr/lower_strings.py)."""
    value: Any = None

    def __str__(self) -> str:
        return f"{self.value!r}"

    def __hash__(self):
        v = self.value
        if isinstance(v, np.ndarray):
            v = (v.shape, v.dtype.str, v.tobytes())
        return hash((self.dtype, v))

    def __eq__(self, other):
        if not isinstance(other, Const):
            return NotImplemented
        if isinstance(self.value, np.ndarray) or isinstance(other.value, np.ndarray):
            return (isinstance(self.value, np.ndarray)
                    and isinstance(other.value, np.ndarray)
                    and self.value.shape == other.value.shape
                    and bool((self.value == other.value).all())
                    and self.dtype == other.dtype)
        return (self.dtype, self.value) == (other.dtype, other.value)


@dataclass(frozen=True)
class Func(Expr):
    """Scalar function application (tipb.Expr with a ScalarFuncSig)."""
    op: str = ""
    args: Tuple[Expr, ...] = ()

    def children(self) -> Tuple[Expr, ...]:
        return self.args

    def __str__(self) -> str:
        return f"{self.op}({', '.join(map(str, self.args))})"


def walk(e: Expr):
    yield e
    for c in e.children():
        yield from walk(c)


def referenced_columns(e: Expr) -> set[int]:
    return {n.index for n in walk(e) if isinstance(n, ColumnRef)}


def clone_func(e: "Func", args) -> "Func":
    """Rebuild a Func with new args, preserving side-channel annotations
    (a dict_map's derived output dictionary) — EVERY plan rewrite that
    reconstructs Func nodes must go through this."""
    out = Func(e.dtype, e.op, tuple(args))
    for attr in ("_derived_dict", "_char_len"):
        d = getattr(e, attr, None)
        if d is not None:
            object.__setattr__(out, attr, d)
    return out


def map_column_indices(e: Expr, mapping: dict[int, int]) -> Expr:
    """Rewrite ColumnRef indices (used when pruning/reordering schemas)."""
    if isinstance(e, ColumnRef):
        return ColumnRef(e.dtype, mapping[e.index], e.name)
    if isinstance(e, Func):
        return clone_func(e, (map_column_indices(a, mapping)
                              for a in e.args))
    return e


def substitute_columns(e: Expr, exprs) -> Expr:
    """Replace ColumnRef i with exprs[i]: `e` over a projection's output
    as an expression over its input."""
    if isinstance(e, ColumnRef):
        return exprs[e.index]
    if isinstance(e, Func):
        return clone_func(e, (substitute_columns(a, exprs) for a in e.args))
    return e


__all__ = ["Expr", "ColumnRef", "Const", "Func", "walk", "clone_func",
           "referenced_columns", "map_column_indices", "substitute_columns"]
