"""Statement memo: what is a pure function of a command's text, kept by
that text.

A statement the server has answered before is, today as ever, looked up
in the plan cache, admitted, launched, transferred and merged.  What it
no longer pays again is its text: the lex and parse, the normalised
digest (``planner/bindinfo`` matches on it, ``utils/stmtsummary`` files
the statement under it) and the walk that lists the tables the
privilege check asks about.  None of these depends on data, catalog,
sysvars, bindings or grants, so an entry is never stale: the only
bound is the LRU's.  It is no plan cache and no result cache.

**The kept AST is shared and nobody writes to it.**  The builder writes
to the AST it is given (``planner/build.build_select`` rewrites
``where``, ``items`` and ``order_by``; a matching binding sets
``hints``), so an AST that has been through a statement cannot be
kept.  The memo therefore keeps an AST only from a parse it makes for
itself, and makes that parse only when a text comes back:

- first sight (``miss``): one parse, as ever; the AST is the
  statement's own and goes wherever it went before.  The memo keeps
  the texts, digests and table lists.
- second sight (``bypass``): one parse again, and this one is the
  memo's: its queries (SELECT, set operations) are kept.  A text that
  never returns never pays for a second parse.
- from then on (``hit``): no parse.  The session reads the kept AST
  (dispatch by type, ``for_update``, the plan-cache lookup) and, where
  it needs one to hand to the builder (the plan cache missed, a binding
  matched), parses the statement afresh (``bypass``).  A statement
  that is no query is always parsed afresh: its executor owns its AST.
"""

from __future__ import annotations

import re
import threading
from collections import OrderedDict
from typing import NamedTuple, Optional

from ..sql import ast as A
from ..sql.parser import parse_sql
from ..utils.stmtsummary import normalize_sql

# texts kept: twice the plans ``planner/plan_cache.PlanCache`` keeps, for
# the statements that have no plan to cache
CAPACITY = 256
# the longest text worth keeping (characters): TPC-H's longest is under
# 3,000; a bulk INSERT ... VALUES is not to sit here
MAX_TEXT = 4096

QUERY = (A.SelectStmt, A.SetOpStmt)
_PREFIX = re.compile(r"(?is)^\s*(explain(\s+analyze)?|trace)\s+")


class MemoStmt(NamedTuple):
    """One statement of a command's text."""
    text: str               # its own text: ``text_span``, stripped
    digest: str             # normalize_sql(text)
    bind_digest: str        # the same, less an EXPLAIN / TRACE prefix
    tables: Optional[tuple]  # (db, table) a query reads; else None


class Resolved(NamedTuple):
    rec: MemoStmt
    stmt: A.Node
    shared: bool            # ``stmt`` is the memo's: read it, never write
    outcome: str            # hit | miss | bypass


def referenced_tables(node: A.Node) -> list[tuple]:
    """All (db, table) names a query reads — walks FROM clauses, joins,
    subqueries, CTE bodies (skipping CTE self-references)."""
    out: list[tuple] = []
    cte_names: set = set()

    def walk(n):
        if n is None or not isinstance(n, A.Node):
            return
        if isinstance(n, A.TableName):
            if n.name not in cte_names:
                out.append((n.db, n.name))
            return
        if isinstance(n, A.CTE):
            cte_names.add(n.name)
        # register CTE names BEFORE visiting FROM clauses that
        # reference them (dataclass field order puts from_ first)
        for cte in getattr(n, "ctes", ()):
            walk(cte)
        for f in getattr(n, "__dataclass_fields__", {}):
            if f == "ctes":
                continue
            v = getattr(n, f, None)
            if isinstance(v, A.Node):
                walk(v)
            elif isinstance(v, (list, tuple)):
                for x in v:
                    if isinstance(x, A.Node):
                        walk(x)
                    elif isinstance(x, tuple):
                        for y in x:
                            if isinstance(y, A.Node):
                                walk(y)
    walk(node)
    return out


def describe(sql: str, stmts: list) -> tuple[MemoStmt, ...]:
    """The memo's record of each statement ``parse_sql(sql)`` gave."""
    recs = []
    for stmt in stmts:
        span = getattr(stmt, "text_span", None)
        text = sql[span[0]:span[1]].strip() if span else sql
        digest = bind_digest = normalize_sql(text)
        target = stmt
        if isinstance(stmt, (A.Explain, A.TraceStmt)):
            # a binding is made for the statement, not for its EXPLAIN
            target = stmt.stmt
            bind_digest = normalize_sql(_PREFIX.sub("", text))
        tables = (tuple(referenced_tables(target))
                  if isinstance(target, QUERY) else None)
        recs.append(MemoStmt(text, digest, bind_digest, tables))
    return tuple(recs)


class StmtMemo:
    """LRU from a command's exact text to ``(records, kept ASTs)``; the
    ASTs are None until the text has come back."""

    def __init__(self, capacity: int = CAPACITY, max_text: int = MAX_TEXT):
        self.capacity = capacity
        self.max_text = max_text
        self._lru: OrderedDict[str, tuple] = OrderedDict()
        self._mu = threading.Lock()   # one thread per server connection

    def __len__(self) -> int:
        return len(self._lru)

    def __contains__(self, sql: str) -> bool:
        return sql in self._lru

    def _put(self, sql: str, entry: tuple) -> None:
        with self._mu:
            self._lru[sql] = entry
            self._lru.move_to_end(sql)
            while len(self._lru) > self.capacity:
                self._lru.popitem(last=False)

    def resolve(self, sql: str) -> list[Resolved]:
        """Each statement of ``sql`` with the AST to run it from.  A
        text that does not parse raises, and nothing is kept."""
        if len(sql) > self.max_text:
            stmts = parse_sql(sql)
            return [Resolved(r, s, False, "bypass")
                    for r, s in zip(describe(sql, stmts), stmts)]
        with self._mu:
            entry = self._lru.get(sql)
            if entry is not None:
                self._lru.move_to_end(sql)
        if entry is None:
            stmts = parse_sql(sql)
            recs = describe(sql, stmts)
            self._put(sql, (recs, None))
            return [Resolved(r, s, False, "miss")
                    for r, s in zip(recs, stmts)]
        recs, kept = entry
        if kept is None:
            # the text came back: this parse is the memo's own, and the
            # statement that makes it already treats it so
            stmts = parse_sql(sql)
            kept = tuple(s if isinstance(s, QUERY) else None
                         for s in stmts)
            self._put(sql, (recs, kept))
            return [Resolved(r, s, k is not None, "bypass")
                    for r, s, k in zip(recs, stmts, kept)]
        return [Resolved(r, k, True, "hit") if k is not None
                else Resolved(r, parse_sql(r.text)[0], False, "bypass")
                for r, k in zip(recs, kept)]


__all__ = ["CAPACITY", "MAX_TEXT", "MemoStmt", "QUERY", "Resolved",
           "StmtMemo", "describe", "referenced_tables"]
