"""Shared test helpers."""

from tidb_tpu.chunk import Column


def col_pair(col: Column):
    """Column -> (data, validity) pair in the evaluator's encoding
    (literal True = all-valid fast path)."""
    return col.data, (True if col.validity.all() else col.validity)


def memo_outcomes() -> dict:
    """``tidb_tpu_stmt_memo_total`` by outcome, as ``/metrics`` has it."""
    from tidb_tpu.utils.metrics import global_registry
    c = global_registry().counter("tidb_tpu_stmt_memo_total",
                                  labels=("outcome",))
    return {o: c.get(outcome=o) for o in ("hit", "miss", "bypass")}
