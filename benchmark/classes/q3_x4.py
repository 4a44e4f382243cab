"""TPC-H Q3 in the deployment ``tpch_sf10_orders_x4``: the statement text,
the parameters, the oracle and the bytes of ``q3.py`` (imported: the text
is one), at SF10 on a 2x2 host, where ``orders`` (15M rows) is past the
planner's broadcast cap: its join with the segment's customers is made on
every ``orders`` shard and stays there as the next join's sharded build
side, and ``lineitem``'s live rows travel to the chip that owns their
order.

Loads only against a program that supports the deployment: one that says
of a join launch whether rows were exchanged (the
``join_exchange_launches`` counter).  A program without it answers Q3 at
this size with a host hash join over 60M rows and Q12 with a repartition
join that scatters every slot of both tables, tens of seconds a
statement, and would be timed for minutes before the first answer: the
harness has no other way to fail early on a parent it is laid over."""

from __future__ import annotations

import importlib.util
import os

from tidb_tpu.copr import facts as _facts

if "join_exchange_launches" not in _facts.counter_names():
    raise SystemExit(
        "benchmark: this program does not support the deployment "
        "tpch_sf10_orders_x4: it keeps no join_exchange_launches counter")


def _sibling(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"bench_x4_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_q3 = _sibling("q3")

NAME = "q3_x4"
POOL = _q3.POOL
ORDERED = _q3.ORDERED
# join launches a statement of this class takes (`shuffle_device_share`):
# `orders` looked up in the segment's customers on every shard, its rows
# left on their chips (a third program, which joins nothing, makes them
# into each chip's table); `lineitem` looked up in those tables after the
# exchange, grouped, ranked
JOIN_LAUNCHES = 2
READS = _q3.READS
draw, sql, prepare, answer, bytes_read = (
    _q3.draw, _q3.sql, _q3.prepare, _q3.answer, _q3.bytes_read)
