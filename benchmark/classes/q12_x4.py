"""TPC-H Q12 in the deployment ``tpch_sf10_orders_x4``: the statement
text, the parameters, the oracle and the bytes of ``q12.py`` (imported:
the text is one), at SF10 on a 2x2 host, where ``orders`` (15M rows) is
past the planner's broadcast cap: each chip keeps the direct-addressed
table of the orders it owns, with the table's snapshot, and the one
``lineitem`` row in two hundred the filters leave travels to the chip that
owns its order.

Loads only against a program that supports the deployment, as
``q3_x4.py`` says."""

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


_q12 = _sibling("q12")

NAME = "q12_x4"
POOL = _q12.POOL
ORDERED = _q12.ORDERED
# one join launch a statement (`shuffle_device_share`): the compacted
# live rows of `lineitem` exchanged and looked up in the chips' kept
# tables of `orders`
JOIN_LAUNCHES = 1
READS = _q12.READS
draw, sql, prepare, answer, bytes_read = (
    _q12.draw, _q12.sql, _q12.prepare, _q12.answer, _q12.bytes_read)
