"""The Q3 oracle's pass over ``lineitem`` (``benchmark/classes/q3.py``
``_ranked``, PR 46) in tier-1: a chunk sums over the range of order rows
its live lines touch, whatever order the rows are stored in, and the
answer is the one a ``dict`` of ``int`` sums over the joined rows gives, a
row at a time; ``CHUNK_ROWS`` patched small, rows by key and permuted, a
tie among the first eleven groups.  The cases are those of
``benchmark/selftest/test_oracles.py``, which tier-1 does not run: they
are collected from that file, so the two cannot drift apart.  Tier-1 then
holds the oracle itself and not only the program against it
(``tests/test_orderjoin.py``)."""

import importlib.util
import os

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")


def _load(name: str, *path: str):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def run_py():
    """``benchmark/run.py``, which finds a class or a table by name (and
    puts ``harness`` on the path)."""
    return _load("q3o_bench_run", "run.py")


_oracles = _load("q3o_selftest_oracles", "selftest", "test_oracles.py")
orders_data = _oracles.orders_data
test_q3_ranked_against_a_dict_of_int_sums = \
    _oracles.test_q3_ranked_against_a_dict_of_int_sums
test_q3_ranked_sees_a_tie_among_the_first_eleven = \
    _oracles.test_q3_ranked_sees_a_tie_among_the_first_eleven
