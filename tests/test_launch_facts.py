"""What a launch says of its program, as `/sched` counts it and the
`sched.launch` span shows it: one case a launch kind, each through
`Session.execute` on the CPU mesh with the engine pinned to the device
path as tests/test_sched.py does.

The deltas and attributes expected here were written down from the tree
before the fact channel (PR 26's), where two named fields of the batch,
three accessors of the programs and three `_note_*` of the scheduler
carried them: they are what `/sched` and the span have to go on saying.
The last case registers a fact no kernel has and sees it counted and on
the span, with nothing of `sched/` edited."""

import threading
import time

import numpy as np
import pytest

from tidb_tpu.chunk.column import Column
from tidb_tpu.copr import dag as D
from tidb_tpu.copr import facts as F
from tidb_tpu.parallel import get_mesh, spmd
from tidb_tpu.sched import scheduler_for
from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.types import dtypes as dt

MODE_COUNTERS = ("launches", "fused_launches", "batched_launches",
                 "batched_rows_launches", "coalesced_launches")
BIG = 8 * 65536         # rows a device: 65536, which a LIMIT 10 prunes


def _wait_until(pred, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


def _bulk(dom, name, n, seed):
    rng = np.random.default_rng(seed)
    flags = np.array(["A", "N", "R"])
    cols = {"o": np.arange(n, dtype=np.int64),
            "p": rng.integers(100, 10_000_000, n),
            "q": rng.integers(1, 50, n),
            "g": flags[rng.integers(0, 3, n)]}
    built = [Column(dt.bigint(False), cols[c].astype(np.int64),
                    np.ones(n, bool)) for c in ("o", "p", "q")]
    built.append(Column.from_values(dt.varchar(False), list(cols["g"])))
    info = TableInfo(name, list(cols), [c.dtype for c in built])
    info.register_columns(built)
    dom.catalog.create_table("test", info)


@pytest.fixture(scope="module")
def dom():
    """One domain on the CPU mesh, the engine pinned to the device path:
    `big` (a size whose TopN prunes), `t1` and `t2` (one schema, two
    snapshots), and a fact table with three build sides."""
    dom = Domain()
    _bulk(dom, "big", BIG, 1)
    _bulk(dom, "t1", 4000, 2)
    _bulk(dom, "t2", 4000, 3)
    s = Session(dom)
    s.execute("create table fact (k bigint, v bigint)")
    s.execute("insert into fact values " + ", ".join(
        f"({k % 9 if k % 7 else 'null'}, {k})" for k in range(200)))
    for name, rows in (
            ("dim", [(k, 100 + k) for k in range(1, 9)]),
            ("dup", [(k, 100 + k) for k in range(1, 9)] + [(2, 7), (5, 8)]),
            ("many", [(3, w) for w in range(3000)]),
            ("none", [])):
        s.execute(f"create table {name} (k bigint, w bigint)")
        if rows:
            s.execute(f"insert into {name} values " + ", ".join(
                f"({k}, {w})" for k, w in rows))
    for t in ("big", "t1", "t2"):
        s.execute(f"analyze table {t}")
    s.execute("set global tidb_tpu_result_cache_entries = 0")
    s.execute("set global tidb_tpu_sched_max_coalesce = 8")
    s.execute("set global tidb_tpu_sched_fusion = 1")
    s.execute("set global tidb_tpu_sched_window_us = -1")
    dom.client._platform = lambda: "tpu"
    yield dom
    _forget_programs()


def _forget_programs():
    """No program object and no executable of an earlier trace."""
    from tidb_tpu.compilecache import compile_cache
    for cache in (spmd._cached, spmd._cached_fused, spmd._cached_fused_rows,
                  spmd._cached_batched, spmd._cached_batched_rows):
        cache.cache_clear()
    compile_cache().clear_pool()


@pytest.fixture
def lowered_for(monkeypatch):
    """lowered_for(platform): every program built from then on, until
    the test ends, is lowered as for a mesh of that platform."""
    def steer(platform):
        monkeypatch.setattr(spmd, "mesh_platform", lambda _mesh: platform)
        _forget_programs()
    yield steer
    _forget_programs()


def _run(dom, sqls, together=False):
    """Run `sqls`, one session each (queued behind a paused drain and
    released at once if `together`) -> (`/sched` deltas that are not 0,
    the `sched.launch` spans' mode, program and fact attributes in
    statement order)."""
    sched = scheduler_for(get_mesh())
    sessions = [Session(dom) for _ in sqls]
    before = sched.stats()
    if together:
        errors = []

        def one(sess, sql):
            try:
                sess.execute(sql)
            except Exception as e:  # noqa: BLE001 - surfaced by the assert
                errors.append(e)
        sched.pause()
        try:
            threads = [threading.Thread(target=one, args=a)
                       for a in zip(sessions, sqls)]
            for t in threads:
                t.start()
            _wait_until(lambda: sched.depth >= len(sqls),
                        msg=f"{len(sqls)} queued cop tasks")
        finally:
            sched.resume()
        for t in threads:
            t.join(timeout=120)
        assert not errors, errors
    else:
        for sess, sql in zip(sessions, sqls):
            sess.execute(sql)
    # the drain counts a launch after it has woken the waiter, and a
    # task as done after that
    _wait_until(lambda: sched.depth == 0, msg="an empty queue")
    spans = []
    for sess in sessions:
        for sp in sess.last_trace.spans:
            if sp.name == "sched.launch":
                a = sp.attrs
                spans.append(
                    {"mode": a["mode"],
                     "program": a.get("program", "").rsplit("_", 1)[0],
                     # (a rows root's capacity is the paging's, which
                     # remembers what the statements before it found)
                     **{k: a[k] if k != "rows_capacity" else a[k] > 0
                        for k in F.FACTS if k in a}})
    _wait_until(lambda: sched.stats()["tasks_done"]
                >= before["tasks_done"] + len(spans),
                msg="the drain's counts")
    after = sched.stats()
    delta = {k: after[k] - before.get(k, 0)
             for k in F.counter_names() + MODE_COUNTERS
             if after.get(k, 0) != before.get(k, 0)}
    return delta, spans


TOPN = "select o, p from {} order by p desc limit 10"
DENSE = "select g, sum(p), count(*), count(q) from {} group by g"
DENSE2 = "select g, sum(q) from {} where p > 5000 group by g"
ROWS = "select p from {} where q = 3"
JOIN = "select sum(v * w), count(*) from fact, {0} where fact.k = {0}.k"
SHUFFLE = "select sum(v), count(*) from fact join dup on fact.k = dup.k"
COMPACT = ("select sum(p * w), count(*) from big, dim "
           "where big.q = dim.k and p < 200000")


def _solo(program, **facts):
    return {"mode": "single", "program": program, **facts}


# a rows root says the capacity its live rows leave in; by the column
# sort only where the program is lowered for a TPU
ROWS_ROOT = _solo("cop_solo_rows", rows_capacity=True)


# kind: (statements, queued together?, the platform the programs are
# lowered for, `/sched` deltas, the `sched.launch` spans)
KINDS = {
    "topn_pruned": (
        [TOPN.format("big")], False, "cpu",
        {"launches": 1, "topn_launches": 1, "topn_pruned_launches": 1},
        [_solo("cop_solo_topn", topn_blocks=64)]),
    "topn_one_block": (
        [TOPN.format("t1")], False, "cpu",
        {"launches": 1, "topn_launches": 1},
        [_solo("cop_solo_topn", topn_blocks=1)]),
    "dense_scatter": (
        [DENSE.format("t1")], False, "cpu",
        {"launches": 1, "dense_agg_launches": 1},
        [_solo("cop_solo_agg_dense")]),
    "dense_limb": (
        [DENSE.format("t1")], False, "tpu",
        {"launches": 1, "dense_agg_launches": 1,
         "dense_agg_limb_launches": 1},
        [_solo("cop_solo_agg_dense", agg_limbs=3)]),
    "join_unique": (
        [JOIN.format("dim")], False, "cpu",
        {"launches": 2, "join_launches": 1, "join_direct_launches": 1,
         "rows_launches": 1},
        [ROWS_ROOT,
         _solo("cop_solo_join_agg_scalar", join="unique", build_rows=8,
               probe_rows=8192, join_form="direct")]),
    "join_multimatch": (
        [JOIN.format("dup")], False, "cpu",
        {"launches": 2, "join_launches": 1, "rows_launches": 1},
        [ROWS_ROOT,
         _solo("cop_solo_join_agg_scalar", join="multimatch", build_rows=10,
               probe_rows=8192, join_form="expanding")]),
    # a filter beneath the join, statistics to size it from, a platform
    # whose gather costs its indices: the probe rows are compacted first
    # (2 % of 524,288 rows estimated, a device's share, a quarter more and
    # six deviations of one of the compaction's columns)
    "join_compact": (
        [COMPACT], False, "tpu",
        {"launches": 2, "join_launches": 1, "join_compact_launches": 1,
         "join_direct_launches": 1, "rows_launches": 1,
         "rows_compact_launches": 1},
        [dict(ROWS_ROOT, rows_compact=1),
         _solo("cop_solo_join_agg_scalar", join="unique", build_rows=8,
               probe_rows=BIG, probe_capacity=4096, join_form="direct")]),
    # (the build side, prepared above, is kept with `dim`'s snapshot)
    "join_filtered_on_the_cpu_mesh": (
        [COMPACT], False, "cpu",
        {"launches": 1, "join_launches": 1, "join_direct_launches": 1},
        [_solo("cop_solo_join_agg_scalar", join="unique", build_rows=8,
               probe_rows=BIG, join_form="direct")]),
    "fused_aggs": (
        [DENSE.format("t1"), DENSE2.format("t1")], True, "tpu",
        {"launches": 1, "fused_launches": 1, "dense_agg_launches": 1,
         "dense_agg_limb_launches": 1},
        [{"mode": "fused", "program": "cop_fused_x2", "agg_limbs": 3 + 2}]
        * 2),
    "fused_rows": (
        [ROWS.format("t1"), TOPN.format("t1")], True, "cpu",
        {"launches": 1, "fused_launches": 1, "topn_launches": 1,
         "rows_launches": 1},
        [{"mode": "fused", "program": "cop_fused_rows_x2",
          "topn_blocks": 1, "rows_capacity": True}] * 2),
    "batched": (
        [DENSE.format("t1"), DENSE.format("t2")], True, "tpu",
        {"launches": 1, "batched_launches": 1, "coalesced_launches": 1,
         "dense_agg_launches": 1, "dense_agg_limb_launches": 1},
        [{"mode": "batched", "program": "cop_batched_agg_dense",
          "agg_limbs": 3}] * 2),
    "batched_rows": (
        [TOPN.format("t1"), TOPN.format("t2")], True, "cpu",
        {"launches": 1, "batched_launches": 1, "batched_rows_launches": 1,
         "coalesced_launches": 1, "topn_launches": 1},
        [{"mode": "batched", "program": "cop_batched_rows_topn",
          "topn_blocks": 1}] * 2),
    "coalesced": (
        [TOPN.format("t1")] * 2, True, "cpu",
        {"launches": 1, "coalesced_launches": 1, "topn_launches": 1},
        [{"mode": "coalesced", "program": "cop_solo_topn",
          "topn_blocks": 1}] * 2),
    "shuffle_join": (
        [SHUFFLE], False, "cpu",
        {"launches": 1, "join_shuffle_launches": 1},
        [{"mode": "opaque", "program": "cop_shuffle_agg_scalar"}]),
    # an empty build side: the join's two sides and its fallback scan
    "join_host_fallback": (
        [JOIN.format("none")], False, "cpu",
        {"launches": 3, "join_host_fallbacks": 1, "rows_launches": 3},
        [ROWS_ROOT] * 3),
}


@pytest.mark.parametrize("kind", list(KINDS))
def test_what_a_launch_says(dom, lowered_for, monkeypatch, kind):
    sqls, together, platform, delta, spans = KINDS[kind]
    if platform != "cpu":
        lowered_for(platform)
    if kind == "shuffle_join":
        # nothing is small enough to broadcast.  (The module's value,
        # scoped: the sysvar's -1 would leave a 0 behind for the process.)
        from tidb_tpu.executor import plan
        monkeypatch.setattr(plan, "BROADCAST_BUILD_MAX_ROWS", 0)
    if spans[0]["mode"] in ("fused", "batched"):
        # the drain compiles no group program: the first time a set
        # turns up it is served apart; the explicit warm compiles its
        # program; what is read here is the second time
        _run(dom, sqls, together)
        sched = scheduler_for(get_mesh())
        sched.warm_groups()
        _wait_until(lambda: not sched._groups_pending
                    and not sched._groups_inflight
                    and not sched._warm_alive, timeout=120,
                    msg="the background group compile")
    assert _run(dom, sqls, together) == (delta, spans)


def test_a_new_fact_needs_nothing_of_the_scheduler(dom, lowered_for,
                                                   monkeypatch):
    """A kernel that wants a counter and a span attribute writes its
    fact where it decides and adds a row to copr/facts.py: here the
    row-output compaction says its capacity.  `sched/` is as it was."""
    monkeypatch.setitem(F.FACTS, "compact_rows", F.Fact(
        counters=(("compact_launches", lambda _n: True),
                  ("compact_wide_launches", lambda n: n > 1 << 30)),
        root=lambda _root: True))

    def compact(batch, capacity, platform, real=spmd.compact_root):
        batch.facts["compact_rows"] = capacity
        return real(batch, capacity, platform)
    monkeypatch.setattr(spmd, "compact_root", compact)
    lowered_for("cpu")
    delta, (span,) = _run(dom, [ROWS.format("t2")])
    assert delta == {"launches": 1, "compact_launches": 1,
                     "rows_launches": 1}
    # (the capacity the client's paging chose: a power of two)
    assert span["compact_rows"] >= 256 and span["mode"] == "single"
    # the names the scheduler shows from its start, at zero
    assert {"compact_launches", "compact_wide_launches",
            "join_regrows"} <= set(F.counter_names())


def test_a_regrown_join_is_counted_by_name(dom):
    """An event no launch carries goes through `DeviceScheduler.count`:
    the expanding join's capacity regrow, seen by the client."""
    scan = D.TableScan((0,), (dt.bigint(False),))
    from tidb_tpu.expr import ColumnRef
    join = D.LookupJoin(scan, ColumnRef(dt.bigint(False), 0), "inner",
                        (dt.bigint(False),), unique=False, out_capacity=4)
    sched = scheduler_for(get_mesh())
    before = sched.stats()["join_regrows"]
    client = dom.client
    assert client._grown_join_dag(join, {"join_total": np.array([3, 4])}) \
        is None
    grown = client._grown_join_dag(join, {"join_total": np.array([3, 9])})
    assert grown.out_capacity == 16
    assert sched.stats()["join_regrows"] == before + 1


@pytest.mark.parametrize("members,root,want", [
    # a fused launch: the members' limb lanes together ...
    ([{"agg_limbs": 3}, {"agg_limbs": 2}], None, {"agg_limbs": 5}),
    # ... 0 if any DENSE member is not in the limb form, and a member
    # with no DENSE aggregation says nothing
    ([{"agg_limbs": 3}, {"agg_limbs": 0}, {}], None, {"agg_limbs": 0}),
    ([{}, {}], None, {}),
    # the most blocks any member's TopN views its input as
    ([{"topn_blocks": 1}, {}, {"topn_blocks": 64}], None,
     {"topn_blocks": 64}),
    # a TopN counts for the program only as its root
    ([{"topn_blocks": 64, "agg_limbs": 0}], "agg", {"agg_limbs": 0}),
    ([{"topn_blocks": 64}], "topn", {"topn_blocks": 64}),
])
def test_the_table_merges_and_roots(members, root, want):
    if root is None:
        assert F.merged(members) == want
        return
    scan = D.TableScan((0,), (dt.bigint(False),))
    from tidb_tpu.expr import ColumnRef
    top = D.TopN(scan, sort_key=ColumnRef(dt.bigint(False), 0), limit=3)
    key = (ColumnRef(dt.bigint(False), 0),)
    node = top if root == "topn" else D.Aggregation(
        top, key, (), D.GroupStrategy.DENSE, domain_sizes=(4,))
    assert F.of_program(members[0], node) == want
    # copforge serving an executable untraced costs an abstract trace
    # only where the program can have a fact: not a scalar aggregation
    assert F.says_in_trace(node) and not F.says_in_trace(
        D.Aggregation(top, (), (), D.GroupStrategy.SCALAR))
    assert F.counters(want) == (["dense_agg_launches"] if root == "agg" else [
        "topn_launches", "topn_pruned_launches"])
    assert F.span_attrs(want) == ({} if root == "agg" else want)
