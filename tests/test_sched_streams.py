"""Eight query streams at once (TPC-H v3 5.3.4, the benchmark's cell
``tpch10x1.throughput`` at a toy scale): whatever launch a statement
shared, its answer is its own, and the drain thread compiles no group
program.

The streams are the cell's: ``benchmark/traffic/throughput8.json``
through the harness's own generator (eight sessions, each its own
shuffled order of ``q6``, ``q1`` and ``part_agg``, four literal sets
each), the tables the cell's generators, the reference the classes'
numpy oracles (text for text) and, beside them, sqlite over the same
rows (as ``tests/test_tpch_diff.py``).  A round is one statement of
every stream, queued behind a paused drain and released at once, so
which statements meet is the seed's and not the threads'.

What the scheduler does with a group of programs whose group program is
not loaded (``sched/scheduler.py``): it serves the members apart, and
remembers the program for an explicit ``warm_groups``, which compiles it
on a background thread; from then on that set shares a launch.  Nothing
compiles a group program by itself: a fused launch is keyed by its
members' literals, and eight texts over one table are 247 sets.
"""

import datetime
import importlib.util
import json
import os
import re
import sqlite3
import sys
import threading
import time

import numpy as np
import pytest

from tidb_tpu.session import Domain, Session
from tidb_tpu.session.catalog import TableInfo
from tidb_tpu.compilecache import (compile_cache, configure,  # noqa: I001
                                   is_group_key, reset_warmed,
                                   simulate_restart, warm_start)

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark")
SCALE, SEED = 0.01, 48          # 60,000 and 2,000 rows
ROUNDS = 8                      # one cycle of the mix: 64 statements a pass
EPOCH = datetime.date(1970, 1, 1)


def _bench(kind: str, name: str):
    """``benchmark/<kind>/<name>.py``, as the harness loads it."""
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)       # harness.exact
    path = os.path.join(BENCH, kind, f"{name}.py")
    spec = importlib.util.spec_from_file_location(f"st_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _wait_until(pred, timeout=60.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.01)
    raise AssertionError(f"timed out waiting for {msg}")


def _land(sched):
    """The explicit warm, and its background threads done: every group
    program of a set that was served apart is in (up to the bound)."""
    sched.warm_groups()
    _wait_until(lambda: not sched._groups_pending
                and not sched._groups_inflight and not sched._warm_alive,
                180, "the background group compiles")


def _text(rows):
    return [tuple(None if v is None else str(v) for v in r) for r in rows]


# --------------------------------------------------------------------- #
# the deployment at a toy scale: two copies of the tables (a statement
# over `lineitem` and the same statement over `lineitem2` are one
# program over two inputs), the oracles, sqlite
# --------------------------------------------------------------------- #

def _sqlite_sql(sql: str) -> str:
    """The class's text in sqlite's dialect: dates are ISO strings, the
    interval is folded into the literal (tests/test_tpch_diff.py)."""
    def minus(m):
        day = datetime.date.fromisoformat(m.group(1)) \
            - datetime.timedelta(days=int(m.group(2)))
        return f"'{day.isoformat()}'"
    sql = re.sub(r"date '([\d-]+)' - interval '(\d+)' day", minus, sql)
    return re.sub(r"date '([\d-]+)'", r"'\1'", sql)


def _sqlite_load(db, name: str, table, data: dict) -> None:
    cols = {}
    for c, v in data.items():
        kind = table.TYPES[c]
        if kind == "dict":
            codes, words = v
            cols[c] = [words[i] for i in codes]
        elif kind == "date":
            cols[c] = [(EPOCH + datetime.timedelta(days=int(d))).isoformat()
                       for d in v]
        elif kind.startswith("decimal"):
            cols[c] = (v / 100.0).tolist()
        else:
            cols[c] = v.tolist()
    db.execute(f"create table {name} ({', '.join(cols)})")
    db.executemany(
        f"insert into {name} values ({', '.join('?' * len(cols))})",
        zip(*cols.values()))


def _near(got, want) -> bool:
    """An engine's exact answer beside sqlite's floating-point one."""
    if len(got) != len(want):
        return False
    for g, w in zip(got, want):
        for a, b in zip(g, w):
            if isinstance(b, float):
                if abs(float(a) - b) > 1e-6 * max(1.0, abs(b)):
                    return False
            elif str(a) != str(b):
                return False
    return True


class Deployment:
    def __init__(self):
        run_py = _bench("", "run")
        traffic = _bench("harness", "traffic")
        with open(os.path.join(BENCH, "traffic", "throughput8.json")) as f:
            self.mix = dict(json.load(f), cycles=1)
        self.classes = {c: _bench("classes", c) for c in self.mix["mix"]}
        tables = {"lineitem": _bench("tables", "lineitem"),
                  "part": _bench("tables", "part")}
        self.dom = Domain()
        self.db = sqlite3.connect(":memory:", check_same_thread=False)
        self.state = {}                   # copy -> class -> oracle state
        for copy, seed in (("", SEED), ("2", SEED + 1)):
            data = {n: t.generate(SCALE, seed, list(t.TYPES))
                    for n, t in tables.items()}
            for n, t in tables.items():
                valid = np.ones(len(next(run_py._arrays(data[n]))), bool)
                cols = [run_py._column(t.TYPES[c], v, valid)
                        for c, v in data[n].items()]
                info = TableInfo(n + copy, list(data[n]),
                                 [c.dtype for c in cols])
                info.register_columns(cols)
                self.dom.catalog.create_table("test", info)
                _sqlite_load(self.db, n + copy, t, data[n])
            self.state[copy] = {c: m.prepare(data)
                                for c, m in self.classes.items()}
        sess = Session(self.dom)
        sess.execute("analyze table lineitem")
        sess.execute("analyze table lineitem2")
        sess.execute("set global tidb_tpu_result_cache_entries = 0")
        sess.execute("set global tidb_tpu_sched_max_coalesce = 8")
        sess.execute("set global tidb_tpu_sched_fusion = 1")
        sess.execute("set global tidb_tpu_sched_window_us = -1")
        self.dom.client._platform = lambda: "tpu"
        # the cell's statements and each stream's own order
        self.pools = traffic.pools(self.classes, self.mix)
        self.orders = traffic.streams(
            self.mix, {c: len(p) for c, p in self.pools.items()}, SEED)
        assert len(self.orders) == 8 and all(
            len(o) == ROUNDS for o in self.orders)
        assert all(len(p) == 4 for p in self.pools.values())
        self._want = {}
        # every statement once, alone: its solo program is loaded (as the
        # cell's warm-up does) and the scheduler started
        for copy in self.state:
            for c, pool in self.pools.items():
                for k in range(len(pool)):
                    self.check(c, k, copy, sess.must_query(
                        self.sql(c, k, copy)))
        self.sched = self.dom.client._sched_obj

    def sql(self, cls: str, k: int, copy: str) -> str:
        sql = self.classes[cls].sql(self.pools[cls][k])
        return re.sub(r"\bfrom (lineitem|part)\b", rf"from \g<1>{copy}", sql)

    def check(self, cls: str, k: int, copy: str, rows) -> None:
        """``rows`` is the statement's answer by the class's oracle,
        text for text, and by sqlite."""
        mod = self.classes[cls]
        if (cls, k, copy) not in self._want:
            oracle = mod.answer(self.state[copy][cls], self.pools[cls][k])
            lite = self.db.execute(
                _sqlite_sql(self.sql(cls, k, copy))).fetchall()
            self._want[cls, k, copy] = (oracle, lite)
        oracle, lite = self._want[cls, k, copy]
        got = _text(rows)
        if not mod.ORDERED:
            got, oracle, lite = sorted(got), sorted(oracle), sorted(lite)
        assert got == oracle, (cls, k, copy, got[:2], oracle[:2])
        assert _near(got, lite), (cls, k, copy, got[:2], lite[:2])

    def one_pass(self, copies, orders=None) -> int:
        """Every stream's statements, a round at a time: stream ``i``
        reads the tables ``copies[i]``.  -> statements answered."""
        orders = orders or self.orders
        sessions = [Session(self.dom) for _ in orders]
        errors = []
        for r in range(ROUNDS):
            def run(i):
                cls, k = orders[i][r]
                try:
                    self.check(cls, k, copies[i], sessions[i].must_query(
                        self.sql(cls, k, copies[i])))
                except BaseException as e:  # noqa: BLE001 - via the assert
                    errors.append(e)
            self.sched.pause()
            try:
                threads = [threading.Thread(target=run, args=(i,))
                           for i in range(len(orders))]
                for t in threads:
                    t.start()
                _wait_until(lambda: self.sched.depth >= len(orders)
                            or errors, msg="a round's tasks queued")
            finally:
                self.sched.resume()
            for t in threads:
                t.join(timeout=120)
            assert not errors, errors[:2]
        return ROUNDS * len(orders)


@pytest.fixture(scope="module")
def deployment():
    d = Deployment()
    yield d
    _land(d.sched)
    simulate_restart()


# --------------------------------------------------------------------- #
# every answer is its own statement's, under every form of group
# --------------------------------------------------------------------- #

ONE_COPY = [""] * 8
# stream i and stream i + 4 send the same statements, one over each copy
# of the tables: one program, two inputs
TWO_COPIES = [""] * 4 + ["2"] * 4


@pytest.mark.parametrize("form", ["apart", "dedup", "fused", "batched"])
def test_every_answer_is_right_whatever_launch_it_shared(deployment, form):
    """Eight sessions on threads, each its own shuffled order of the
    three classes with four literal sets each, 128 statements a case,
    every one checked against the oracle and sqlite; the case's form of
    group really occurs (its counter moves)."""
    d, sched = deployment, deployment.sched
    _land(sched)
    simulate_restart()              # no group program is loaded
    sess = Session(d.dom)
    for copy in d.state:            # ... and every solo program is
        for c, pool in d.pools.items():
            for k in range(len(pool)):
                sess.must_query(d.sql(c, k, copy))
    copies, orders = ONE_COPY, d.orders
    if form == "batched":
        copies, orders = TWO_COPIES, d.orders[:4] * 2
    before = sched.stats()
    answered = d.one_pass(copies, orders)
    first = sched.stats()
    # nothing was loaded: the groups that formed were served apart (a
    # set that came again later in the pass may have found its program),
    # and not by a refusal of the program
    assert first["groups_apart_unloaded"] > before["groups_apart_unloaded"]
    assert first["batched_refused"] == before["batched_refused"]
    _land(sched)
    landed = sched.stats()
    assert landed["group_compiles_bg"] > before["group_compiles_bg"]
    assert landed["warm_failures"] == before["warm_failures"]
    answered += d.one_pass(copies, orders)      # the same sets again
    after = sched.stats()
    assert answered == 128
    assert after["tasks_done"] - before["tasks_done"] >= answered
    moved = {k: after[k] - landed[k] for k in (
        "dedup_tasks", "fused_launches", "fused_tasks", "batched_launches",
        "groups_apart_unloaded", "launches", "tasks_done")}
    if form == "apart":
        # (the first pass was the case)
        assert first["launches"] - before["launches"] <= 64
    elif form == "dedup":
        assert moved["dedup_tasks"] > 0
        assert moved["launches"] < moved["tasks_done"]
    elif form == "fused":
        assert moved["fused_launches"] > 0
        assert moved["fused_tasks"] >= 2 * moved["fused_launches"]
        # the sets of the first pass came again and are loaded
        assert moved["groups_apart_unloaded"] == 0
    else:
        assert moved["batched_launches"] > 0
    # whichever threads of this process compiled a group program (a test
    # of another file may build and call one on its own thread), the
    # drain was not among them
    threads = compile_cache().stats()["group_compile_threads"]
    assert "copforge-group" in threads and "sched-drain" not in threads
    assert compile_cache().stats()["fallback_calls"] == 0


# --------------------------------------------------------------------- #
# the invariant, directly
# --------------------------------------------------------------------- #

@pytest.fixture
def no_group_compile_on_the_drain(monkeypatch):
    """Entering the compile cache's resolve seam on the drain thread for
    a group program that is not in the pool raises: it would load or
    compile where every client waits."""
    import tidb_tpu.compilecache.cache as cmod
    real, entered = cmod.CompileCache.resolve, []

    def resolve(self, key, jit_fn, args, execute_ok=True):
        if threading.current_thread().name == "sched-drain" \
                and is_group_key(key) and not self.loaded(key, args):
            entered.append(key.capacity_sig)
            raise AssertionError(
                f"the drain resolves a group program: {key.capacity_sig}")
        return real(self, key, jit_fn, args, execute_ok)
    monkeypatch.setattr(cmod.CompileCache, "resolve", resolve)
    yield entered
    assert not entered, entered


def _together(d, stmts, copies=None):
    """``stmts`` [(class, literal set)], a session each, queued behind a
    paused drain and released at once; every answer checked."""
    copies = copies or [""] * len(stmts)
    errors = []

    def run(i):
        try:
            cls, k = stmts[i]
            d.check(cls, k, copies[i], Session(d.dom).must_query(
                d.sql(cls, k, copies[i])))
        except BaseException as e:  # noqa: BLE001 - surfaced via the assert
            errors.append(e)
    d.sched.pause()
    try:
        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(len(stmts))]
        for t in threads:
            t.start()
        _wait_until(lambda: d.sched.depth >= len(stmts) or errors,
                    msg="the statements queued")
    finally:
        d.sched.resume()
    for t in threads:
        t.join(timeout=120)
    assert not errors, errors[:2]


GROUPS = {
    # three texts of q6 over one snapshot: one fused program
    "fused": ([("q6", 0), ("q6", 1), ("q6", 3)], None, "fused_launches"),
    # one text of q6 over two snapshots: one vmapped program, two slots
    "batched": ([("q6", 2), ("q6", 2)], ["", "2"], "batched_launches"),
}


@pytest.mark.parametrize("form", list(GROUPS))
def test_a_first_seen_set_is_served_apart_and_shares_a_launch_next_time(
        deployment, no_group_compile_on_the_drain, form):
    d, sched = deployment, deployment.sched
    stmts, copies, launches = GROUPS[form]
    _land(sched)
    simulate_restart()
    for i, (cls, k) in enumerate(stmts):        # the solo programs
        Session(d.dom).must_query(
            d.sql(cls, k, copies[i] if copies else ""))
    before = sched.stats()
    cc0 = compile_cache().stats()
    _together(d, stmts, copies)
    first = sched.stats()
    assert first["groups_apart_unloaded"] \
        == before["groups_apart_unloaded"] + 1
    assert first["fused_refused"] == before["fused_refused"]
    assert first["batched_refused"] == before["batched_refused"]
    assert first[launches] == before[launches]
    assert first["launches"] == before["launches"] + len(stmts)
    # nothing compiles a group program by itself: the set comes again
    # and is served apart again, until somebody asks (`warm_groups`)
    time.sleep(0.2)
    _together(d, stmts, copies)
    again = sched.stats()
    assert again["groups_apart_unloaded"] \
        == before["groups_apart_unloaded"] + 2
    assert again["group_compiles_bg"] == before["group_compiles_bg"]
    assert compile_cache().stats()["misses"] == cc0["misses"]
    assert not sched._warm_alive and len(sched._groups_pending) == 1
    _land(sched)                        # the explicit warm
    landed = sched.stats()
    assert landed["group_compiles_bg"] == before["group_compiles_bg"] + 1
    assert landed["warm_failures"] == before["warm_failures"]
    cc1 = compile_cache().stats()
    assert cc1["misses"] == cc0["misses"] + 1
    assert cc1["group_programs_loaded"] == 1
    _together(d, stmts, copies)
    after = sched.stats()
    assert after[launches] == landed[launches] + 1
    assert after["launches"] == landed["launches"] + 1      # ONE launch
    assert after["groups_apart_unloaded"] == landed["groups_apart_unloaded"]
    assert after["group_compiles_bg"] == landed["group_compiles_bg"]
    cc2 = compile_cache().stats()
    assert cc2["misses"] == cc1["misses"]
    assert "copforge-group" in cc2["group_compile_threads"]
    assert "sched-drain" not in cc2["group_compile_threads"]
    assert cc2["fallback_calls"] == cc0["fallback_calls"]


def test_the_launch_span_says_which_form_and_a_group_launch_holds_no_compile(
        deployment, no_group_compile_on_the_drain):
    """``sched.launch`` {``group``, ``members``, ``waiters``}: apart the
    first time, fused the second, dedup for identical statements; no
    task of a fused launch carries a compile."""
    d, sched = deployment, deployment.sched
    Session(d.dom).execute("set global tidb_tpu_trace_sample = 1")
    stmts = [("q6", 3), ("q6", 0), ("q6", 0)]
    _land(sched)
    simulate_restart()
    for cls, k in stmts[:2]:
        Session(d.dom).must_query(d.sql(cls, k, ""))

    def launches():
        out = []
        for ent in d.dom.flight_recorder.index()[:len(stmts)]:
            tree = d.dom.flight_recorder.get(ent["trace_id"])
            out += [sp for sp in tree.spans if sp.name == "sched.launch"]
        return out
    _together(d, stmts)
    forms = sorted((sp.attrs["group"], sp.attrs["members"],
                    sp.attrs["waiters"]) for sp in launches())
    assert forms == [("apart_unloaded", 1, 1), ("apart_unloaded", 1, 2),
                     ("apart_unloaded", 1, 2)], forms
    _land(sched)
    dedup0 = sched.dedup_tasks
    _together(d, stmts)
    spans = launches()
    assert [(sp.attrs["group"], sp.attrs["members"], sp.attrs["waiters"],
             sp.attrs["mode"]) for sp in spans] \
        == [("fused", 2, 3, "fused")] * 3
    assert sched.dedup_tasks == dedup0 + 1
    for ent in d.dom.flight_recorder.index()[:len(stmts)]:
        names = [sp.name for sp in
                 d.dom.flight_recorder.get(ent["trace_id"]).spans]
        assert "sched.compile" not in names, names
    # identical statements in flight: one execution, no group program
    _together(d, [("part_agg", 0)] * 3)
    assert [(sp.attrs["group"], sp.attrs["waiters"]) for sp in launches()] \
        == [("dedup", 3)] * 3
    Session(d.dom).execute("set global tidb_tpu_trace_sample = 16")


def test_the_hold_is_a_span_of_its_own_inside_the_queue(deployment):
    """``sched.hold``: the micro-batch window's hold on a lead, a child
    of the ``sched.queue`` it used to be an unnamed part of, with the
    riders it gained; ``hold_ns_total`` on ``/sched``."""
    d, sched = deployment, deployment.sched
    sess = Session(d.dom)
    sess.execute("set global tidb_tpu_trace_sample = 1")
    sess.execute("set global tidb_tpu_sched_window_us = 50000")
    try:
        sql = d.sql("part_agg", 1, "")
        sess.must_query(sql)            # the knob reaches the scheduler
        h0, w0 = sched.hold_ns_total, sched.window_waits
        out = {}
        t1 = threading.Thread(target=lambda: out.setdefault(
            1, Session(d.dom).must_query(sql)))
        t2 = threading.Thread(target=lambda: out.setdefault(
            2, Session(d.dom).must_query(sql)))
        t1.start()
        time.sleep(0.01)                # inside the lead's 50 ms hold
        t2.start()
        t1.join(timeout=60)
        t2.join(timeout=60)
        assert sched.window_waits > w0
        assert sched.hold_ns_total - h0 >= 40_000_000
        assert sched.stats()["hold_ns_total"] == sched.hold_ns_total
        held = []
        for ent in d.dom.flight_recorder.index()[:2]:
            tree = d.dom.flight_recorder.get(ent["trace_id"])
            by_id = {sp.span_id: sp for sp in tree.spans}
            for sp in tree.spans:
                if sp.name == "sched.hold":
                    queue = by_id[sp.parent_id]
                    assert queue.name == "sched.queue"
                    assert queue.start_ns <= sp.start_ns \
                        and sp.end_ns <= queue.end_ns
                    held.append(sp)
        assert len(held) == 2           # the lead's and the straggler's
        assert {sp.attrs["riders_gained"] for sp in held} == {1}
        # the lead sat through all of it, the straggler through the rest
        ms = sorted((sp.end_ns - sp.start_ns) / 1e6 for sp in held)
        assert ms[1] >= 40 and ms[0] <= ms[1]
    finally:
        sess.execute("set global tidb_tpu_sched_window_us = -1")
        sess.execute("set global tidb_tpu_trace_sample = 16")
        sched.configure(window_us=-1)


# --------------------------------------------------------------------- #
# the bound is on what exists; a restart brings the programs back
# --------------------------------------------------------------------- #

@pytest.fixture
def cache_dir(tmp_path, deployment):
    cc = compile_cache()
    old = (cc.enable, cc.cache_dir, cc.pool_cap_bytes)
    _land(deployment.sched)
    simulate_restart()
    configure(enable=True, cache_dir=str(tmp_path), pool_bytes=None)
    reset_warmed()
    yield str(tmp_path)
    _land(deployment.sched)
    simulate_restart()
    cc.configure(enable=old[0], cache_dir=old[1])
    cc.pool_cap_bytes = old[2]
    reset_warmed()


def test_a_restart_over_the_same_cache_directory_fuses_at_once(
        deployment, cache_dir, no_group_compile_on_the_drain):
    """The fused program a background thread compiled is persisted and
    recorded as a group program; after a restart boot replay loads it,
    the first co-occurrence of the set is ONE fused launch, and nothing
    compiles."""
    d, sched = deployment, deployment.sched
    cc = compile_cache()
    stmts = [("q6", 0), ("q6", 2), ("part_agg", 3)]
    for cls, k in stmts:
        Session(d.dom).must_query(d.sql(cls, k, ""))
    _together(d, stmts)                 # lineitem's two: apart, compiled
    _land(sched)
    assert cc.manifest.group_entries() and cc.group_programs() == 1
    simulate_restart()                  # the process dies, the disk stays
    assert cc.stats()["group_programs_loaded"] == 0 and cc.group_programs() == 1
    assert warm_start(d.dom.client, wait=True) >= 4
    assert cc.stats()["group_programs_loaded"] == 1
    before, misses = sched.stats(), cc.stats()["misses"]
    _together(d, stmts)
    after = sched.stats()
    assert after["fused_launches"] == before["fused_launches"] + 1
    assert after["groups_apart_unloaded"] == before["groups_apart_unloaded"]
    assert after["group_compiles_bg"] == before["group_compiles_bg"]
    assert cc.stats()["misses"] == misses


def test_a_group_program_on_disk_is_loaded_by_the_background_thread(
        deployment, cache_dir, no_group_compile_on_the_drain):
    """Persisted but not in the pool (no boot replay yet): the drain
    serves the set apart and the background thread loads the entry:
    a load, not a compile."""
    d, sched = deployment, deployment.sched
    cc = compile_cache()
    stmts = [("q6", 1), ("q6", 2)]
    for cls, k in stmts:
        Session(d.dom).must_query(d.sql(cls, k, ""))
    _together(d, stmts)
    _land(sched)
    simulate_restart()
    from tidb_tpu.compilecache import warmup
    warmup._WARMED.add(cache_dir)       # this process replays nothing
    before, misses = sched.stats(), cc.stats()["misses"]
    _together(d, stmts)                 # solo programs: disk hits
    _land(sched)
    mid = sched.stats()
    assert mid["groups_apart_unloaded"] == before["groups_apart_unloaded"] + 1
    assert mid["group_loads_bg"] == before["group_loads_bg"] + 1
    assert mid["group_compiles_bg"] == before["group_compiles_bg"]
    assert cc.stats()["misses"] == misses
    _together(d, stmts)
    assert sched.stats()["fused_launches"] == mid["fused_launches"] + 1


def test_once_the_bound_is_reached_no_further_group_program_is_compiled(
        deployment, monkeypatch, no_group_compile_on_the_drain):
    """At most ``GROUP_PROGRAMS_MAX`` group programs exist: a set that
    is not among them is served apart for good, and is not tried
    again."""
    import tidb_tpu.compilecache as pkg
    import tidb_tpu.compilecache.cache as cmod
    d, sched = deployment, deployment.sched
    _land(sched)
    simulate_restart()
    for name in ("warm_group",):
        real = getattr(cmod.CompileCache, name)
        monkeypatch.setattr(
            cmod.CompileCache, name,
            lambda self, key, jit, args, limit=1, real=real:
            real(self, key, jit, args, 1))
    assert pkg.GROUP_PROGRAMS_MAX == 32
    sets = [[("q6", 0), ("q6", 1)], [("q6", 0), ("q6", 2)]]
    for cls, k in {s for st in sets for s in st}:
        Session(d.dom).must_query(d.sql(cls, k, ""))
    before = sched.stats()
    _together(d, sets[0])
    _land(sched)
    for _ in range(2):
        _together(d, sets[1])           # the bound is 1: apart for good
        _land(sched)
    after = sched.stats()
    assert after["group_compiles_bg"] == before["group_compiles_bg"] + 1
    assert after["groups_apart_unloaded"] \
        == before["groups_apart_unloaded"] + 3
    assert after["warm_failures"] == before["warm_failures"]
    assert compile_cache().stats()["group_programs_loaded"] == 1
    f0 = sched.fused_launches
    _together(d, sets[0])               # the one that exists still fuses
    assert sched.fused_launches == f0 + 1


def test_one_client_compiles_no_group_program(deployment):
    """A warm-up of every statement twice by one session: nothing
    co-occurs, so no fused and no batched program is compiled, loaded or
    asked for, in the foreground or the background."""
    d, sched = deployment, deployment.sched
    _land(sched)
    simulate_restart()
    before, cc0 = sched.stats(), compile_cache().stats()
    sess = Session(d.dom)
    n = 0
    for _ in range(2):
        for c, pool in d.pools.items():
            for k in range(len(pool)):
                d.check(c, k, "", sess.must_query(d.sql(c, k, "")))
                n += 1
    _wait_until(lambda: sched.stats()["tasks_done"]
                >= before["tasks_done"] + n, msg="the drain's counts")
    after, cc1 = sched.stats(), compile_cache().stats()
    assert after["launches"] == before["launches"] + n
    for k in ("groups_apart_unloaded", "group_compiles_bg",
              "group_loads_bg", "fused_launches", "batched_launches",
              "dedup_tasks", "warm_failures"):
        assert after[k] == before[k], k
    assert cc1["group_programs_loaded"] == 0
    assert cc1["misses"] == cc0["misses"] + 12      # the solo programs
    assert not sched._groups_pending and not sched._warm_alive
