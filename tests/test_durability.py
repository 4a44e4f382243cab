"""Durability: WAL + checkpoint in the native engine, catalog-on-KV.

Reference analog: unistore's badger-backed persistence (mvcc.go:50) +
catalog under the `m` prefix (meta.go:78).  VERDICT round-1 item #7:
kill the process mid-workload, restart, and data + schema + DDL state
must be intact.
"""

import os
import signal
import subprocess
import sys
import textwrap

import pytest

from tidb_tpu.session import Domain, Session


def test_kv_wal_replay(tmp_path):
    """Committed writes survive an unclean close (no checkpoint)."""
    from tidb_tpu.store.kv import KVStore
    p = str(tmp_path / "kv")
    s1 = KVStore(path=p)
    t = s1.begin()
    t.put(b"a", b"1")
    t.put(b"b", b"2")
    t.commit()
    ts_mid = s1.alloc_ts()          # snapshot between the two commits
    t2 = s1.begin()
    t2.put(b"a", b"3")
    t2.delete(b"b")
    t2.commit()
    # uncommitted txn: must NOT survive
    t3 = s1.begin()
    t3.put(b"c", b"9")
    # simulate crash: never close/commit, just reopen from the files
    s2 = KVStore(path=p)
    ts = s2.alloc_ts()
    assert s2.get(b"a", ts) == b"3"
    assert s2.get(b"b", ts) is None
    assert s2.get(b"c", ts) is None
    # MVCC history survives too: the pre-update snapshot still reads old
    assert s2.get(b"a", ts_mid) == b"1"
    assert s2.get(b"b", ts_mid) == b"2"
    s1.close()
    s2.close()


def test_kv_checkpoint_compacts(tmp_path):
    from tidb_tpu.store.kv import KVStore
    p = str(tmp_path / "kv")
    s1 = KVStore(path=p)
    for i in range(50):
        t = s1.begin()
        t.put(b"k%03d" % i, b"v%d" % i)
        t.commit()
    n = s1.checkpoint()
    assert n >= 50
    assert os.path.getsize(p + ".wal") == 0
    t = s1.begin()
    t.put(b"post", b"wal")
    t.commit()
    s1.close()
    s2 = KVStore(path=p)
    ts = s2.alloc_ts()
    assert s2.get(b"k007", ts) == b"v7"
    assert s2.get(b"post", ts) == b"wal"   # snap + post-checkpoint WAL
    s2.close()


def test_schema_and_data_survive_restart(tmp_path):
    d = str(tmp_path / "data")
    dom = Domain(data_dir=d)
    s = Session(dom)
    s.execute("create database app")
    s.execute("create table t (id bigint primary key auto_increment, "
              "name varchar(20), score decimal(8,2))")
    s.execute("insert into t (name, score) values ('ann', 1.50), "
              "('bob', 2.25)")
    s.execute("create index iname on t (name)")
    s.execute("insert into t (name, score) values ('cat', 99.99)")
    dom.kv.close()

    dom2 = Domain(data_dir=d)
    s2 = Session(dom2)
    assert "app" in dom2.catalog.databases      # database object survived
    rows = s2.must_query("select id, name, score from t order by id")
    assert [(r[0], r[1], str(r[2])) for r in rows] == [
        (1, "ann", "1.50"), (2, "bob", "2.25"), (3, "cat", "99.99")]
    # schema: index survived and serves lookups
    tbl = dom2.catalog.get_table("test", "t")
    assert tbl.index_by_name("iname") is not None
    assert tbl.index_by_name("PRIMARY") is not None
    plan = "\n".join(r[0] for r in s2.must_query(
        "explain select * from t where name = 'bob'"))
    assert "IndexLookUp" in plan or "CopTask" in plan
    # auto-inc resumes ABOVE every persisted id — the centralized autoid
    # service continues past the last persisted RANGE end after restart
    # (TiDB AUTO_ID_CACHE jump semantics: never reuse, gaps expected)
    s2.execute("insert into t (name, score) values ('dee', 0.01)")
    new_id = s2.must_query("select id from t where name = 'dee'")[0][0]
    assert new_id > 3
    assert s2.must_query(
        "select count(distinct id), count(*) from t") == [(4, 4)]
    dom2.kv.close()


def test_hard_kill_mid_workload(tmp_path):
    """SIGKILL a writer process mid-stream; every row it reported
    committed must be present after reopen (WAL with sync=True)."""
    p = str(tmp_path / "kv")
    kv_py = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tidb_tpu", "store", "kv.py")
    code = textwrap.dedent("""
        # load kv.py by path: the package __init__ imports jax, which this
        # crash-test child must not touch (durability lives in the WAL; the
        # SQL-level restart story is test_schema_and_data_survive_restart)
        import importlib.util
        import os
        import sys
        import types
        kv_py = %r
        # stand-in parents so kv.py's `from ..native import ensure_built`
        # resolves (tidb_tpu/native is jax-free) without running them
        pkg_dir = os.path.dirname(os.path.dirname(kv_py))
        for name, path in (("tidb_tpu", pkg_dir),
                           ("tidb_tpu.store", os.path.dirname(kv_py))):
            mod = types.ModuleType(name)
            mod.__path__ = [path]
            sys.modules[name] = mod
        spec = importlib.util.spec_from_file_location("tidb_tpu.store.kv",
                                                      kv_py)
        kvmod = importlib.util.module_from_spec(spec)
        sys.modules[spec.name] = kvmod  # dataclasses resolves via sys.modules
        spec.loader.exec_module(kvmod)
        assert "jax" not in sys.modules
        s = kvmod.KVStore(path=%r, sync=True)
        i = 0
        while True:
            t = s.begin()
            t.put(b"k%%08d" %% i, b"v%%d" %% (i * 10))
            t.commit()
            print(i, flush=True)
            i += 1
    """ % (kv_py, p))
    proc = subprocess.Popen([sys.executable, "-c", code],
                            stdout=subprocess.PIPE, text=True)
    acked = -1
    try:
        while acked < 200:
            line = proc.stdout.readline()
            if not line:
                raise AssertionError("writer died early")
            acked = int(line)
    finally:
        proc.kill()
        proc.wait()

    from tidb_tpu.store.kv import KVStore
    s = KVStore(path=p)
    ts = s.alloc_ts()
    rows = list(s.scan(b"k", b"l", ts))
    # every acked commit is present; an unacked trailing one may be too
    assert len(rows) >= acked + 1, (len(rows), acked)
    for i, (k, v) in enumerate(rows):
        assert k == b"k%08d" % i and v == b"v%d" % (i * 10)
    s.close()


def test_ddl_job_history_survives(tmp_path):
    d = str(tmp_path / "data")
    dom = Domain(data_dir=d)
    s = Session(dom)
    s.execute("create table t (a bigint, b bigint)")
    s.execute("insert into t values (1, 2), (3, 4)")
    s.execute("alter table t add index ib (b)")
    hist = s.must_query("admin show ddl jobs")
    assert hist
    dom.kv.close()

    dom2 = Domain(data_dir=d)
    s2 = Session(dom2)
    hist2 = s2.must_query("admin show ddl jobs")
    assert len(hist2) >= len(hist)   # archived jobs persisted in KV
    tbl = dom2.catalog.get_table("test", "t")
    ix = tbl.index_by_name("ib")
    assert ix is not None and ix.state == "public"
    dom2.kv.close()


def test_drop_table_purges_data_and_ids_never_reused(tmp_path):
    d = str(tmp_path / "data")
    dom = Domain(data_dir=d)
    s = Session(dom)
    s.execute("create table a (x bigint)")
    s.execute("insert into a values (1), (2), (3)")
    tid_a = dom.catalog.get_table("test", "a").table_id
    s.execute("drop table a")
    # record+index range no longer visible (MVCC delete-range purge)
    from tidb_tpu.store.codec import encode_int_key
    lo = b"t" + encode_int_key(tid_a)
    rows = list(dom.kv.scan(lo, lo + b"\xff", dom.kv.alloc_ts()))
    assert rows == []
    dom.kv.close()

    dom2 = Domain(data_dir=d)
    s2 = Session(dom2)
    s2.execute("create table b (y bigint)")
    tid_b = dom2.catalog.get_table("test", "b").table_id
    assert tid_b > tid_a                # dropped id never reused
    assert s2.must_query("select count(*) from b") == [(0,)]
    dom2.kv.close()


def test_torn_tail_then_more_commits(tmp_path):
    """A torn WAL tail is truncated at reopen so records appended AFTER a
    crash are not stranded behind garbage (review finding)."""
    from tidb_tpu.store.kv import KVStore
    p = str(tmp_path / "kv")
    s1 = KVStore(path=p)
    for i in range(5):
        t = s1.begin()
        t.put(b"k%d" % i, b"v%d" % i)
        t.commit()
    s1.close()
    # simulate a crash mid-append: write half a record at the tail
    with open(p + ".wal", "ab") as f:
        f.write(b"\x00\x01\x02\x03garbage")
    s2 = KVStore(path=p)     # replays 5 records, truncates the tear
    t = s2.begin()
    t.put(b"post", b"tear")
    t.commit()
    s2.close()
    s3 = KVStore(path=p)
    ts = s3.alloc_ts()
    assert s3.get(b"k3", ts) == b"v3"
    assert s3.get(b"post", ts) == b"tear"   # NOT stranded behind the tear
    s3.close()
