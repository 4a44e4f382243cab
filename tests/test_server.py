"""MySQL wire protocol server + HTTP status API tests.

Reference analog: pkg/server tests (conn_test.go, tidb_test.go) — a real
client over a real socket against an embedded server, the pattern of
§4.2 (the fake/in-proc backend implements the production interface).
"""

import json
import urllib.request

import pytest

from tidb_tpu.server import MySQLServer, StatusServer
from tidb_tpu.server.client import Client, MySQLError
from tidb_tpu.session.session import Domain


@pytest.fixture(scope="module")
def server():
    srv = MySQLServer(Domain())
    srv.start()
    yield srv
    srv.close()


@pytest.fixture()
def client(server):
    c = Client("127.0.0.1", server.port)
    yield c
    c.close()


def test_handshake_and_select_one(client):
    assert client.query("select 1") == [("1",)]


def test_bad_password_rejected(server):
    with pytest.raises(MySQLError) as ei:
        Client("127.0.0.1", server.port, user="root", password="wrong")
    assert ei.value.errno == 1045


def test_unknown_user_rejected(server):
    with pytest.raises(MySQLError):
        Client("127.0.0.1", server.port, user="nobody")


def test_ddl_dml_query_roundtrip(client):
    client.execute("drop table if exists srv_t")
    client.execute("create table srv_t (a bigint, b varchar(20), "
                   "c decimal(10,2))")
    n = client.execute("insert into srv_t values (1,'x',1.50),"
                       "(2,'y',2.25),(3,null,null)")
    assert n == 3
    rows = client.query("select a, b, c from srv_t order by a")
    assert rows == [("1", "x", "1.50"), ("2", "y", "2.25"),
                    ("3", None, None)]
    rows = client.query("select sum(a), count(b) from srv_t")
    assert rows == [("6", "2")]


def test_error_packet_for_bad_sql(client):
    with pytest.raises(MySQLError):
        client.query("select * from no_such_table_xyz")
    # connection still usable after an error
    assert client.query("select 2") == [("2",)]


def test_init_db_and_use(server):
    c = Client("127.0.0.1", server.port)
    c.execute("create database if not exists srvdb")
    c.execute("use srvdb")
    c.execute("create table t2 (x bigint)")
    c.execute("insert into t2 values (42)")
    assert c.query("select x from t2") == [("42",)]
    c.close()
    # connect directly with db
    c2 = Client("127.0.0.1", server.port, db="srvdb")
    assert c2.query("select x from t2") == [("42",)]
    c2.close()


def test_prepared_statement_binary_protocol(client):
    client.execute("drop table if exists srv_ps")
    client.execute("create table srv_ps (a bigint, b double, c varchar(10))")
    ins = client.prepare("insert into srv_ps values (?, ?, ?)")
    ins.execute(1, 1.5, "one")
    ins.execute(2, 2.5, "two")
    ins.execute(3, None, None)
    ins.close()
    sel = client.prepare("select a, b, c from srv_ps where a >= ? order by a")
    rows = sel.execute(2)
    assert rows == [(2, 2.5, "two"), (3, None, None)]
    sel.close()


def test_multiple_connections_share_domain(server):
    c1 = Client("127.0.0.1", server.port)
    c2 = Client("127.0.0.1", server.port)
    c1.execute("create table if not exists shared_t (v bigint)")
    c1.execute("insert into shared_t values (7)")
    assert c2.query("select v from shared_t") == [("7",)]
    c1.close()
    c2.close()


def test_status_http_api(server):
    st = StatusServer(server.domain)
    st.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{st.port}/status") as r:
            body = json.load(r)
        assert "version" in body
        with urllib.request.urlopen(
                f"http://127.0.0.1:{st.port}/schema") as r:
            schema = json.load(r)
        assert "test" in schema
        with urllib.request.urlopen(
                f"http://127.0.0.1:{st.port}/metrics") as r:
            text = r.read().decode()
        assert "tidb_tpu_query_total" in text
        # the write buffer's counters (PR 40): the connections of the
        # tests above framed packets and flushed them
        for name in ("tidb_tpu_wire_packets_total",
                     "tidb_tpu_wire_flushes_total"):
            (line,) = [ln for ln in text.splitlines()
                       if ln.startswith(name + " ")]
            assert float(line.split()[1]) > 0
    finally:
        st.close()


def test_graceful_shutdown():
    srv = MySQLServer(Domain())
    srv.start()
    c = Client("127.0.0.1", srv.port)
    assert c.query("select 1") == [("1",)]
    c.close()
    srv.close()
    with pytest.raises(OSError):
        Client("127.0.0.1", srv.port)
