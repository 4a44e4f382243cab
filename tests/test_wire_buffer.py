"""The connection's write buffer (PR 40): ``PacketIO.write`` frames a
packet into a buffer and ``flush`` hands it to ONE ``sendall``.

What a buffer can get wrong, each held here: the bytes on the socket (they
are the packet-by-packet framing of the parent, sequence ids included);
how many ``sendall`` calls carry them and how much the buffer holds; and
the orderings: an error after rows, every write the peer must answer
before the server reads again, commands back to back, a command with no
answer, a peer that goes away mid-answer.
"""

import socket
import threading
import time

import pytest

from tidb_tpu.server import packet as P
from tidb_tpu.server import mysql_server as M
from tidb_tpu.server.client import Client, MySQLError
from tidb_tpu.server.mysql_server import (MAX_PAYLOAD, WRITE_BUFFER_BYTES,
                                          ClientConn, MySQLServer, PacketIO)
from tidb_tpu.session.session import ResultSet
from tidb_tpu.testing.mysql_client import MiniMySQLClient
from tidb_tpu.types import dtypes as dt


# ------------------------------------------------------------------ #
# the bytes, the sendalls, the buffer
# ------------------------------------------------------------------ #

class CountingSock:
    """One end of a socket pair; every ``sendall`` is counted."""

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self.sends: list[int] = []

    def sendall(self, data):
        self.sends.append(len(data))
        self.sock.sendall(data)

    def recv(self, n):
        return self.sock.recv(n)

    def close(self):
        self.sock.close()


def framed_one_by_one(payloads, seq: int) -> bytes:
    """The parent's framing: a packet a payload (a payload of 0xFFFFFF
    bytes or more split, an empty packet after an exact multiple), the
    sequence id counted through."""
    out = bytearray()
    for payload in payloads:
        data = payload
        while True:
            chunk, data = data[:0xFFFFFF], data[0xFFFFFF:]
            out += len(chunk).to_bytes(3, "little") + bytes([seq]) + chunk
            seq = (seq + 1) & 0xFF
            if len(chunk) < 0xFFFFFF:
                break
    return bytes(out)


def packets_of(stream: bytes) -> list[tuple[int, bytes]]:
    """(sequence id, payload) of every packet of a byte stream."""
    out, pos = [], 0
    while pos < len(stream):
        n = int.from_bytes(stream[pos:pos + 3], "little")
        out.append((stream[pos + 3], stream[pos + 4:pos + 4 + n]))
        pos += 4 + n
    assert pos == len(stream), "a packet is cut short"
    return out


@pytest.fixture(scope="module")
def unstarted_server():
    """A server that listens nowhere: ``ClientConn`` needs its domain
    and its two counters, not its accept loop."""
    return MySQLServer()


@pytest.fixture()
def conn_pair(unstarted_server):
    """A ``ClientConn`` writing into a counted end of a socket pair, and
    a thread that collects what arrives at the other end."""
    a, b = socket.socketpair()
    sock = CountingSock(a)
    conn = ClientConn(unstarted_server, sock)
    got = bytearray()

    def drain():
        while True:
            chunk = b.recv(1 << 20)
            if not chunk:
                return
            got.extend(chunk)

    t = threading.Thread(target=drain, daemon=True)
    t.start()

    def received() -> bytes:
        a.shutdown(socket.SHUT_WR)
        t.join(timeout=60)
        assert not t.is_alive()
        return bytes(got)

    try:
        yield conn, sock, received
    finally:
        conn.session.close()
        a.close()
        b.close()


NAMES = ["id", "v", "x"]
DTYPES = [dt.bigint(nullable=False), dt.varchar(), dt.double()]


def _rows(n: int) -> list[tuple]:
    return [(i, None if i % 7 == 3 else f"row{i:08d}" * 3, i / 4)
            for i in range(n)]


ROWSETS = {
    "0_rows": lambda: _rows(0),
    "1_row": lambda: _rows(1),
    "25_rows": lambda: _rows(25),
    "5000_rows": lambda: _rows(5000),
    # one row whose payload passes 0xFFFFFF bytes: two packets, between rows
    "row_past_16MiB": lambda: [(1, "a", 0.5),
                               (2, "b" * (MAX_PAYLOAD + 10), 1.5),
                               (3, "c", 2.5)],
}


@pytest.mark.parametrize("rowset", list(ROWSETS))
@pytest.mark.parametrize("protocol", ["text", "binary"])
def test_result_set_bytes_and_sendalls(conn_pair, protocol, rowset):
    conn, sock, received = conn_pair
    binary = protocol == "binary"
    rows = ROWSETS[rowset]()
    rs = ResultSet(names=NAMES, rows=rows, dtypes=DTYPES)
    status = conn._status()
    payloads = [P.put_lenenc_int(len(NAMES))]
    payloads += [P.column_def(n, t, conn.session.db)
                 for n, t in zip(NAMES, DTYPES)]
    payloads.append(P.eof_packet(status))
    payloads += [P.binary_row(r, DTYPES) if binary else P.text_row(r)
                 for r in rows]
    payloads.append(P.eof_packet(status))
    want = framed_one_by_one(payloads, seq=1)

    # the buffer's high-water mark, read after every packet
    held = []
    write = conn.io.write

    def watched(payload):
        write(payload)
        held.append(len(conn.io._out))

    conn.io.write = watched
    conn.io.seq = 1                 # the command's packet was 0
    conn._write_result(rs, binary)
    assert not conn.io._out, "the answer's flush left bytes behind"
    got = received()

    assert got == want
    seqs = [seq for seq, _ in packets_of(got)]
    assert seqs == [(1 + i) & 0xFF for i in range(len(seqs))]
    split = rowset == "row_past_16MiB"      # that row is two packets
    assert len(seqs) == len(payloads) + split
    assert sum(sock.sends) == len(want)
    if len(want) < WRITE_BUFFER_BYTES:
        assert sock.sends == [len(want)], "a small answer is ONE sendall"
    else:
        assert len(sock.sends) <= -(-len(want) // WRITE_BUFFER_BYTES) + 1
    # never more than the threshold plus one packet, and under the
    # threshold whenever ``write`` returns
    largest = max(min(len(p), MAX_PAYLOAD) + 4 for p in payloads)
    assert max(sock.sends) < WRITE_BUFFER_BYTES + largest
    assert max(held) < WRITE_BUFFER_BYTES
    assert conn.io.packets == len(seqs)
    assert conn.io.bytes_out == len(want)
    assert conn.io.flushes == len(sock.sends)


@pytest.mark.parametrize("size", [0, 1, MAX_PAYLOAD - 1, MAX_PAYLOAD,
                                  MAX_PAYLOAD + 1],
                         ids=["empty", "one", "below_split", "at_split",
                              "past_split"])
def test_packet_split_matches_the_parents(size):
    """``PacketIO`` alone at the split's edges: a payload of exactly
    0xFFFFFF bytes is followed by an empty packet; ``read`` puts a split
    payload together again."""
    a, b = socket.socketpair()
    try:
        out, payload = PacketIO(CountingSock(a)), b"z" * size
        got = bytearray()
        want = framed_one_by_one([payload, b"after"], seq=0)

        def drain():
            while len(got) < len(want):
                got.extend(b.recv(1 << 20))

        t = threading.Thread(target=drain, daemon=True)
        t.start()
        out.write(payload)
        out.write(b"after")
        out.flush()
        t.join(timeout=60)
        assert bytes(got) == want
        assert out.seq == len(packets_of(want))

        class Replay:
            def __init__(self, data):
                self.data = memoryview(data)

            def recv(self, n):
                head, self.data = self.data[:n], self.data[n:]
                return bytes(head)

        back = PacketIO(Replay(want))
        assert back.read() == payload
        assert back.read() == b"after"
    finally:
        a.close()
        b.close()


# ------------------------------------------------------------------ #
# the orderings a buffer can break
# ------------------------------------------------------------------ #

@pytest.fixture(scope="module")
def server():
    srv = MySQLServer()
    srv.start()
    c = Client("127.0.0.1", srv.port)
    c.execute("create database if not exists wb")
    c.execute("create table wb.t (id int primary key, v varchar(20))")
    c.execute("insert into wb.t values " + ",".join(
        f"({i}, 'row{i}')" for i in range(40)))
    c.execute("create table wb.big (id int primary key, v varchar(20))")
    c.execute("insert into wb.big values " + ",".join(
        f"({i}, 'row{i:06d}')" for i in range(5000)))
    c.close()
    yield srv
    srv.close()


def _only_conn(srv) -> ClientConn:
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline:
        with srv._lock:
            conns = list(srv._conns)
        if len(conns) == 1:
            return conns[0]
        time.sleep(0.01)
    raise AssertionError(f"{len(conns)} connections, wanted one")


class RawConn:
    """A client that keeps every packet it reads, sequence id and all."""

    def __init__(self, port: int):
        self.c = MiniMySQLClient("127.0.0.1", port, database="wb")
        self.c.sock.settimeout(10)

    def command(self, cmd: int, body: bytes):
        self.c._command(cmd, body)

    def packet(self) -> tuple[int, bytes]:
        hdr = self.c._read_n(4)
        return hdr[3], self.c._read_n(int.from_bytes(hdr[:3], "little"))

    def close(self):
        self.c.close()


def test_error_after_rows_goes_out_behind_them(server, monkeypatch):
    """An error raised after rows were written: the rows, then the ERR
    packet, in one flush, the sequence ids continuous."""
    text_row = P.text_row

    def failing(row):
        if row[0] == 2:
            raise RuntimeError("row 2 cannot be written")
        return text_row(row)

    raw = RawConn(server.port)
    try:
        conn = _only_conn(server)
        flushes, packets = conn.io.flushes, conn.io.packets
        monkeypatch.setattr(M.P, "text_row", failing)
        raw.command(P.COM_QUERY, b"select id, v from t where id < 5 "
                                 b"order by id")
        got = []
        while True:
            seq, payload = raw.packet()
            got.append((seq, payload))
            if payload[0] == 0xFF:
                break
        # count, two definitions, EOF, rows 0 and 1, ERR
        assert [seq for seq, _ in got] == list(range(1, 8))
        assert [p for _, p in got[4:6]] == [text_row((0, "row0")),
                                            text_row((1, "row1"))]
        assert b"row 2 cannot be written" in got[-1][1]
        assert conn.io.flushes - flushes == 1
        assert conn.io.packets - packets == 7
        # the connection serves the next command from sequence id 1
        monkeypatch.setattr(M.P, "text_row", text_row)
        raw.command(P.COM_QUERY, b"select 1")
        assert raw.packet()[0] == 1
    finally:
        raw.close()


@pytest.mark.parametrize("flow", ["greeting", "auth_switch",
                                  "sha2_full_auth_tls", "prepare_params"])
def test_writes_before_a_read_reach_the_peer(server, flow):
    """Every write the peer must answer has left the buffer when the
    server blocks in ``read``: the peer gets it (it would wait out its
    timeout behind a buffer nobody flushed)."""
    if flow == "greeting":
        # greeting -> response -> OK, then a command
        c = MiniMySQLClient("127.0.0.1", server.port)
        assert c.query("select 1+1") == [("2",)]
    elif flow == "auth_switch":
        # an unknown plugin: the server writes AuthSwitchRequest and reads
        c = MiniMySQLClient("127.0.0.1", server.port,
                            auth_plugin="sha256_password")
        assert c.query("select 2") == [("2",)]
    elif flow == "sha2_full_auth_tls":
        # greeting, SSLRequest, TLS, AuthMoreData(full auth), the
        # cleartext password, OK: four writes each followed by a read
        server.sha2_cache.clear()
        c = MiniMySQLClient("127.0.0.1", server.port, use_tls=True,
                            auth_plugin="caching_sha2_password")
        assert c.tls and c.query("select 3") == [("3",)]
        assert "root" in server.sha2_cache
    else:
        # COM_STMT_PREPARE with parameters: head, two definitions, EOF
        c = MiniMySQLClient("127.0.0.1", server.port)
        stmt_id, n_params = c.prepare(
            "select id from wb.t where id > ? and id < ?")
        assert n_params == 2 and stmt_id >= 1
        assert c.query("select 4") == [("4",)]
    conn = _only_conn(server)
    assert conn.io.flushes >= 2         # the handshake's, the command's
    assert not conn.io._out
    c.close()


def test_clients_commands_back_to_back(server):
    """``server/client.py`` writes through the same class: each command
    is flushed by the read of its answer, in order."""
    c = Client("127.0.0.1", server.port, db="wb")
    try:
        flushes = c.io.flushes
        assert c.query("select count(*) from t") == [("40",)]
        assert c.query("select v from t where id = 7") == [("row7",)]
        assert c.io.flushes - flushes == 2 and not c.io._out
        with pytest.raises(MySQLError):
            c.query("select nothing from nowhere")
        assert c.query("select 5") == [("5",)]
    finally:
        c.close()
    # COM_QUIT was flushed by close(): the server ends the connection
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and server._conns:
        time.sleep(0.01)
    assert not server._conns


def test_stmt_close_leaves_nothing_behind(server):
    """COM_STMT_CLOSE has no answer: the server's buffer is empty after
    it and the next command's answer starts at sequence id 1; the
    client's COM_STMT_CLOSE has left before ``close`` returns."""
    c = Client("127.0.0.1", server.port, db="wb")
    try:
        conn = _only_conn(server)
        st = c.prepare("select v from t where id = ?")
        assert st.execute(3) == [("row3",)]
        packets, flushes = conn.io.packets, conn.io.flushes
        st.close()
        assert not c.io._out
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and conn.stmts:
            time.sleep(0.005)
        assert not conn.stmts, "the server never saw COM_STMT_CLOSE"
        assert (conn.io.packets, conn.io.flushes) == (packets, flushes)
        assert c.query("select 6") == [("6",)]
        assert c.io.seq == 6        # count, definition, EOF, row, EOF: 1..5
    finally:
        c.close()


def test_peer_that_closes_mid_answer_ends_the_connection(server):
    """A client that goes away while a large answer is written: the
    ``ConnectionError`` of a flush ends the connection, as the one of a
    packet's ``sendall`` did."""
    raw = RawConn(server.port)
    conn = _only_conn(server)

    class Closing(CountingSock):
        """The connection's socket, whose peer is gone after the first
        ``sendall`` of an answer."""

        def sendall(self, data):
            if self.sends:
                self.sends.append(len(data))
                raise BrokenPipeError("peer closed")
            super().sendall(data)

    conn.io.sock = closing = Closing(conn.io.sock)
    # 5,000 rows of 21 bytes: past the buffer once, so two sendalls
    raw.command(P.COM_QUERY, b"select id, v from big")
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline and server._conns:
        time.sleep(0.01)
    assert not server._conns, "the connection outlived its peer"
    assert len(closing.sends) == 2
    assert closing.sends[0] >= WRITE_BUFFER_BYTES
    raw.close()


def test_accepted_sockets_do_not_delay(server):
    c = Client("127.0.0.1", server.port)
    try:
        sock = _only_conn(server).sock
        assert sock.getsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY)
    finally:
        c.close()


def test_field_list_is_one_flush(server):
    """COM_FIELD_LIST writes a definition a column and an EOF with no
    ``wire.write`` around them: the command's flush sends them."""
    raw = RawConn(server.port)
    try:
        conn = _only_conn(server)
        flushes = conn.io.flushes
        raw.command(P.COM_FIELD_LIST, b"t\x00")
        got = [raw.packet() for _ in range(3)]
        assert [seq for seq, _ in got] == [1, 2, 3]
        assert got[-1][1][0] == 0xFE
        assert conn.io.flushes - flushes == 1
    finally:
        raw.close()
