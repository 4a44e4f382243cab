"""A minimal MySQL wire client: handshake v10 with an empty password,
COM_QUERY, text result sets.  It is the yardstick's and not the program's:
it imports nothing from ``tidb_tpu`` and nothing that imports JAX, so the
load generator's process can use it, and no later PR to the program's own
client can change what a statement's time includes.

A statement's time, as the benchmark reads it, is from the ``sendall`` of
the COM_QUERY packet to the last row decoded into Python strings.
"""

from __future__ import annotations

import socket
import struct

_COM_QUIT, _COM_QUERY = 0x01, 0x03
_CLIENT_LONG_PASSWORD = 1 << 0
_CLIENT_CONNECT_WITH_DB = 1 << 3
_CLIENT_PROTOCOL_41 = 1 << 9
_CLIENT_SECURE_CONNECTION = 1 << 15
_CLIENT_PLUGIN_AUTH = 1 << 19


class WireError(RuntimeError):
    """The server answered with an ERR packet."""

    def __init__(self, errno: int, msg: str):
        super().__init__(f"({errno}) {msg}")
        self.errno = errno


def _lenenc_int(buf: bytes, pos: int) -> tuple[int, int]:
    first = buf[pos]
    if first < 0xFB:
        return first, pos + 1
    if first == 0xFC:
        return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
    if first == 0xFD:
        return int.from_bytes(buf[pos + 1:pos + 4], "little"), pos + 4
    return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9


class Connection:
    """One connection as user ``root`` with no password."""

    def __init__(self, host: str, port: int, db: str = "",
                 timeout: float = 900.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._buf = b""
        greeting = self._read()
        if greeting[0] == 0xFF:
            self._raise(greeting)
        if greeting[0] != 0x0A:
            raise ConnectionError(f"not a v10 handshake: {greeting[:1]!r}")
        caps = (_CLIENT_PROTOCOL_41 | _CLIENT_SECURE_CONNECTION
                | _CLIENT_PLUGIN_AUTH | _CLIENT_LONG_PASSWORD)
        if db:
            caps |= _CLIENT_CONNECT_WITH_DB
        p = struct.pack("<IIB23x", caps, 1 << 24, 33)
        p += b"root\x00" + b"\x00"              # empty password: no scramble
        if db:
            p += db.encode() + b"\x00"
        p += b"mysql_native_password\x00"
        self._write(p, seq=1)
        resp = self._read()
        if resp[0] == 0xFF:
            self._raise(resp)

    def close(self) -> None:
        try:
            self._write(bytes([_COM_QUIT]), seq=0)
        except OSError:
            pass
        self.sock.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    def query(self, sql: str) -> list[tuple]:
        """Rows of a statement as tuples of ``str`` or ``None``; an empty
        list for a statement that returns no result set."""
        self._write(bytes([_COM_QUERY]) + sql.encode(), seq=0)
        first = self._read()
        if first[0] == 0xFF:
            self._raise(first)
        if first[0] == 0x00:
            return []
        n_cols, _ = _lenenc_int(first, 0)
        for _ in range(n_cols):
            self._read()                        # column definitions
        if self._read()[0] != 0xFE:
            raise ConnectionError("no EOF after the column definitions")
        rows = []
        while True:
            pkt = self._read()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return rows
            if pkt[0] == 0xFF:
                self._raise(pkt)
            row, pos = [], 0
            for _ in range(n_cols):
                if pkt[pos] == 0xFB:
                    row.append(None)
                    pos += 1
                else:
                    n, pos = _lenenc_int(pkt, pos)
                    row.append(pkt[pos:pos + n].decode())
                    pos += n
            rows.append(tuple(row))

    # ---------------------------------------------------------------- #

    def _raise(self, payload: bytes):
        errno = struct.unpack_from("<H", payload, 1)[0]
        raise WireError(errno, payload[9:].decode(errors="replace"))

    def _write(self, payload: bytes, seq: int) -> None:
        out = b""
        while True:
            chunk, payload = payload[:0xFFFFFF], payload[0xFFFFFF:]
            out += len(chunk).to_bytes(3, "little") + bytes([seq & 0xFF]) \
                + chunk
            seq += 1
            if len(chunk) < 0xFFFFFF:
                break
        self.sock.sendall(out)

    def _read_n(self, n: int) -> bytes:
        while len(self._buf) < n:
            got = self.sock.recv(65536)
            if not got:
                raise ConnectionError("server closed the connection")
            self._buf += got
        out, self._buf = self._buf[:n], self._buf[n:]
        return out

    def _read(self) -> bytes:
        payload = b""
        while True:
            header = self._read_n(4)
            length = int.from_bytes(header[:3], "little")
            payload += self._read_n(length)
            if length < 0xFFFFFF:
                return payload
