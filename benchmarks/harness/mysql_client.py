"""MiniClient: copy of tests/mysql_client.py (PR 23), kept with the yardstick.

Pure sockets; imports neither jax nor tidb_tpu. The load generator child and
the post-window read-back use it. Later PRs may change the program; they may
not change this file.
"""

from __future__ import annotations

import hashlib
import socket
import struct
from typing import Any, Optional


class MySQLError(Exception):
    def __init__(self, code: int, message: str,
                 sqlstate: str = "HY000") -> None:
        super().__init__(f"({code}) {message}")
        self.code = code
        self.sqlstate = sqlstate


class MiniClient:
    def __init__(self, host: str, port: int, user: str = "root",
                 password: str = "", db: str = "",
                 timeout: float = 120.0, use_ssl: bool = False,
                 preamble: bytes = b"") -> None:
        # generous default: under full-suite load (one core, a jax
        # compile in a sibling) a first query can take tens of seconds;
        # a 10s cap made test_multiproc flaky (round-4 verdict weak #3)
        self.sock = socket.create_connection((host, port), timeout=timeout)
        if preamble:  # e.g. a PROXY protocol header a LB would send
            self.sock.sendall(preamble)
        self.rfile = self.sock.makefile("rb")
        self.wfile = self.sock.makefile("wb")
        self.seq = 0
        self.tls = False
        self._handshake(user, password, db, use_ssl)

    # ---- framing -----------------------------------------------------------
    def _read_packet(self) -> bytes:
        header = self.rfile.read(4)
        if len(header) < 4:
            raise ConnectionError("server closed connection")
        n = int.from_bytes(header[:3], "little")
        self.seq = (header[3] + 1) % 256
        data = self.rfile.read(n)
        if len(data) < n:
            raise ConnectionError("short packet")
        return data

    def _write_packet(self, payload: bytes) -> None:
        self.wfile.write(len(payload).to_bytes(3, "little")
                         + bytes([self.seq]) + payload)
        self.wfile.flush()
        self.seq = (self.seq + 1) % 256

    # ---- handshake ---------------------------------------------------------
    def _handshake(self, user: str, password: str, db: str,
                   use_ssl: bool) -> None:
        greet = self._read_packet()
        if greet[0] == 0xFF:
            # the server may reject with an ERR packet in place of the
            # greeting (errno 1040 at the connection gate)
            raise MySQLError(*_parse_err(greet))
        assert greet[0] == 0x0A, "expected protocol v10 handshake"
        pos = greet.index(b"\x00", 1) + 1  # server version
        pos += 4  # thread id
        salt = greet[pos:pos + 8]
        pos += 9  # salt part1 + filler
        server_caps = int.from_bytes(greet[pos:pos + 2], "little")
        pos += 2 + 1 + 2  # caps low, charset, status
        server_caps |= int.from_bytes(greet[pos:pos + 2], "little") << 16
        pos += 2  # caps high
        pos += 1 + 10  # auth len + reserved
        salt += greet[pos:pos + 12]
        caps = 0x0F7FF  # PROTOCOL_41 | SECURE_CONNECTION | CONNECT_WITH_DB...
        if use_ssl:
            if not server_caps & 0x800:
                raise MySQLError(2026, "server does not support SSL")
            import ssl as _ssl
            caps |= 0x800  # CLIENT_SSL
            # SSLRequest: caps + max packet + charset + 23 filler bytes,
            # then upgrade the socket and continue the sequence encrypted
            self._write_packet(
                struct.pack("<IIB", caps, 2**24 - 1, 255) + b"\x00" * 23)
            ctx = _ssl.SSLContext(_ssl.PROTOCOL_TLS_CLIENT)
            ctx.check_hostname = False
            ctx.verify_mode = _ssl.CERT_NONE
            self.sock = ctx.wrap_socket(self.sock)
            self.rfile = self.sock.makefile("rb")
            self.wfile = self.sock.makefile("wb")
            self.tls = True
        auth = _scramble(password, salt) if password else b""
        payload = struct.pack("<IIB", caps, 2**24 - 1, 255) + b"\x00" * 23
        payload += user.encode() + b"\x00"
        payload += bytes([len(auth)]) + auth
        payload += (db.encode() + b"\x00") if db else b"\x00"
        self._write_packet(payload)
        resp = self._read_packet()
        if resp[0] == 0xFF:
            raise MySQLError(*_parse_err(resp))

    # ---- queries -----------------------------------------------------------
    def query(self, sql: str) -> list[tuple[Optional[str], ...]]:
        """COM_QUERY; returns rows of decoded text values (None = NULL)."""
        self.seq = 0
        self._write_packet(b"\x03" + sql.encode("utf-8"))
        first = self._read_packet()
        if first[0] == 0xFF:
            raise MySQLError(*_parse_err(first))
        if first[0] == 0x00:
            return []  # OK packet: no resultset
        ncols, _ = _lenenc(first, 0)
        self.columns = []
        for _ in range(ncols):
            cd = self._read_packet()
            self.columns.append(_column_name(cd))
        eof = self._read_packet()
        assert eof[0] == 0xFE
        rows = []
        while True:
            data = self._read_packet()
            if data[0] == 0xFE and len(data) < 9:
                break
            if data[0] == 0xFF:
                raise MySQLError(*_parse_err(data))
            rows.append(_parse_text_row(data, ncols))
        return rows

    def execute(self, sql: str) -> int:
        """COM_QUERY for statements; returns affected rows."""
        self.seq = 0
        self._write_packet(b"\x03" + sql.encode("utf-8"))
        first = self._read_packet()
        if first[0] == 0xFF:
            raise MySQLError(*_parse_err(first))
        if first[0] == 0x00:
            affected, _ = _lenenc(first, 1)
            return affected
        # resultset: drain it
        ncols, _ = _lenenc(first, 0)
        for _ in range(ncols):
            self._read_packet()
        while True:
            data = self._read_packet()
            if data[0] == 0xFE and len(data) < 9:
                break
        while True:
            data = self._read_packet()
            if data[0] == 0xFE and len(data) < 9:
                break
        return 0

    def ping(self) -> bool:
        self.seq = 0
        self._write_packet(b"\x0e")
        return self._read_packet()[0] == 0x00

    def init_db(self, db: str) -> None:
        self.seq = 0
        self._write_packet(b"\x02" + db.encode())
        resp = self._read_packet()
        if resp[0] == 0xFF:
            raise MySQLError(*_parse_err(resp))

    def close(self) -> None:
        try:
            self.seq = 0
            self._write_packet(b"\x01")  # COM_QUIT
        except OSError:
            pass
        self.sock.close()


def _scramble(password: str, salt: bytes) -> bytes:
    p1 = hashlib.sha1(password.encode()).digest()
    p2 = hashlib.sha1(p1).digest()
    p3 = hashlib.sha1(salt + p2).digest()
    return bytes(a ^ b for a, b in zip(p1, p3))


def _lenenc(buf: bytes, pos: int) -> tuple[int, int]:
    first = buf[pos]
    if first < 251:
        return first, pos + 1
    if first == 0xFC:
        return int.from_bytes(buf[pos + 1:pos + 3], "little"), pos + 3
    if first == 0xFD:
        return int.from_bytes(buf[pos + 1:pos + 4], "little"), pos + 4
    return int.from_bytes(buf[pos + 1:pos + 9], "little"), pos + 9


def _parse_err(data: bytes) -> tuple[int, str, str]:
    code = int.from_bytes(data[1:3], "little")
    msg = data[3:].decode("utf-8", "replace")
    state = "HY000"
    if msg.startswith("#"):
        state, msg = msg[1:6], msg[6:]
    return code, msg, state


def _column_name(cd: bytes) -> str:
    pos = 0
    for _ in range(4):  # catalog, schema, table, org_table
        n, pos = _lenenc(cd, pos)
        pos += n
    n, pos = _lenenc(cd, pos)
    return cd[pos:pos + n].decode()


def _parse_text_row(data: bytes, ncols: int) -> tuple[Optional[str], ...]:
    out: list[Optional[str]] = []
    pos = 0
    for _ in range(ncols):
        if data[pos] == 0xFB:
            out.append(None)
            pos += 1
        else:
            n, pos = _lenenc(data, pos)
            out.append(data[pos:pos + n].decode("utf-8"))
            pos += n
    return tuple(out)
