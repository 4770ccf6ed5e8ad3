"""The benchmark's own MySQL wire client: a copy of the text-protocol part of
`galaxysql_tpu/net/client.py` (`MiniClient`) with the few packet helpers it
needs, so that the yardstick does not move when the program's client does and
so that a load-generator process imports nothing of the program (and no JAX).

Handshake v10 + mysql_native_password, COM_QUERY with text result sets.  A
call to `query` returns after the last result packet was received: the
server's work for the statement is finished and consumed."""

from __future__ import annotations

import hashlib
import socket
import struct
from typing import List, Optional, Tuple

CLIENT_CONNECT_WITH_DB = 8
CLIENT_PROTOCOL_41 = 512
CLIENT_TRANSACTIONS = 8192
CLIENT_SECURE_CONNECTION = 32768
CLIENT_MULTI_STATEMENTS = 1 << 16
CLIENT_PLUGIN_AUTH = 1 << 19
SERVER_MORE_RESULTS_EXISTS = 8
COM_QUIT = 0x01
COM_QUERY = 0x03


class WireError(Exception):
    """An ER packet from the server."""

    def __init__(self, errno: int, sqlstate: str, message: str):
        super().__init__(f"({errno}, {sqlstate}): {message}")
        self.errno = errno
        self.sqlstate = sqlstate
        self.message = message


def read_lenenc_int(buf: bytes, pos: int) -> Tuple[int, int]:
    first = buf[pos]
    if first < 0xFB:
        return first, pos + 1
    if first == 0xFC:
        return struct.unpack_from("<H", buf, pos + 1)[0], pos + 3
    if first == 0xFD:
        return struct.unpack_from("<I", buf[pos + 1:pos + 4] + b"\0")[0], pos + 4
    return struct.unpack_from("<Q", buf, pos + 1)[0], pos + 9


def read_lenenc_str(buf: bytes, pos: int) -> Tuple[bytes, int]:
    n, pos = read_lenenc_int(buf, pos)
    return buf[pos:pos + n], pos + n


def native_password_scramble(password: bytes, seed: bytes) -> bytes:
    """mysql_native_password: SHA1(pw) XOR SHA1(seed + SHA1(SHA1(pw)))."""
    if not password:
        return b""
    h1 = hashlib.sha1(password).digest()
    h2 = hashlib.sha1(h1).digest()
    h3 = hashlib.sha1(seed + h2).digest()
    return bytes(a ^ b for a, b in zip(h1, h3))


class WireClient:
    def __init__(self, host: str, port: int, user: str = "root",
                 password: str = "", database: Optional[str] = None,
                 timeout: float = 1100.0):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        self.more_results = False
        self._handshake(user, password, database)

    # -- framing -------------------------------------------------------------

    def _recvn(self, n: int) -> bytes:
        buf = b""
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("server closed connection")
            buf += chunk
        return buf

    def _read_packet(self) -> bytes:
        payload = b""
        while True:
            header = self._recvn(4)
            length = header[0] | (header[1] << 8) | (header[2] << 16)
            self.seq = (header[3] + 1) & 0xFF
            payload += self._recvn(length)
            if length < 0xFFFFFF:
                return payload

    def _send(self, payload: bytes):
        frames = []
        while True:
            chunk, payload = payload[:0xFFFFFF], payload[0xFFFFFF:]
            header = struct.pack("<I", len(chunk))[:3] + bytes([self.seq])
            self.seq = (self.seq + 1) & 0xFF
            frames.append(header + chunk)
            if len(chunk) < 0xFFFFFF:
                break
        self.sock.sendall(b"".join(frames))

    def _command(self, payload: bytes):
        self.seq = 0
        self._send(payload)

    # -- handshake -----------------------------------------------------------

    def _handshake(self, user: str, password: str, database: Optional[str]):
        greeting = self._read_packet()
        if greeting[0] == 0xFF:
            raise self._err(greeting)
        pos = 1
        end = greeting.index(b"\0", pos)
        self.server_version = greeting[pos:end].decode()
        pos = end + 1
        self.conn_id = struct.unpack_from("<I", greeting, pos)[0]
        pos += 4
        seed = greeting[pos:pos + 8]
        pos += 9
        pos += 2 + 1 + 2 + 2 + 1 + 10  # caps_lo, charset, status, caps_hi, authlen, pad
        end = greeting.index(b"\0", pos)
        seed += greeting[pos:end]
        caps = (CLIENT_PROTOCOL_41 | CLIENT_SECURE_CONNECTION |
                CLIENT_PLUGIN_AUTH | CLIENT_MULTI_STATEMENTS |
                CLIENT_TRANSACTIONS)
        if database:
            caps |= CLIENT_CONNECT_WITH_DB
        auth = native_password_scramble(password.encode(), seed[:20])
        payload = struct.pack("<IIB", caps, 1 << 24, 255) + b"\0" * 23
        payload += user.encode() + b"\0"
        payload += bytes([len(auth)]) + auth
        if database:
            payload += database.encode() + b"\0"
        payload += b"mysql_native_password\0"
        self._send(payload)
        resp = self._read_packet()
        if resp[0] == 0xFF:
            raise self._err(resp)

    @staticmethod
    def _err(payload: bytes) -> WireError:
        errno = struct.unpack_from("<H", payload, 1)[0]
        sqlstate = payload[4:9].decode("ascii", "replace")
        message = payload[9:].decode("utf8", "replace")
        return WireError(errno, sqlstate, message)

    # -- queries -------------------------------------------------------------

    def query(self, sql: str) -> Tuple[List[str], List[Tuple]]:
        """(column names, rows) of the LAST statement in `sql`; text-protocol
        values arrive as strings (None for NULL)."""
        self._command(bytes([COM_QUERY]) + sql.encode("utf8"))
        out = self._read_result()
        while self.more_results:
            out = self._read_result()
        return out

    def _read_result(self) -> Tuple[List[str], List[Tuple]]:
        first = self._read_packet()
        if first[0] == 0xFF:
            self.more_results = False
            raise self._err(first)
        if first[0] == 0x00:
            # OK packet: [affected][last_id][status][warnings]
            pos = 1
            _, pos = read_lenenc_int(first, pos)
            _, pos = read_lenenc_int(first, pos)
            status = struct.unpack_from("<H", first, pos)[0]
            self.more_results = bool(status & SERVER_MORE_RESULTS_EXISTS)
            return [], []
        n_cols, _ = read_lenenc_int(first, 0)
        names: List[str] = []
        for _ in range(n_cols):
            cd = self._read_packet()
            pos = 0
            for _field in range(4):  # catalog, schema, table, org_table
                _, pos = read_lenenc_str(cd, pos)
            name, pos = read_lenenc_str(cd, pos)
            names.append(name.decode("utf8"))
        self._read_packet()  # EOF
        rows: List[Tuple] = []
        while True:
            pkt = self._read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                status = struct.unpack_from("<H", pkt, 3)[0]
                self.more_results = bool(status & SERVER_MORE_RESULTS_EXISTS)
                break
            if pkt[0] == 0xFF:
                raise self._err(pkt)
            out = []
            pos = 0
            for _ in range(n_cols):
                if pkt[pos] == 0xFB:
                    out.append(None)
                    pos += 1
                else:
                    s, pos = read_lenenc_str(pkt, pos)
                    out.append(s.decode("utf8"))
            rows.append(tuple(out))
        return names, rows

    def close(self):
        try:
            self._command(bytes([COM_QUIT]))
        except OSError:
            pass  # the peer may already be gone; the socket is closed below
        self.sock.close()
