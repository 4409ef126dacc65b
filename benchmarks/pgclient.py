"""Just enough of pgwire v3 for simple queries with text results.

Copied from chip_smoke.py's `_SimpleQueryClient` (ran on the chip in PR 22).
Sockets and struct only: the load generator imports this and must never
import jax or cockroach_tpu. An ErrorResponse does not raise: it comes back
as the statement's error, so a failure is counted and the connection lives.
"""

from __future__ import annotations

import socket
import struct


class PgClient:
    def __init__(self, addr, timeout: float = 900.0, user: str = "bench"):
        self.sock = socket.create_connection(tuple(addr), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        body = (struct.pack("!I", 196608) + b"user\x00" + user.encode()
                + b"\x00\x00")
        self.sock.sendall(struct.pack("!I", len(body) + 4) + body)
        self._until_ready()

    def _recv(self, n: int) -> bytes:
        buf = bytearray()
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("pgwire server closed the connection")
            buf.extend(c)
        return bytes(buf)

    def _until_ready(self) -> list[tuple[bytes, bytes]]:
        msgs = []
        while True:
            tag = self._recv(1)
            body = self._recv(struct.unpack("!I", self._recv(4))[0] - 4)
            msgs.append((tag, body))
            if tag == b"Z":
                return msgs

    def query(self, sql: str):
        """-> (column names, rows of text-or-None, error text or None);
        returns once ReadyForQuery has been read."""
        body = sql.encode() + b"\x00"
        self.sock.sendall(b"Q" + struct.pack("!I", len(body) + 4) + body)
        names: list[str] = []
        rows: list[list[str | None]] = []
        error = None
        for tag, body in self._until_ready():
            if tag == b"E":
                error = body.decode(errors="replace").replace("\x00", " ")
                continue
            if tag not in (b"T", b"D"):
                continue
            off = 2
            row: list[str | None] = []
            for _ in range(struct.unpack("!H", body[:2])[0]):
                if tag == b"T":
                    end = body.index(b"\x00", off)
                    names.append(body[off:end].decode())
                    off = end + 1 + 18
                    continue
                ln = struct.unpack("!i", body[off:off + 4])[0]
                off += 4
                row.append(None if ln == -1
                           else body[off:off + ln].decode())
                off += max(ln, 0)
            if tag == b"D":
                rows.append(row)
        return names, rows, error

    def close(self) -> None:
        try:
            self.sock.sendall(b"X" + struct.pack("!I", 4))
        except OSError:
            pass
        self.sock.close()
