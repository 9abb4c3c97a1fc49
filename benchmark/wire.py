"""A MySQL 4.1 text-protocol client over a raw socket: handshake v10,
mysql_native_password, COM_QUERY. The benchmark's own copy (the pattern
is `tidb_tpu.testkit.MiniClient`'s): it imports nothing of the program,
so the yardstick does not move when the program's protocol module does.
Values come back as the wire sends them: str, or None for NULL."""
import hashlib
import socket
import struct

CLIENT_PROTOCOL_41 = 0x0200
CLIENT_SECURE_CONNECTION = 0x8000
CLIENT_CONNECT_WITH_DB = 0x0008
COM_QUIT = 0x01
COM_QUERY = 0x03
MAX_PACKET = 0xFFFFFF


class WireError(RuntimeError):
    """The server answered with an ERR packet."""

    def __init__(self, code, msg):
        super().__init__(f"server error {code}: {msg}")
        self.code = code


def _scramble(password, salt):
    if not password:
        return b""
    s1 = hashlib.sha1(password.encode()).digest()
    mix = hashlib.sha1(salt + hashlib.sha1(s1).digest()).digest()
    return bytes(a ^ b for a, b in zip(s1, mix))


def _lenenc(data, pos):
    b = data[pos]
    if b < 251:
        return b, pos + 1
    if b == 0xFB:
        return None, pos + 1
    if b == 0xFC:
        return struct.unpack_from("<H", data, pos + 1)[0], pos + 3
    if b == 0xFD:
        return int.from_bytes(data[pos + 1:pos + 4], "little"), pos + 4
    return struct.unpack_from("<Q", data, pos + 1)[0], pos + 9


class Wire:
    def __init__(self, port, db="test", user="root", password="",
                 timeout=900.0):
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.seq = 0
        greeting = self._read()
        if greeting[0] != 10:
            raise RuntimeError(f"not a handshake v10 greeting: {greeting!r}")
        end = greeting.index(b"\x00", 1)
        # salt: 8 bytes after version+conn id, 12 more after the
        # filler/capability/charset/status/length/reserved block
        p2 = end + 13 + 1 + 2 + 1 + 2 + 2 + 1 + 10
        salt = greeting[end + 5:end + 13] + greeting[p2:p2 + 12]
        caps = CLIENT_PROTOCOL_41 | CLIENT_SECURE_CONNECTION | \
            (CLIENT_CONNECT_WITH_DB if db else 0)
        token = _scramble(password, salt)
        resp = struct.pack("<IIB", caps, 1 << 24, 46) + b"\x00" * 23 + \
            user.encode() + b"\x00" + bytes([len(token)]) + token
        if db:
            resp += db.encode() + b"\x00"
        self._write(resp)
        ok = self._read()
        if ok[0] != 0x00:
            raise RuntimeError(f"authentication refused: {ok!r}")

    def _read_n(self, n):
        buf = bytearray()
        while len(buf) < n:
            chunk = self.sock.recv(n - len(buf))
            if not chunk:
                raise ConnectionError("server closed the connection")
            buf += chunk
        return bytes(buf)

    def _read(self):
        out = b""
        while True:
            hdr = self._read_n(4)
            ln = hdr[0] | (hdr[1] << 8) | (hdr[2] << 16)
            self.seq = (hdr[3] + 1) & 0xFF
            out += self._read_n(ln)
            if ln < MAX_PACKET:
                return out

    def _write(self, payload):
        while True:
            part, payload = payload[:MAX_PACKET], payload[MAX_PACKET:]
            self.sock.sendall(struct.pack("<I", len(part))[:3] +
                              bytes([self.seq]) + part)
            self.seq = (self.seq + 1) & 0xFF
            if len(part) < MAX_PACKET:
                return

    def query(self, sql):
        """-> {"affected": n} for an OK packet, else {"cols", "rows"};
        an ERR packet raises WireError."""
        self.seq = 0
        self._write(bytes([COM_QUERY]) + sql.encode())
        first = self._read()
        if first[0] == 0xFF:
            raise WireError(struct.unpack_from("<H", first, 1)[0],
                            first[9:].decode(errors="replace"))
        if first[0] == 0x00:
            return {"affected": _lenenc(first, 1)[0]}
        ncols, _ = _lenenc(first, 0)
        cols = []
        for _ in range(ncols):
            pkt, pos, name = self._read(), 0, b""
            for _ in range(5):           # the name is the 5th string
                ln, pos = _lenenc(pkt, pos)
                name, pos = pkt[pos:pos + ln], pos + ln
            cols.append(name.decode())
        eof = self._read()
        if eof[0] != 0xFE:
            raise RuntimeError(f"expected EOF after columns: {eof!r}")
        rows = []
        while True:
            pkt = self._read()
            if pkt[0] == 0xFE and len(pkt) < 9:
                return {"cols": cols, "rows": rows}
            row, pos = [], 0
            while pos < len(pkt):
                ln, pos = _lenenc(pkt, pos)
                if ln is None:
                    row.append(None)
                else:
                    row.append(pkt[pos:pos + ln].decode())
                    pos += ln
            rows.append(tuple(row))

    def rows(self, sql):
        return self.query(sql)["rows"]

    def close(self):
        try:
            self.seq = 0
            self._write(bytes([COM_QUIT]))
        except OSError:
            pass
        self.sock.close()
