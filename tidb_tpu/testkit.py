"""TestKit (reference pkg/testkit/testkit.go:79 — MustExec /
MustQuery().Check()). The workhorse harness: whole SQL layer in-process
against the embedded store. MiniClient is its wire-side twin: a
raw-socket MySQL 4.1 client for driving `server.Server` the way an
external client would (tests/test_server.py, chip_smoke.py)."""
from __future__ import annotations

import socket
import struct

from .session import Session, Domain, new_store


class TestKit:
    __test__ = False          # not a pytest test class

    def __init__(self, domain: Domain | None = None):
        self.domain = domain or new_store()
        self.sess = Session(self.domain)
        self.sess.vars.current_db = "test"
        # write-time row<->index self-check in testing builds (reference
        # intest.EnableInternalCheck + mutation_checker.go); perf
        # harnesses opt out (TIDB_TPU_MUTATION_CHECK=0) so measured
        # write paths match a real deployment
        import os as _os
        from .executor.table_rt import MUTATION_CHECK
        MUTATION_CHECK[0] = _os.environ.get(
            "TIDB_TPU_MUTATION_CHECK", "1") != "0"

    def must_exec(self, sql: str, params=None):
        return self.sess.execute(sql, params)

    def must_query(self, sql: str, params=None) -> "QueryResult":
        rs = self.sess.execute(sql, params)
        return QueryResult(rs)

    def exec_err(self, sql: str) -> Exception:
        from .errors import TiDBError
        try:
            self.sess.execute(sql)
        except TiDBError as e:
            return e
        raise AssertionError(f"expected error for: {sql}")

    def new_session(self) -> "TestKit":
        tk = TestKit.__new__(TestKit)
        tk.domain = self.domain
        tk.sess = Session(self.domain)
        tk.sess.vars.current_db = "test"
        return tk


class QueryResult:
    __test__ = False

    def __init__(self, rs):
        self.rs = rs
        self.names = rs.names

    @property
    def rows(self):
        return self.rs.rows

    def _norm(self):
        out = []
        for row in self.rows:
            out.append(tuple("<nil>" if v is None else _fmt(v) for v in row))
        return out

    def check(self, expected: list):
        """expected: list of tuples/lists of strings (or values)."""
        got = self._norm()
        want = [tuple("<nil>" if v is None else _fmt(v) for v in row)
                for row in expected]
        assert got == want, f"result mismatch:\n got: {got}\nwant: {want}"
        return self

    def sort_check(self, expected: list):
        got = sorted(self._norm())
        want = sorted(tuple("<nil>" if v is None else _fmt(v) for v in row)
                      for row in expected)
        assert got == want, f"result mismatch:\n got: {got}\nwant: {want}"
        return self

    def check_contain(self, text: str):
        for row in self._norm():
            if any(text in c for c in row):
                return self
        raise AssertionError(f"{text!r} not found in {self._norm()}")


def _fmt(v):
    if isinstance(v, bool):
        return str(int(v))
    if isinstance(v, float):
        if v == int(v) and abs(v) < 1e15:
            return str(int(v))
        return repr(v)
    return str(v)


class MiniClient:
    """Minimal MySQL 4.1 text-protocol client over a raw socket:
    handshake v10 + mysql_native_password, then COM_QUERY. Values come
    back as the wire sends them (str, or None for NULL)."""
    __test__ = False

    def __init__(self, port, db="", user="root", password="",
                 expect_ok=True, timeout=10):
        from .server import protocol as P
        self._P = P
        self.sock = socket.create_connection(("127.0.0.1", port),
                                             timeout=timeout)
        self.io = P.PacketIO(self.sock)
        self.warnings = 0
        greeting = self.io.read_packet()
        if greeting[0] != 10:
            raise RuntimeError(f"not a handshake v10 greeting: {greeting!r}")
        # salt: 8 bytes after conn_id+version, 12 more before auth name
        ver_end = greeting.index(b"\x00", 1)
        salt = greeting[ver_end + 5:ver_end + 13] + \
            greeting[ver_end + 13 + 1 + 2 + 1 + 2 + 2 + 1 + 10:
                     ver_end + 13 + 1 + 2 + 1 + 2 + 2 + 1 + 10 + 12]
        caps = P.CLIENT_PROTOCOL_41 | P.CLIENT_SECURE_CONNECTION
        if db:
            caps |= P.CLIENT_CONNECT_WITH_DB
        token = P.native_password_token(password, salt)
        resp = struct.pack("<IIB", caps, 1 << 24, 46) + b"\x00" * 23
        resp += user.encode() + b"\x00"
        resp += bytes([len(token)]) + token
        if db:
            resp += db.encode() + b"\x00"
        self.io.write_packet(resp)
        ok = self.io.read_packet()
        self.auth_ok = ok[0] == 0x00
        if expect_ok and not self.auth_ok:
            raise RuntimeError(f"authentication refused: {ok!r}")

    def _read_lenenc(self, data, pos):
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

    def query(self, sql):
        """-> {"affected": n} for an OK packet, else {"cols", "rows"};
        a server ERR packet raises RuntimeError("server error <code>:
        ..."). `self.warnings` is the statement's warning count as the
        OK packet or the closing EOF packet carried it."""
        P = self._P
        self.io.reset_seq()
        self.io.write_packet(bytes([P.COM_QUERY]) + sql.encode())
        first = self.io.read_packet()
        if first[0] == 0xFF:
            code = struct.unpack_from("<H", first, 1)[0]
            raise RuntimeError(f"server error {code}: "
                               f"{first[9:].decode(errors='replace')}")
        if first[0] == 0x00:
            affected, pos = self._read_lenenc(first, 1)
            _last_id, pos = self._read_lenenc(first, pos)
            _status, self.warnings = struct.unpack_from("<HH", first, pos)
            return {"affected": affected}
        ncols, _ = self._read_lenenc(first, 0)
        cols = []
        for _ in range(ncols):
            pkt = self.io.read_packet()
            # parse column name (5th lenenc string)
            pos = 0
            vals = []
            for _ in range(5):
                ln, pos = self._read_lenenc(pkt, pos)
                vals.append(pkt[pos:pos + ln])
                pos += ln
            cols.append(vals[4].decode())
        eof = self.io.read_packet()
        if eof[0] != 0xFE:
            raise RuntimeError(f"expected EOF after columns: {eof!r}")
        rows = []
        while True:
            pkt = self.io.read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                self.warnings = struct.unpack_from("<H", pkt, 1)[0]
                break
            row = []
            pos = 0
            while pos < len(pkt):
                v, pos2 = self._read_lenenc(pkt, pos)
                if v is None:
                    row.append(None)
                    pos = pos2
                else:
                    row.append(pkt[pos2:pos2 + v].decode())
                    pos = pos2 + v
            rows.append(tuple(row))
        return {"cols": cols, "rows": rows}

    def close(self):
        try:
            self.io.reset_seq()
            self.io.write_packet(bytes([self._P.COM_QUIT]))
        except OSError:
            pass
        self.sock.close()
