"""Wire protocol: the raw-socket MySQL 4.1 client (testkit.MiniClient)
against the server."""
import socket
import struct

import pytest

from tidb_tpu.session import new_store
from tidb_tpu.server import Server
from tidb_tpu.server import protocol as P
from tidb_tpu.testkit import MiniClient


@pytest.fixture(scope="module")
def server():
    domain = new_store()
    srv = Server(domain, port=0).start()
    yield srv
    srv.shutdown()


def test_wire_basic(server):
    c = MiniClient(server.port, db="test")
    try:
        r = c.query("select 1+1, 'hi'")
        assert r["rows"] == [("2", "hi")]
        c.query("create table wt (a int primary key, b varchar(10))")
        r = c.query("insert into wt values (1,'x'),(2,null)")
        assert r["affected"] == 2
        r = c.query("select * from wt order by a")
        assert r["cols"] == ["a", "b"]
        assert r["rows"] == [("1", "x"), ("2", None)]
    finally:
        c.close()


def test_wire_error_and_sessions(server):
    c1 = MiniClient(server.port, db="test")
    c2 = MiniClient(server.port, db="test")
    try:
        with pytest.raises(RuntimeError, match="1146"):
            c1.query("select * from missing_table")
        c1.query("create table ws (a int)")
        c1.query("begin")
        c1.query("insert into ws values (1)")
        # other connection doesn't see uncommitted data
        r = c2.query("select count(*) from ws")
        assert r["rows"] == [("0",)]
        c1.query("commit")
        r = c2.query("select count(*) from ws")
        assert r["rows"] == [("1",)]
    finally:
        c1.close()
        c2.close()


def test_wire_warning_count_rides_eof_and_ok(server):
    """A statement's warning count is in the result set's EOF packets
    and in the OK packet: a client sees a device degrade (9013) without
    a SHOW WARNINGS round trip."""
    from tidb_tpu.utils import device_guard, failpoint
    c = MiniClient(server.port, db="test")

    def eof_counts(sql):
        """The warning counts of the two EOF packets of a result set."""
        c.io.reset_seq()
        c.io.write_packet(bytes([P.COM_QUERY]) + sql.encode())
        counts = []
        while len(counts) < 2:
            pkt = c.io.read_packet()
            assert pkt[0] != 0xFF, pkt
            if pkt[0] == 0xFE and len(pkt) < 9:
                counts.append(struct.unpack_from("<H", pkt, 1)[0])
        return counts

    try:
        c.query("create table ww (a int primary key, b int, c int)")
        r = c.query("insert into ww values " + ",".join(
            f"({i}, {i % 7}, {i % 13})" for i in range(400)))
        assert r["affected"] == 400 and c.warnings == 0  # OK packet
        q = "select b, sum(c) from ww group by b order by b"
        clean = c.query(q)
        assert c.warnings == 0
        failpoint.enable("device_guard/copr/agg", "error:compile")
        try:
            degraded = c.query(q)
            assert c.warnings == 1
            assert eof_counts(q) == [1, 1]
        finally:
            failpoint.disable_all()
            device_guard.reset()
        assert degraded["rows"] == clean["rows"]
        shown = c.query("show warnings")["rows"]
        assert [w[1] for w in shown] == ["9013"], shown
        assert eof_counts(q) == [0, 0]                  # reset per statement
    finally:
        c.close()


def test_status_port(server):
    import json
    import urllib.request
    from tidb_tpu.server.status import start_status_server
    st = start_status_server(server.domain, port=0)
    try:
        base = f"http://127.0.0.1:{st.bound_port}"
        server.domain.inc_metric("unit_test_counter", 3)
        body = urllib.request.urlopen(f"{base}/metrics", timeout=10).read()
        assert b"tidb_tpu_unit_test_counter 3" in body
        schema = json.loads(urllib.request.urlopen(
            f"{base}/schema", timeout=10).read())
        assert "test" in schema
        status = json.loads(urllib.request.urlopen(
            f"{base}/status", timeout=10).read())
        assert "version" in status
    finally:
        st.shutdown()


def test_binary_protocol_prepared(server):
    c = MiniClient(server.port, db="test")
    try:
        c.query("create table bp (a int primary key, b varchar(10))")
        c.query("insert into bp values (1,'x'),(2,'y'),(3,'z')")
        # COM_STMT_PREPARE
        c.io.reset_seq()
        c.io.write_packet(bytes([P.COM_STMT_PREPARE]) +
                          b"select b from bp where a > ? order by a")
        ok = c.io.read_packet()
        assert ok[0] == 0x00
        sid = int.from_bytes(ok[1:5], "little")
        n_params = struct.unpack_from("<H", ok, 7)[0]
        assert n_params == 1
        for _ in range(n_params):
            c.io.read_packet()
        c.io.read_packet()   # eof
        # COM_STMT_EXECUTE with param a > 1 (longlong)
        c.io.reset_seq()
        payload = (bytes([P.COM_STMT_EXECUTE]) +
                   struct.pack("<I", sid) + b"\x00" +
                   struct.pack("<I", 1) +
                   b"\x00" +            # null bitmap
                   b"\x01" +            # new params bound
                   struct.pack("<H", 0x08) +
                   struct.pack("<q", 1))
        c.io.write_packet(payload)
        first = c.io.read_packet()
        assert first[0] != 0xFF, first
        ncols, _ = c._read_lenenc(first, 0)
        for _ in range(ncols):
            c.io.read_packet()
        c.io.read_packet()   # eof
        rows = []
        while True:
            pkt = c.io.read_packet()
            if pkt[0] == 0xFE and len(pkt) < 9:
                break
            # binary row: 0x00 header + null bitmap + lenenc values
            pos = 1 + (ncols + 9) // 8
            ln, pos = c._read_lenenc(pkt, pos)
            rows.append(pkt[pos:pos + ln].decode())
        assert rows == ["y", "z"]
        # close
        c.io.reset_seq()
        c.io.write_packet(bytes([P.COM_STMT_CLOSE]) + struct.pack("<I", sid))
    finally:
        c.close()


def test_wire_auth(server):
    """Handshake must verify the native-password scramble and bind the
    session to the authenticated user (ADVICE r1: every client ran as
    root before)."""
    root = MiniClient(server.port, db="test")
    try:
        root.query("create user if not exists 'alice'@'%' "
                   "identified by 'sekrit'")
        root.query("grant select on *.* to 'alice'@'%'")
    finally:
        root.close()
    # correct password
    c = MiniClient(server.port, user="alice", password="sekrit")
    try:
        r = c.query("select current_user()")
        assert r["rows"][0][0].startswith("alice")
    finally:
        c.close()
    # wrong password rejected
    bad = MiniClient(server.port, user="alice", password="wrong",
                     expect_ok=False)
    assert not bad.auth_ok
    bad.sock.close()
    # unknown user rejected
    nob = MiniClient(server.port, user="nobody", password="",
                     expect_ok=False)
    assert not nob.auth_ok
    nob.sock.close()
    # authenticated non-root user is privilege-checked
    c2 = MiniClient(server.port, user="alice", password="sekrit", db="test")
    try:
        with pytest.raises(RuntimeError, match="1142|denied"):
            c2.query("create table alice_t (a int)")
    finally:
        c2.close()


def _self_signed(tmpdir):
    """Self-signed cert via openssl (baked into the image)."""
    import os
    import subprocess
    cert = os.path.join(tmpdir, "cert.pem")
    key = os.path.join(tmpdir, "key.pem")
    subprocess.run(
        ["openssl", "req", "-x509", "-newkey", "rsa:2048", "-nodes",
         "-keyout", key, "-out", cert, "-days", "1", "-subj",
         "/CN=localhost"], check=True, capture_output=True)
    return cert, key


def test_wire_tls(tmp_path):
    """TLS upgrade: SSLRequest packet -> wrapped socket -> normal
    handshake + queries over TLS (reference server.go onConn TLS)."""
    import ssl
    import struct as _struct
    from tidb_tpu.session import new_store
    cert, key = _self_signed(str(tmp_path))
    domain = new_store()
    srv = Server(domain, port=0, tls_cert=cert, tls_key=key).start()
    try:
        sock = socket.create_connection(("127.0.0.1", srv.port),
                                        timeout=10)
        io = P.PacketIO(sock)
        greeting = io.read_packet()
        caps_lo = _struct.unpack_from(
            "<H", greeting, greeting.index(b"\x00", 1) + 13 + 1)[0]
        assert caps_lo & P.CLIENT_SSL        # server advertises TLS
        caps = (P.CLIENT_PROTOCOL_41 | P.CLIENT_SECURE_CONNECTION |
                P.CLIENT_SSL)
        # SSLRequest: caps header only, then upgrade
        io.write_packet(_struct.pack("<IIB", caps, 1 << 24, 46) +
                        b"\x00" * 23)
        ctx = ssl.SSLContext(ssl.PROTOCOL_TLS_CLIENT)
        ctx.check_hostname = False
        ctx.verify_mode = ssl.CERT_NONE
        tsock = ctx.wrap_socket(sock)
        tio = P.PacketIO(tsock)
        tio.seq = io.seq
        resp = (_struct.pack("<IIB", caps, 1 << 24, 46) + b"\x00" * 23 +
                b"root\x00" + b"\x00")
        tio.write_packet(resp)
        ok = tio.read_packet()
        assert ok[0] == 0x00, ok
        tio.reset_seq()
        tio.write_packet(bytes([P.COM_QUERY]) + b"select 40 + 2")
        first = tio.read_packet()
        assert first[0] == 1                 # one column
        tio.read_packet()                    # col def
        tio.read_packet()                    # eof
        row = tio.read_packet()
        assert row.endswith(b"42")
        tsock.close()
        # plaintext connections still work alongside TLS
        c = MiniClient(srv.port, db="test")
        assert c.query("select 1")["rows"] == [("1",)]
        c.close()
    finally:
        srv.shutdown()
