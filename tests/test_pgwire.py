"""PostgreSQL v3 wire protocol (net/pgwire.py) — driven by a minimal
from-scratch libpq frontend (psycopg2 is not in this environment; the
client below implements the same byte protocol a real driver speaks:
startup, md5 auth, simple query, extended Parse/Bind/Execute, cancel).
"""

import hashlib
import socket
import struct

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.net.pgwire import PgWireServer, write_pg_users
from opentenbase_tpu.parallel.cluster import Cluster


class MiniPg:
    """Minimal libpq frontend (text protocol, v3)."""

    def __init__(self, host, port, user="u", password=None,
                 database="otb"):
        self.sock = socket.create_connection((host, port), timeout=60)
        self.params = {}
        self.backend = None
        payload = struct.pack("!I", 196608)
        for k, v in (("user", user), ("database", database)):
            payload += k.encode() + b"\x00" + v.encode() + b"\x00"
        payload += b"\x00"
        self._send_raw(payload)
        self.user, self.password = user, password
        self._auth()

    def _send_raw(self, payload):
        self.sock.sendall(struct.pack("!I", len(payload) + 4) + payload)

    def _msg(self, typ, payload=b""):
        self.sock.sendall(typ + struct.pack("!I", len(payload) + 4)
                          + payload)

    def _read(self):
        typ = self._exact(1)
        ln = struct.unpack("!I", self._exact(4))[0]
        return typ, self._exact(ln - 4)

    def _exact(self, n):
        buf = b""
        while len(buf) < n:
            c = self.sock.recv(n - len(buf))
            if not c:
                raise ConnectionError("closed")
            buf += c
        return buf

    def _auth(self):
        while True:
            typ, payload = self._read()
            if typ == b"E":
                raise RuntimeError(_err_msg(payload))
            if typ == b"R":
                code = struct.unpack("!I", payload[:4])[0]
                if code == 0:
                    continue
                if code == 3:
                    self._msg(b"p", self.password.encode() + b"\x00")
                elif code == 5:
                    salt = payload[4:8]
                    inner = hashlib.md5(
                        (self.password + self.user).encode()
                    ).hexdigest()
                    outer = "md5" + hashlib.md5(
                        inner.encode() + salt).hexdigest()
                    self._msg(b"p", outer.encode() + b"\x00")
                else:
                    raise RuntimeError(f"auth code {code}")
            elif typ == b"S":
                k, v = payload.split(b"\x00")[:2]
                self.params[k.decode()] = v.decode()
            elif typ == b"K":
                self.backend = struct.unpack("!II", payload)
            elif typ == b"Z":
                self.status = payload.decode()
                return

    def query(self, sql):
        """Simple query: returns (rows, tags); raises on ErrorResponse
        (after draining to ReadyForQuery)."""
        self._msg(b"Q", sql.encode() + b"\x00")
        rows, tags, err = [], [], None
        while True:
            typ, payload = self._read()
            if typ == b"T":
                ncols = struct.unpack("!H", payload[:2])[0]
                names, off = [], 2
                for _ in range(ncols):
                    end = payload.index(b"\x00", off)
                    names.append(payload[off:end].decode())
                    off = end + 1 + 18
                self.colnames = names
            elif typ == b"D":
                n = struct.unpack("!H", payload[:2])[0]
                off, row = 2, []
                for _ in range(n):
                    ln = struct.unpack("!i", payload[off:off + 4])[0]
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif typ == b"C":
                tags.append(payload[:-1].decode())
            elif typ == b"E":
                err = _err_msg(payload)
            elif typ == b"Z":
                self.status = payload.decode()
                if err:
                    raise RuntimeError(err)
                return rows, tags
            elif typ == b"I":
                tags.append("")

    def extended(self, sql, args, name=""):
        """Parse/Bind/Execute/Sync round trip; text args."""
        self._msg(b"P", name.encode() + b"\x00" + sql.encode()
                  + b"\x00" + struct.pack("!H", 0))
        bind = name.encode() + b"\x00" + name.encode() + b"\x00"
        bind += struct.pack("!H", 0)
        bind += struct.pack("!H", len(args))
        for a in args:
            if a is None:
                bind += struct.pack("!i", -1)
            else:
                b = str(a).encode()
                bind += struct.pack("!I", len(b)) + b
        bind += struct.pack("!H", 0)
        self._msg(b"B", bind)
        self._msg(b"E", name.encode() + b"\x00"
                  + struct.pack("!i", 0))
        self._msg(b"S")
        rows, err = [], None
        while True:
            typ, payload = self._read()
            if typ == b"D":
                n = struct.unpack("!H", payload[:2])[0]
                off, row = 2, []
                for _ in range(n):
                    ln = struct.unpack("!i", payload[off:off + 4])[0]
                    off += 4
                    if ln < 0:
                        row.append(None)
                    else:
                        row.append(payload[off:off + ln].decode())
                        off += ln
                rows.append(tuple(row))
            elif typ == b"E":
                err = _err_msg(payload)
            elif typ == b"Z":
                if err:
                    raise RuntimeError(err)
                return rows

    def cancel(self, host, port):
        s = socket.create_connection((host, port), timeout=30)
        payload = struct.pack("!III", 80877102, *self.backend)
        s.sendall(struct.pack("!I", len(payload) + 4) + payload)
        s.close()

    def close(self):
        try:
            self._msg(b"X")
        except OSError:
            pass
        self.sock.close()


def _err_msg(payload):
    out = {}
    off = 0
    while off < len(payload) and payload[off:off + 1] != b"\x00":
        k = payload[off:off + 1].decode()
        end = payload.index(b"\x00", off + 1)
        out[k] = payload[off + 1:end].decode()
        off = end + 1
    return out.get("M", str(out))


@pytest.fixture(scope="module")
def server(tmp_path_factory):
    d = tmp_path_factory.mktemp("pgw")
    users = str(d / "users.json")
    write_pg_users(users, {"u": "pw"})
    cl = Cluster(n_datanodes=2)
    srv = PgWireServer(lambda: ClusterSession(cl), users_path=users)
    srv.start()
    yield srv
    srv.stop()


class TestStartup:
    def test_md5_auth_and_banner(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        assert "opentenbase_tpu" in c.params["server_version"]
        assert c.status == "I"
        c.close()

    def test_bad_password_rejected(self, server):
        with pytest.raises(RuntimeError, match="authentication"):
            MiniPg(server.host, server.port, "u", "wrong")

    def test_ssl_probe_refused_then_startup(self, server):
        s = socket.create_connection((server.host, server.port),
                                     timeout=30)
        s.sendall(struct.pack("!II", 8, 80877103))
        assert s.recv(1) == b"N"
        s.close()


class TestSimpleQuery:
    def test_ddl_dml_select(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        _, tags = c.query("create table pgt (k bigint primary key, "
                          "v bigint, nm text, f float, d date) "
                          "distribute by shard(k)")
        assert tags == ["CREATE TABLE"]
        _, tags = c.query(
            "insert into pgt values (1, 10, 'one', 1.5, '1995-01-02'),"
            " (2, null, 'two', 2.5, '1996-03-04')")
        assert tags == ["INSERT 0 2"]
        rows, tags = c.query("select k, v, nm, f, d from pgt "
                             "order by k")
        assert rows == [("1", "10", "one", "1.5", "1995-01-02"),
                        ("2", None, "two", "2.5", "1996-03-04")]
        assert c.colnames == ["k", "v", "nm", "f", "d"]
        assert tags == ["SELECT 2"]
        c.close()

    def test_multi_statement_and_txn_status(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pgt2 (k bigint primary key) "
                "distribute by shard(k)")
        c.query("begin")
        assert c.status == "T"
        c.query("insert into pgt2 values (1); insert into pgt2 "
                "values (2)")
        c.query("commit")
        assert c.status == "I"
        rows, _ = c.query("select count(*) from pgt2")
        assert rows == [("2",)]
        c.close()

    def test_error_recovers(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        with pytest.raises(RuntimeError):
            c.query("select * from no_such_table_xyz")
        rows, _ = c.query("select 1 + 1")
        assert rows == [("2",)]
        c.close()


class TestExtendedProtocol:
    def test_parse_bind_execute(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pge (k bigint primary key, v bigint) "
                "distribute by shard(k)")
        for i in range(5):
            c.extended("insert into pge values ($1, $2)",
                       [i, i * 100])
        rows = c.extended("select v from pge where k = $1", [3])
        assert rows == [("300",)]
        rows = c.extended("select count(*) from pge where v >= $1",
                          [200])
        assert rows == [("3",)]
        c.close()

    def test_null_param(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pgn (k bigint primary key, v bigint) "
                "distribute by shard(k)")
        c.extended("insert into pgn values ($1, $2)", [1, None])
        rows = c.extended(
            "select count(*) from pgn where v is null", [])
        assert rows == [("1",)]
        c.close()

    def test_extended_error_then_sync_recovers(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        with pytest.raises(RuntimeError):
            c.extended("select * from nope_xyz where k = $1", [1])
        rows = c.extended("select 41 + $1", [1])
        assert rows == [("42",)]
        c.close()


class TestDescribeAndFetchSize:
    """Describe-driven drivers (JDBC, async fetch-size clients): a
    SELECT portal Describe answers a REAL RowDescription, and a
    row-limited Execute sends PortalSuspended and keeps the portal's
    position for the next Execute."""

    def _drive(self, c, msgs):
        """Send raw extended-protocol messages + Sync; return the
        ordered reply list [(type, payload)] up to ReadyForQuery."""
        for typ, payload in msgs:
            c._msg(typ, payload)
        c._msg(b"S")
        out = []
        while True:
            typ, payload = c._read()
            if typ == b"Z":
                return out
            out.append((typ, payload))

    @staticmethod
    def _parse_rowdesc(payload):
        ncols = struct.unpack("!H", payload[:2])[0]
        names, oids, off = [], [], 2
        for _ in range(ncols):
            end = payload.index(b"\x00", off)
            names.append(payload[off:end].decode())
            oid = struct.unpack("!I", payload[end + 7:end + 11])[0]
            oids.append(oid)
            off = end + 1 + 18
        return names, oids

    def test_describe_portal_row_description(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pgd (k bigint primary key, nm text) "
                "distribute by shard(k)")
        c.query("insert into pgd values (1, 'x')")
        sql = "select k, nm from pgd"
        bind = b"\x00\x00" + struct.pack("!HHH", 0, 0, 0)
        replies = self._drive(c, [
            (b"P", b"\x00" + sql.encode() + b"\x00"
             + struct.pack("!H", 0)),
            (b"B", bind),
            (b"D", b"P\x00"),
        ])
        kinds = [t for t, _ in replies]
        assert b"T" in kinds, f"Describe answered {kinds}, not a " \
            "RowDescription"
        names, oids = self._parse_rowdesc(
            next(p for t, p in replies if t == b"T"))
        assert names == ["k", "nm"]
        assert oids[0] == 20 and oids[1] == 25   # int8, text
        c.close()

    def test_describe_statement_param_description(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pgds (k bigint primary key) "
                "distribute by shard(k)")
        sql = "select k from pgds where k = $1"
        replies = self._drive(c, [
            (b"P", b"st1\x00" + sql.encode() + b"\x00"
             + struct.pack("!H", 0)),
            (b"D", b"Sst1\x00"),
        ])
        kinds = [t for t, _ in replies]
        assert b"t" in kinds                     # ParameterDescription
        tpay = next(p for t, p in replies if t == b"t")
        assert struct.unpack("!H", tpay[:2])[0] == 1
        c.close()

    def test_fetch_size_suspends_and_resumes(self, server):
        c = MiniPg(server.host, server.port, "u", "pw")
        c.query("create table pgf (k bigint primary key) "
                "distribute by shard(k)")
        c.query("insert into pgf values (1), (2), (3), (4), (5)")
        sql = "select k from pgf order by k"
        bind = b"\x00\x00" + struct.pack("!HHH", 0, 0, 0)
        replies = self._drive(c, [
            (b"P", b"\x00" + sql.encode() + b"\x00"
             + struct.pack("!H", 0)),
            (b"B", bind),
            (b"E", b"\x00" + struct.pack("!i", 2)),   # fetch 2
            (b"E", b"\x00" + struct.pack("!i", 2)),   # next 2
            (b"E", b"\x00" + struct.pack("!i", 0)),   # the rest
        ])
        kinds = [t for t, _ in replies]
        # two suspended fetches, then the final CommandComplete —
        # and EVERY row arrives exactly once
        assert kinds.count(b"s") == 2
        assert kinds.count(b"C") == 1
        rows = [p for t, p in replies if t == b"D"]
        vals = []
        for p in rows:
            ln = struct.unpack("!I", p[2:6])[0]
            vals.append(p[6:6 + ln].decode())
        assert vals == ["1", "2", "3", "4", "5"]
        # suspension order: 2 rows, s, 2 rows, s, 1 row, C
        seq = [t for t, _ in replies if t in (b"D", b"s", b"C")]
        assert seq == [b"D", b"D", b"s", b"D", b"D", b"s", b"D", b"C"]
        c.close()
