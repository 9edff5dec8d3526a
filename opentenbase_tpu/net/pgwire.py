"""PostgreSQL frontend/backend (v3) wire protocol at the coordinator.

Reference analog: tcop/postgres.c:6703 (PostgresMain message loop),
libpq/auth.c (startup-packet auth handshake), postmaster.c
processCancelRequest (out-of-band cancel), printtup.c (RowDescription/
DataRow emission).  This is the reference's front door: any libpq
driver (psql, psycopg2, JDBC) can speak to the CN without knowing the
engine behind it.

Subset implemented (PG protocol 3.0):
- startup: SSLRequest refused with 'N', StartupMessage -> auth
  (trust, cleartext, or md5 with per-connection salt) -> ParameterStatus
  + BackendKeyData + ReadyForQuery
- simple query 'Q' (multi-statement strings supported — the session
  splits them), RowDescription/DataRow/CommandComplete, per-statement
  errors with an ErrorResponse and recovery to ReadyForQuery
- extended protocol: Parse/Bind/Describe/Execute/Close/Sync/Flush.
  Bind substitutes text-format parameter values as typed literals into
  the parsed statement (the custom-plan path, commands/prepare.c) —
  the engine's auto-prepare then caches the lifted template, so
  drivers that Parse once and Bind many still reuse one plan.
- CancelRequest on a fresh connection (pid + secret key)
- terminate 'X'

Text result format only (format code 0) — what every driver defaults
to for simple deployments.
"""

from __future__ import annotations

import hashlib
import secrets
import socket
import socketserver
import struct
import threading
from typing import Optional
from ..utils import locks

PROTO_V3 = 196608
CANCEL_CODE = 80877102
SSL_CODE = 80877103
GSS_CODE = 80877104

# type OIDs (pg_type.h)
OID_BOOL, OID_INT8, OID_INT4, OID_FLOAT8 = 16, 20, 23, 701
OID_TEXT, OID_NUMERIC, OID_DATE = 25, 1700, 1082


def _read_exact(sock, n: int) -> bytes:
    buf = b""
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionError("client closed")
        buf += chunk
    return buf


def _cstr(b: bytes, off: int):
    end = b.index(b"\x00", off)
    return b[off:end].decode("utf-8"), end + 1


class _Conn:
    """One backend connection: buffered writes, typed message frames."""

    def __init__(self, sock):
        self.sock = sock
        self.buf = bytearray()

    def msg(self, typ: bytes, payload: bytes = b""):
        self.buf += typ + struct.pack("!I", len(payload) + 4) + payload

    def flush(self):
        if self.buf:
            self.sock.sendall(bytes(self.buf))
            self.buf.clear()

    def read_message(self):
        typ = _read_exact(self.sock, 1)
        ln = struct.unpack("!I", _read_exact(self.sock, 4))[0]
        return typ, _read_exact(self.sock, ln - 4)


def _oid_for(v) -> int:
    if isinstance(v, bool):
        return OID_BOOL
    if isinstance(v, int):
        return OID_INT8
    if isinstance(v, float):
        return OID_FLOAT8
    return OID_TEXT


def _fmt(v) -> Optional[bytes]:
    if v is None:
        return None
    if isinstance(v, bool):
        return b"t" if v else b"f"
    if isinstance(v, float):
        return repr(v).encode()
    return str(v).encode()


def _row_description(names, rows) -> bytes:
    sample = {}
    for r in rows:
        for i, v in enumerate(r):
            if i not in sample and v is not None:
                sample[i] = v
    out = struct.pack("!H", len(names))
    for i, n in enumerate(names):
        oid = _oid_for(sample.get(i))
        out += n.encode() + b"\x00" + struct.pack(
            "!IhIhih", 0, 0, oid, -1, -1, 0)
    return out


_KIND_OID = None


def _oid_of_type(t) -> int:
    """SqlType -> pg_type OID (0 = unknown, which drivers treat as
    text — matching the text-format values we send)."""
    global _KIND_OID
    if _KIND_OID is None:
        from ..catalog.types import TypeKind as K
        _KIND_OID = {K.BOOL: OID_BOOL, K.INT32: OID_INT4,
                     K.INT64: OID_INT8, K.FLOAT64: OID_FLOAT8,
                     K.DECIMAL: OID_NUMERIC, K.DATE: OID_DATE,
                     K.TEXT: OID_TEXT}
    return _KIND_OID.get(getattr(t, "kind", None), 0)


def _describe_select(sess, stmt):
    """RowDescription payload for a SELECT WITHOUT executing it: bind +
    plan (through the session's plan cache) for the output names, with
    column type OIDs where the plan's top node exposes typed outputs
    (reference: exec_describe_portal_message driving printtup's
    descriptor from the planned targetlist).  None when planning fails
    — the caller answers NoData and the later Execute surfaces the
    real error."""
    try:
        if hasattr(sess, "_plan_distributed"):
            dp = sess._plan_distributed(stmt)
            names = list(dp.output_names)
            plans = [f.plan for f in dp.fragments]
        else:
            planned = sess._plan_select(stmt)
            names = list(planned.output_names)
            plans = [planned.plan]
    except Exception:
        return None
    # the CN-side top fragment is often a bare exchange consumer; the
    # typed targetlist lives on the producer — walk every fragment and
    # let later (downstream) assignments win per output name
    types = {}

    def walk(node):
        if node is None or not hasattr(node, "__dataclass_fields__"):
            return
        for attr in ("child", "left", "right"):
            walk(getattr(node, attr, None))
        for c in getattr(node, "inputs", None) or []:
            walk(c)
        for nm, e in (getattr(node, "outputs", None) or []):
            t = getattr(e, "type", None)
            if t is not None:
                types[nm] = t
    for p in plans:
        walk(p)
    out = struct.pack("!H", len(names))
    for n in names:
        out += n.encode() + b"\x00" + struct.pack(
            "!IhIhih", 0, 0, _oid_of_type(types.get(n)), -1, -1, 0)
    return out


def _command_tag(res) -> bytes:
    cmd = res.command or "SELECT"
    if cmd == "SELECT":
        return f"SELECT {len(res.rows or [])}".encode()
    if cmd in ("INSERT",):
        return f"INSERT 0 {res.rowcount or 0}".encode()
    if cmd in ("UPDATE", "DELETE", "MERGE"):
        return f"{cmd} {res.rowcount or 0}".encode()
    return cmd.encode()


def _infer_literal(text: str):
    """Text-format Bind value -> AST literal with literal-equivalent
    typing (int / numeric / string — matches Binder._bind_const)."""
    from ..sql import ast as A
    t = text.strip()
    try:
        int(t)
        return A.Const(t, "int")
    except ValueError:
        pass
    try:
        float(t)
        if "e" in t.lower() or "." in t:
            return A.Const(t, "num")
    except ValueError:
        pass
    return A.Const(text, "str")


class PgWireServer:
    """PG-v3 listener over a shared cluster (sessions are threads —
    the CnServer sibling speaking libpq instead of the JSON wire)."""

    def __init__(self, make_session, users_path: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 auth: str = "md5"):
        self.make_session = make_session
        self.users_path = users_path
        self.auth_mode = auth if users_path else "trust"
        self._sessions: dict = {}
        self._next_pid = [2000]
        self._lock = locks.Lock("net.pgwire.PgWireServer._lock")
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                try:
                    outer._handle(self.request)
                except (ConnectionError, OSError):
                    pass

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address

    def start(self) -> "PgWireServer":
        threading.Thread(target=self._server.serve_forever,
                         daemon=True).start()
        return self

    def stop(self):
        self._server.shutdown()
        self._server.server_close()

    # ------------------------------------------------------------------
    def _check_auth(self, conn, user: str) -> bool:
        if self.auth_mode == "trust":
            return True
        import json
        try:
            with open(self.users_path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            rec = {}
        u = rec.get(user)
        if self.auth_mode == "cleartext":
            conn.msg(b"R", struct.pack("!I", 3))
            conn.flush()
            typ, payload = conn.read_message()
            if typ != b"p":
                return False
            pw, _ = _cstr(payload, 0)
            if u is None:
                return False
            import hmac as _h
            from .cn_server import hash_password
            return _h.compare_digest(
                hash_password(pw, u["salt"]).encode(),
                str(u["hash"]).encode())
        # md5: md5(md5(password + user) + salt4).  The users file keeps
        # the md5(password+user) inner hash under "md5" (written by
        # write_pg_users) — the standard pg_authid storage form.
        salt = secrets.token_bytes(4)
        conn.msg(b"R", struct.pack("!I", 5) + salt)
        conn.flush()
        typ, payload = conn.read_message()
        if typ != b"p":
            return False
        got, _ = _cstr(payload, 0)
        if u is None or "md5" not in u:
            return False
        want = "md5" + hashlib.md5(
            u["md5"].encode() + salt).hexdigest()
        import hmac as _h
        return _h.compare_digest(got.encode(), want.encode())

    def _handle(self, sock: socket.socket):
        conn = _Conn(sock)
        # startup phase (SSL probe loop)
        while True:
            ln = struct.unpack("!I", _read_exact(sock, 4))[0]
            payload = _read_exact(sock, ln - 4)
            code = struct.unpack("!I", payload[:4])[0]
            if code in (SSL_CODE, GSS_CODE):
                sock.sendall(b"N")
                continue
            if code == CANCEL_CODE:
                pid, key = struct.unpack("!II", payload[4:12])
                with self._lock:
                    ent = self._sessions.get(pid)
                if ent is not None and ent[0] == key:
                    sess = ent[1]
                    if getattr(sess, "cancel_event", None) is not None:
                        sess.cancel_event.set()
                return
            if code != PROTO_V3:
                self._error(conn, "08P01",
                            f"unsupported protocol {code}")
                return
            break
        params = {}
        off = 4
        while off < len(payload) - 1:
            k, off = _cstr(payload, off)
            if not k:
                break
            v, off = _cstr(payload, off)
            params[k] = v
        user = params.get("user", "")
        if not self._check_auth(conn, user):
            self._error(conn, "28P01",
                        f'password authentication failed for user '
                        f'"{user}"')
            return
        conn.msg(b"R", struct.pack("!I", 0))          # AuthenticationOk
        for k, v in (("server_version", "14.0 (opentenbase_tpu)"),
                     ("server_encoding", "UTF8"),
                     ("client_encoding",
                      params.get("client_encoding", "UTF8")),
                     ("DateStyle", "ISO, YMD"),
                     ("integer_datetimes", "on"),
                     ("standard_conforming_strings", "on")):
            conn.msg(b"S", k.encode() + b"\x00" + v.encode() + b"\x00")
        sess = self.make_session()
        sess.cancel_event = threading.Event()
        with self._lock:
            pid = self._next_pid[0]
            self._next_pid[0] += 1
            key = secrets.randbits(32)
            self._sessions[pid] = (key, sess)
        conn.msg(b"K", struct.pack("!II", pid, key))
        try:
            self._main_loop(conn, sess)
        finally:
            try:
                if sess.txn is not None:
                    sess.execute("rollback")
            except Exception:
                pass
            with self._lock:
                self._sessions.pop(pid, None)

    # ------------------------------------------------------------------
    def _ready(self, conn, sess):
        status = b"T" if sess.txn is not None else b"I"
        conn.msg(b"Z", status)
        conn.flush()

    def _error(self, conn, code: str, message: str,
               severity: str = "ERROR"):
        conn.msg(b"E", b"S" + severity.encode() + b"\x00"
                 + b"V" + severity.encode() + b"\x00"
                 + b"C" + code.encode() + b"\x00"
                 + b"M" + message.encode() + b"\x00\x00")
        conn.flush()

    def _send_results(self, conn, results, describe: bool = True,
                      max_rows: int = 0):
        for res in results:
            rows = res.rows or []
            if res.names:
                if describe:
                    conn.msg(b"T", _row_description(res.names, rows))
                if max_rows:
                    rows = rows[:max_rows]
                for r in rows:
                    payload = struct.pack("!H", len(r))
                    for v in r:
                        b = _fmt(v)
                        if b is None:
                            payload += struct.pack("!i", -1)
                        else:
                            payload += struct.pack("!I", len(b)) + b
                    conn.msg(b"D", payload)
            conn.msg(b"C", _command_tag(res) + b"\x00")

    def _main_loop(self, conn, sess):
        from ..sql import ast as A
        from ..sql.parser import parse_sql
        prepared: dict = {}     # name -> (stmt ast, n_params)
        # name -> {"stmt": bound ast, "res": Result|None, "sent": n} —
        # a row-limited Execute suspends the portal (PortalSuspended)
        # and a later Execute resumes from `sent` (reference:
        # exec_execute_message's portal re-entry)
        portals: dict = {}
        self._ready(conn, sess)
        while True:
            typ, payload = conn.read_message()
            if typ == b"X":
                return
            if typ == b"Q":
                sql, _ = _cstr(payload, 0)
                if not sql.strip():
                    conn.msg(b"I")
                    self._ready(conn, sess)
                    continue
                sess.cancel_event.clear()
                try:
                    results = sess.execute(sql)
                    self._send_results(conn, results)
                except Exception as e:   # statement error: recover
                    self._error(conn, "XX000",
                                f"{type(e).__name__}: {e}")
                    self._ready(conn, sess)
                    continue
                self._ready(conn, sess)
            elif typ == b"P":
                name, off = _cstr(payload, 0)
                sql, off = _cstr(payload, off)
                try:
                    stmts = parse_sql(sql) if sql.strip() else []
                    if len(stmts) > 1:
                        raise ValueError(
                            "cannot Parse multiple statements")
                    nparams = 0
                    if stmts:
                        nparams = max(
                            (x.index for x in _walk_params(stmts[0])),
                            default=0)
                    prepared[name] = (stmts[0] if stmts else None,
                                      nparams)
                    conn.msg(b"1")
                except Exception as e:
                    self._error(conn, "42601", str(e))
                    self._sync_skip(conn, sess)
            elif typ == b"B":
                try:
                    portal, stmt = self._do_bind(payload, prepared)
                    portals[portal] = {"stmt": stmt, "res": None,
                                       "sent": 0}
                    conn.msg(b"2")
                except Exception as e:
                    self._error(conn, "08P01", str(e))
                    self._sync_skip(conn, sess)
            elif typ == b"D":
                kind = payload[0:1]
                name, _ = _cstr(payload, 1)
                if kind == b"P":
                    ent = portals.get(name)
                    stmt = ent["stmt"] if ent else None
                else:
                    stmt, nparams = prepared.get(name) or (None, 0)
                    # statement Describe also answers the parameter
                    # types (unknown: the engine infers at Bind)
                    conn.msg(b"t", struct.pack("!H", nparams)
                             + struct.pack("!I", 0) * nparams)
                desc = _describe_select(sess, stmt) \
                    if isinstance(stmt, A.SelectStmt) else None
                if desc is None:
                    conn.msg(b"n")        # NoData
                else:
                    conn.msg(b"T", desc)
            elif typ == b"E":
                name, off = _cstr(payload, 0)
                max_rows = struct.unpack("!i", payload[off:off + 4])[0]
                ent = portals.get(name)
                if ent is None:
                    self._error(conn, "34000",
                                f"portal {name!r} does not exist")
                    self._sync_skip(conn, sess)
                    continue
                sess.cancel_event.clear()
                try:
                    if ent["res"] is None:
                        ent["res"] = sess.execute_ast(ent["stmt"])
                        ent["sent"] = 0
                    self._send_portal(conn, ent, max_rows or 0)
                except Exception as e:
                    self._error(conn, "XX000",
                                f"{type(e).__name__}: {e}")
                    self._sync_skip(conn, sess)
            elif typ == b"C":
                kind = payload[0:1]
                name, _ = _cstr(payload, 1)
                (portals if kind == b"P" else prepared).pop(name, None)
                conn.msg(b"3")
            elif typ == b"S":
                self._ready(conn, sess)
            elif typ == b"H":
                conn.flush()
            elif typ == b"d" or typ == b"c" or typ == b"f":
                pass                      # COPY subprotocol: ignored
            else:
                self._error(conn, "08P01",
                            f"unsupported message {typ!r}")
                self._ready(conn, sess)

    def _send_portal(self, conn, ent: dict, max_rows: int):
        """Emit a portal's rows honoring the Execute row limit: a
        truncating limit sends PortalSuspended ('s') and KEEPS the
        portal's position so the next Execute resumes — previously the
        rows past the limit were silently lost."""
        res = ent["res"]
        rows = res.rows or []
        if res.names:
            remaining = rows[ent["sent"]:]
            if max_rows and len(remaining) > max_rows:
                remaining = remaining[:max_rows]
                suspended = True
            else:
                suspended = False
            for r in remaining:
                payload = struct.pack("!H", len(r))
                for v in r:
                    b = _fmt(v)
                    if b is None:
                        payload += struct.pack("!i", -1)
                    else:
                        payload += struct.pack("!I", len(b)) + b
                conn.msg(b"D", payload)
            ent["sent"] += len(remaining)
            if suspended:
                conn.msg(b"s")
                return
        conn.msg(b"C", _command_tag(res) + b"\x00")

    def _sync_skip(self, conn, sess):
        """After an extended-protocol error, discard until Sync
        (reference: postgres.c ignore_till_sync)."""
        while True:
            typ, _ = conn.read_message()
            if typ == b"S":
                self._ready(conn, sess)
                return
            if typ == b"X":
                raise ConnectionError("terminated")

    def _do_bind(self, payload: bytes, prepared: dict):
        from .cn_server import CnClient  # noqa: F401 (doc link only)
        portal, off = _cstr(payload, 0)
        source, off = _cstr(payload, off)
        if source not in prepared:
            raise ValueError(f"prepared statement {source!r} "
                             "does not exist")
        stmt, nparams = prepared[source]
        nfmt = struct.unpack("!H", payload[off:off + 2])[0]
        fmts = struct.unpack(f"!{nfmt}h",
                             payload[off + 2:off + 2 + 2 * nfmt])
        off += 2 + 2 * nfmt
        nvals = struct.unpack("!H", payload[off:off + 2])[0]
        off += 2
        args = []
        for i in range(nvals):
            ln = struct.unpack("!i", payload[off:off + 4])[0]
            off += 4
            if ln < 0:
                args.append(None)
            else:
                v = payload[off:off + ln]
                off += ln
                fmt = fmts[i] if i < len(fmts) else \
                    (fmts[0] if fmts else 0)
                if fmt != 0:
                    raise ValueError("binary parameter format "
                                     "unsupported")
                args.append(v.decode("utf-8"))
        if stmt is None:
            return portal, None
        if nparams != len(args):
            raise ValueError(f"bind supplies {len(args)} parameters "
                             f"but statement needs {nparams}")
        if not args:
            return portal, stmt
        from ..exec.dist_session import _subst_params
        from ..sql import ast as A
        lits = [A.Const(None, "null") if a is None
                else _infer_literal(a) for a in args]
        return portal, _subst_params(stmt, lits)


def _walk_params(node):
    import dataclasses
    from ..sql import ast as A
    stack = [node]
    while stack:
        x = stack.pop()
        if isinstance(x, A.Param):
            yield x
        elif dataclasses.is_dataclass(x) and not isinstance(x, type):
            for f in dataclasses.fields(x):
                stack.append(getattr(x, f.name))
        elif isinstance(x, (list, tuple)):
            stack.extend(x)


def write_pg_users(path: str, users: dict[str, str]) -> None:
    """Extend the users file with the md5 inner hash
    (md5(password + user), the pg_authid form) next to the existing
    salted-sha verifier so BOTH wire protocols authenticate."""
    import json
    from .cn_server import hash_password
    rec = {}
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        pass
    for name, pw in users.items():
        ent = rec.get(name, {})
        if "hash" not in ent:
            salt = secrets.token_hex(8)
            ent = {"salt": salt, "hash": hash_password(pw, salt)}
        ent["md5"] = hashlib.md5((pw + name).encode()).hexdigest()
        rec[name] = ent
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
