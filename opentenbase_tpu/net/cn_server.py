"""Client-facing SQL server — the coordinator's front door.

Reference analog: tcop/postgres.c:6703 (PostgresMain, the per-backend
read-execute-respond loop behind libpq), the startup-packet password
handshake (auth.c), and the out-of-band query-cancel protocol — a
separate short-lived connection carrying (pid, secret), postmaster.c
processCancelRequest.

Design notes (TPU-first deployment): the CN server owns the cluster's
device mesh, so EVERY connected client shares one staged-table cache and
one compiled-program cache — a new connection pays zero recompilation
for plans the cluster has already run (the reference pays backend fork +
catalog warmup per connection instead).  Sessions are threads; the GIL
is released inside XLA compute, so concurrent clients overlap host work
with device work.

Cancel semantics match PostgreSQL's: the flag is polled at safe points
(statement start, between fragment dispatches), so a cancel lands at
the next host-sync boundary, aborts the open transaction, and leaves
the session usable.
"""

from __future__ import annotations

import hashlib
import hmac
import json
import os
import secrets
import socket
import socketserver
import threading
import time
from typing import Optional

from .wire import decode_msg, recv_frame, recv_msg, send_msg
from ..obs import trace as obs_trace
from ..obs import xray
from ..utils import locks

_BANNER = "opentenbase_tpu"


# ---------------------------------------------------------------------------
# password file (reference: pg_authid's rolpassword, md5/scram verifier)
# ---------------------------------------------------------------------------

def hash_password(password: str, salt: str) -> str:
    return hashlib.sha256((salt + ":" + password).encode()).hexdigest()


def write_users(path: str, users: dict[str, str]) -> None:
    """users: {name: cleartext} -> salted-hash file."""
    rec = {}
    for name, pw in users.items():
        salt = secrets.token_hex(8)
        rec[name] = {"salt": salt, "hash": hash_password(pw, salt)}
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)


def check_password(path: str, user: str, password: str) -> bool:
    try:
        with open(path) as f:
            rec = json.load(f)
    except (OSError, ValueError):
        return False
    u = rec.get(user)
    if u is None:
        return False
    # constant-time: a network peer must not learn hash prefixes from
    # comparison timing (reference: auth.c uses strcmp on md5 hashes,
    # but hmac.compare_digest is the modern contract)
    return hmac.compare_digest(
        hash_password(password, u["salt"]).encode(),
        str(u["hash"]).encode())


# ---------------------------------------------------------------------------
# server
# ---------------------------------------------------------------------------

class CnServer:
    """One process-wide SQL listener; one session (thread) per client.

    make_session: () -> ClusterSession — each connection gets a fresh
    session over the SHARED cluster object (shared mesh runner, shared
    plan caches, per-session txn/GUC/prepared state).

    scheduler: optional serving-tier Scheduler (exec/scheduler.py) —
    when set, every statement routes through its admission/coalescing
    queue instead of executing directly on the handler thread, so
    same-signature queries from different connections batch into one
    device dispatch.
    """

    def __init__(self, make_session, users_path: Optional[str] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 scheduler=None):
        self.make_session = make_session
        self.users_path = users_path
        self.scheduler = scheduler
        self._sessions: dict = {}     # pid -> (secret, session)
        self._next_pid = [1000]
        self._lock = locks.Lock("net.cn_server.CnServer._lock")
        outer = self

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                outer._handle(self.request)

        class Server(socketserver.ThreadingTCPServer):
            daemon_threads = True
            allow_reuse_address = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address

    def start(self) -> "CnServer":
        t = threading.Thread(target=self._server.serve_forever,
                             daemon=True)
        t.start()
        return self

    def stop(self):
        self._server.shutdown()
        self._server.server_close()

    # ------------------------------------------------------------------
    def _auth_ok(self, msg) -> bool:
        if self.users_path is None:
            return True       # auth not configured (trust mode)
        return check_password(self.users_path, msg.get("user", ""),
                              msg.get("password", ""))

    def _handle(self, sock: socket.socket):
        first = recv_msg(sock)
        if first is None:
            return
        if first.get("op") == "cancel":
            # out-of-band cancel: a separate connection that never
            # authenticates (it proves identity with the secret)
            with self._lock:
                ent = self._sessions.get(first.get("pid"))
            # bytes on both sides: compare_digest raises on non-ASCII
            # str input, and the peer controls the secret field
            if ent is not None and hmac.compare_digest(
                    ent[0].encode(),
                    str(first.get("secret", "")).encode()):
                sess = ent[1]
                if sess.cancel_event is not None:
                    sess.cancel_event.set()
                send_msg(sock, {"ok": True})
            else:
                send_msg(sock, {"ok": False})
            return
        if first.get("op") != "startup":
            send_msg(sock, {"error": "expected startup message"})
            return
        if not self._auth_ok(first):
            send_msg(sock, {"error":
                            "password authentication failed"})
            return
        sess = self.make_session()
        # a waker-capable cancel: scheduler.wait parks on a condition
        # instead of polling, and this event can still interrupt it
        from ..exec.scheduler import CancelEvent
        sess.cancel_event = CancelEvent()
        with self._lock:
            pid = self._next_pid[0]
            self._next_pid[0] += 1
            secret = secrets.token_hex(16)
            self._sessions[pid] = (secret, sess)
        send_msg(sock, {"ok": {"server": _BANNER, "pid": pid,
                               "secret": secret}})
        try:
            while True:
                # a cancel that landed while the session was idle
                # targets nothing — drop it HERE, at the idle point,
                # before blocking for the next message (reference: a
                # backend ignores SIGINT outside statement execution).
                # Clearing any later — say, just before execute() —
                # races the cancel connection: a cancel arriving after
                # the query message was read but before the clear would
                # be silently dropped instead of canceling the
                # statement it targeted.
                sess.cancel_event.clear()
                blob = recv_frame(sock)
                if blob is None or not self._serve(sess, sock, blob):
                    return
        finally:
            # disconnect aborts any open transaction (reference:
            # backend exit path, AbortOutOfAnyTransaction)
            try:
                if sess.txn is not None:
                    sess.execute("rollback")
            except Exception:
                pass
            with self._lock:
                self._sessions.pop(pid, None)

    def _serve(self, sess, sock: socket.socket, blob: bytes) -> bool:
        """One message of a session, whose frame has arrived; False
        when the client is done.  A statement's trace starts at the
        frame's arrival, not after the parse, so that the wire's share
        (decode, and the reply's encode and send) and the parse are
        inside it; with a scheduler the dispatcher thread adopts it
        (one trace a statement, whichever thread runs it)."""
        t0 = time.perf_counter()
        c0 = obs_trace.thread_cpu() if obs_trace.ENABLED else None
        msg = decode_msg(blob)
        recv_ms = (time.perf_counter() - t0) * 1e3
        op = msg.get("op")
        if op != "query":
            return self._serve_op(sess, sock, op)
        sig = str(msg.get("sql", "")).strip()[:200]
        with obs_trace.trace_query(sig, since=t0, cpu_since=c0) as qt:
            obs_trace.record("wire.recv", recv_ms, bytes=len(blob))
            try:
                if self.scheduler is not None:
                    results = self.scheduler.run(sess, msg["sql"])
                else:
                    results = sess.execute(msg["sql"])
                reply = {"ok": [
                    {"command": r.command, "names": r.names,
                     "rows": r.rows, "rowcount": r.rowcount,
                     "text": r.text} for r in results]}
            except Exception as e:   # statement error: report, keep
                reply = {"error": f"{type(e).__name__}: {e}"}
                if qt is not None:
                    qt.failed = True
            # the client has its reply before this span ends: a
            # `last_query_stats()` read then sees the trace still open
            with obs_trace.span("wire.send") as sp:
                sp.set(bytes=send_msg(sock, reply))
        return True

    @staticmethod
    def _serve_op(sess, sock: socket.socket, op) -> bool:
        if op == "terminate":
            return False
        try:
            if op == "metrics":
                # Prometheus text exposition over the wire (the
                # reference exposes pg_stat_* via SQL only; a scrape
                # endpoint is table stakes here)
                reply = {"ok": sess.metrics_text()}
            elif op == "workshare":
                # cross-query work-sharing counters (otbshare):
                # shared-stream fan-in and result-cache hit/miss/
                # invalidation totals, queryable out-of-band so a load
                # driver can prove sublinearity without a full scrape
                from ..exec import share as workshare
                reply = {"ok": workshare.stats_snapshot()}
            elif op == "flight":
                # flight-recorder retrieval: the ringed postmortem
                # bundles (quarantine / timeout / breaker / OOM), so an
                # operator can pull forensics off a live CN without
                # filesystem access
                reply = {"ok": xray.flights()}
            else:
                reply = {"error": f"unknown op {op!r}"}
        except Exception as e:
            reply = {"error": f"{type(e).__name__}: {e}"}
        send_msg(sock, reply)
        return True


# ---------------------------------------------------------------------------
# client (the libpq analog; also used by `ctl shell --connect`)
# ---------------------------------------------------------------------------

class CnClient:
    def __init__(self, host: str, port: int, user: str = "otb",
                 password: str = "", timeout: float = 300.0):
        self.addr = (host, port)
        self._sock = socket.create_connection(self.addr,
                                              timeout=timeout)
        send_msg(self._sock, {"op": "startup", "user": user,
                              "password": password})
        resp = recv_msg(self._sock)
        if resp is None or "error" in resp:
            raise ConnectionError(
                (resp or {}).get("error", "connection closed"))
        self.pid = resp["ok"]["pid"]
        self.secret = resp["ok"]["secret"]

    def execute(self, sql: str) -> list[dict]:
        send_msg(self._sock, {"op": "query", "sql": sql})
        # expect_reply: the server owes an answer to every query — a
        # close here is a failed conversation, not an idle hangup
        with xray.wait_event("rpc-wire", node="cn"):
            resp = recv_msg(self._sock, expect_reply=True)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["ok"]

    def query(self, sql: str) -> list[tuple]:
        return [tuple(r) for r in self.execute(sql)[-1]["rows"]]

    def metrics(self) -> str:
        """Fetch the server's Prometheus text exposition."""
        send_msg(self._sock, {"op": "metrics"})
        with xray.wait_event("rpc-wire", node="cn"):
            resp = recv_msg(self._sock, expect_reply=True)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["ok"]

    def workshare(self) -> dict:
        """Fetch cross-query work-sharing counters (otbshare)."""
        send_msg(self._sock, {"op": "workshare"})
        with xray.wait_event("rpc-wire", node="cn"):
            resp = recv_msg(self._sock, expect_reply=True)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["ok"]

    def flight(self) -> list:
        """Fetch the server's ringed flight-recorder bundles."""
        send_msg(self._sock, {"op": "flight"})
        with xray.wait_event("rpc-wire", node="cn"):
            resp = recv_msg(self._sock, expect_reply=True)
        if "error" in resp:
            raise RuntimeError(resp["error"])
        return resp["ok"]

    def cancel(self):
        """Cancel the in-flight statement from ANOTHER connection (the
        PQcancel analog)."""
        s = socket.create_connection(self.addr, timeout=30)
        try:
            send_msg(s, {"op": "cancel", "pid": self.pid,
                         "secret": self.secret})
            return (recv_msg(s) or {}).get("ok", False)
        finally:
            s.close()

    def close(self):
        try:
            send_msg(self._sock, {"op": "terminate"})
        except Exception:
            pass
        self._sock.close()


def default_users_path(cluster_dir: str) -> str:
    return os.path.join(cluster_dir, "users.json")
