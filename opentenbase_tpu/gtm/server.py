"""GTM — the global timestamp / transaction manager service.

Reference analog: src/gtm (GTM_ThreadMain main.c:3860, GTS issue
ProcessGetGTSCommand gtm_txn.c:1635, sequences gtm_seq.c, persistent store
gtm_store.c, standby streaming gtm_standby.c).  Re-designed host-side:

- A monotonic hybrid clock: GTS = max(last+1, wall_us) so timestamps are
  both monotone and loosely wall-aligned (the reference bumps a persisted
  base by a monotonic delta, gtm_txn.c:1434,1582).
- Runs in-process (centralized mode) or as a threaded TCP server with a
  tiny length-prefixed msgpack-free protocol (net/wire.py).
- Persistence: periodic state snapshots + a reserve window so a crash can
  never hand out a timestamp twice (the reference reserves GTS ranges in
  its mmap'd store for the same reason).
- Standby: see gtm/standby.py — a secondary GTM polls the primary's
  persisted reserve windows and promotes by resuming past them.
"""

from __future__ import annotations

import json
import os
import socket
import socketserver
import threading
import time
from typing import Optional

from ..net.wire import recv_msg, send_msg
from ..obs import xray
from ..utils import locks

RESERVE = 1_000_000  # timestamps reserved ahead per persistence write


class GtmCore:
    """The clock + txid + sequence state machine (shared by in-process and
    server modes)."""

    def __init__(self, store_path: Optional[str] = None,
                 ship=None, sync_ship: bool = True):
        """``ship``: optional hook called with each persisted state
        snapshot (reserve-window replication to a GtmStandby — see
        gtm/standby.py).  With ``sync_ship`` (the reference's synchronous
        standby), a failed ship blocks allocation past the last shipped
        window, so a promoted standby can never re-issue; async mode
        keeps serving and flags ``standby_ok`` False instead."""
        self._lock = locks.Lock("gtm.server.GtmCore._lock")
        self._ts = 100
        self._txid = 1
        self._sequences: dict[str, dict] = {}
        self._prepared: dict[str, dict] = {}   # gid -> info (2PC registry)
        # cluster barriers: name -> {gts, wall} (reference: the barrier
        # records CREATE BARRIER leaves for PITR, pgxc/barrier/barrier.c;
        # the GTM copy is the restore authority)
        self._barriers: dict[str, dict] = {}
        self.store_path = store_path
        self._ship = ship
        self._sync_ship = sync_ship
        self.standby_ok = ship is not None
        self._reserved_until = 0
        self._txid_reserved_until = 0
        if store_path and os.path.exists(store_path):
            with open(store_path) as f:
                st = json.load(f)
            # resume past the reserve window: nothing before it can have
            # been handed out after the crash
            self._ts = st["reserved_ts"]
            self._txid = st["reserved_txid"]
            self._sequences = st.get("sequences", {})
            self._prepared = st.get("prepared", {})
            self._barriers = st.get("barriers", {})
        self._persist_locked()

    def _persist_locked(self):
        st = {"reserved_ts": self._ts + RESERVE,
              "reserved_txid": self._txid + RESERVE,
              "sequences": self._sequences,
              "prepared": self._prepared,
              "barriers": self._barriers}
        if self.store_path:
            tmp = self.store_path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(st, f)
            os.replace(tmp, self.store_path)
        if self._ship is not None:
            # ship BEFORE extending the usable window: nothing may be
            # issued from a window the standby hasn't durably seen.
            # Deep-copied: an in-process standby must not alias the live
            # sequence/prepared dicts of a primary that later mutates them
            try:
                # may-acquire: gtm.standby.GtmStandby._lock
                self._ship(json.loads(json.dumps(st)))
                self.standby_ok = True
            except Exception:
                self.standby_ok = False
                if self._sync_ship:
                    raise
        self._reserved_until = self._ts + RESERVE
        self._txid_reserved_until = self._txid + RESERVE

    # ---- catalog generation (multi-coordinator DDL sync): every CN
    # checks this monotone counter before planning and reloads the
    # shared catalog on change (reference: CN-to-CN DDL propagation,
    # EXEC_ON_COORDS fan-out — here the GTM is the sync point).
    # Volatile by design: a GTM restart resets it to 0, which every CN
    # sees as a MISMATCH with its cached value and reloads — safe.
    def catalog_gen(self) -> int:
        with self._lock:
            return getattr(self, "_catalog_gen", 0)

    def bump_catalog_gen(self) -> int:
        with self._lock:
            self._catalog_gen = getattr(self, "_catalog_gen", 0) + 1
            return self._catalog_gen

    # ---- cluster-wide resource queues (reference: gtm_resqueue.c —
    # the GTM is the one place every coordinator already talks to, so
    # per-group concurrency caps enforced here hold across ALL CNs,
    # not per-process).  Each slot records its acquirer identity and a
    # lease deadline: a coordinator that crashes (or loses its GTM
    # connection) between acquire and release can no longer leak the
    # slot forever — expired leases are reaped at the next acquire, and
    # the TCP server reaps a connection's owners on disconnect,
    # mirroring gtm_resqueue.c's per-connection cleanup.
    def _resq_slots(self, group: str) -> list:
        # caller holds self._lock; slots: [owner, lease_deadline]
        rq = getattr(self, "_resq", None)
        if rq is None:
            rq = self._resq = {}
        slots = rq.setdefault(group, [])
        now = time.monotonic()
        kept = [s for s in slots if s[1] > now]
        # a reaped lease was an acquire that will never see its release
        # land (the owner crashed or lost its GTM connection): account
        # it, or the acquired/released ledger silently diverges
        if len(kept) != len(slots):
            st = self._resq_stats_dict()
            st["expired"] += len(slots) - len(kept)
        slots[:] = kept
        return slots

    def _resq_stats_dict(self) -> dict:
        # caller holds self._lock
        st = getattr(self, "_resq_stat", None)
        if st is None:
            st = self._resq_stat = {"acquired": 0, "released": 0,
                                    "expired": 0}
        return st

    def resq_acquire(self, group: str, cap: int, owner: str = "",
                     lease_s: float = 30.0) -> bool:
        with self._lock:
            slots = self._resq_slots(group)
            if cap > 0 and len(slots) >= cap:
                return False
            slots.append([owner,
                          time.monotonic() + max(float(lease_s), 0.001)])
            self._resq_stats_dict()["acquired"] += 1
            return True

    def resq_release(self, group: str, owner: str = "") -> None:
        with self._lock:
            slots = self._resq_slots(group)
            for i, s in enumerate(slots):
                if s[0] == owner:
                    del slots[i]
                    self._resq_stats_dict()["released"] += 1
                    return
            # identity-less legacy caller: positional release.  An
            # IDENTIFIED owner whose slot was already lease-reaped must
            # NOT pop someone else's slot — no-op instead (the reap was
            # already counted as `expired`, never double as `released`).
            if slots and not owner:
                del slots[0]
                self._resq_stats_dict()["released"] += 1

    def resq_disconnect(self, owner: str) -> int:
        """Reap every slot held by `owner` (connection closed / session
        gone).  Returns how many were freed."""
        if not owner:
            return 0
        freed = 0
        with self._lock:
            for group in list(getattr(self, "_resq", None) or {}):
                slots = self._resq_slots(group)
                kept = [s for s in slots if s[0] != owner]
                freed += len(slots) - len(kept)
                slots[:] = kept
            if freed:
                # the owner's goodbye IS its release (ledger stays
                # balanced for sessions that die holding slots)
                self._resq_stats_dict()["released"] += freed
        return freed

    def resq_counts(self) -> dict:
        with self._lock:
            return {g: len(self._resq_slots(g))
                    for g in list(getattr(self, "_resq", None) or {})}

    def resq_stats(self) -> dict:
        """Slot-lifecycle ledger: acquired == released + expired +
        (slots currently live) at any quiescent point — the GTM side of
        the scheduler's slot-leak invariant."""
        with self._lock:
            for g in list(getattr(self, "_resq", None) or {}):
                self._resq_slots(g)     # fold pending expiries in
            st = dict(self._resq_stats_dict())
        st["live"] = sum(self.resq_counts().values())
        return st

    # ---- API ----
    def next_gts(self) -> int:
        with self._lock:
            wall = int(time.time() * 1e6)
            self._ts = max(self._ts + 1, wall)
            if self._ts >= self._reserved_until:
                self._persist_locked()
            return self._ts

    def next_txid(self) -> int:
        with self._lock:
            self._txid += 1
            # txid allocation must trigger persistence on its own: a burst
            # of txid-only grants past the reserve window would otherwise
            # let a restarted GTM re-issue txids (advisor r1)
            if self._txid >= self._txid_reserved_until:
                self._persist_locked()
            return self._txid

    def seq_next(self, name: str, cache: int = 1) -> int:
        with self._lock:
            s = self._sequences.setdefault(
                name, {"next": 1, "increment": 1})
            v = s["next"]
            s["next"] = v + s["increment"] * cache
            self._persist_locked()
            return v

    def seq_list(self) -> dict:
        """Live sequence state {name: {"next","increment"}} — dump
        needs positions, not definitions (pg_dump emits setval)."""
        with self._lock:
            return {n: dict(s) for n, s in self._sequences.items()}

    def seq_create(self, name: str, start: int = 1, increment: int = 1):
        with self._lock:
            self._sequences[name] = {"next": start, "increment": increment}
            self._persist_locked()

    def seq_drop(self, name: str):
        with self._lock:
            self._sequences.pop(name, None)
            self._persist_locked()

    # ---- 2PC registry (reference: GTM tracks open/prepared global txns;
    # the in-doubt resolver asks it for verdicts, like pg_clean asks) ----
    def prepare_txn(self, gid: str, participants: list[str], txid: int):
        with self._lock:
            self._prepared[gid] = {"participants": participants,
                                   "txid": txid, "state": "prepared"}
            self._persist_locked()

    def commit_txn(self, gid: str, commit_ts: int):
        with self._lock:
            if gid in self._prepared:
                self._prepared[gid]["state"] = "committed"
                self._prepared[gid]["commit_ts"] = commit_ts
                self._persist_locked()

    def forget_txn(self, gid: str):
        with self._lock:
            self._prepared.pop(gid, None)
            self._persist_locked()

    def abort_txn(self, gid: str):
        with self._lock:
            if gid in self._prepared:
                self._prepared[gid]["state"] = "aborted"
                self._persist_locked()

    def txn_verdict(self, gid: str) -> str:
        """For in-doubt resolution: 'committed' (with ts), 'aborted', or
        'unknown' (never prepared here -> abort is safe)."""
        with self._lock:
            info = self._prepared.get(gid)
            if info is None:
                return "unknown"
            return info["state"]

    def prepared_list(self) -> dict:
        with self._lock:
            return dict(self._prepared)

    # ---- barriers (restore points) ----
    def barrier_create(self, name: str, gts: int):
        with self._lock:
            self._barriers[name] = {"gts": int(gts), "wall": time.time()}
            self._persist_locked()

    def barrier_list(self) -> dict:
        with self._lock:
            return dict(self._barriers)

    def stats(self) -> dict:
        """Read-only observability snapshot (no timestamp allocation)."""
        with self._lock:
            return {"ts": self._ts, "txid": self._txid,
                    "prepared": len(self._prepared)}


class GtmServer:
    """Threaded TCP front end for GtmCore (the reference's thread-pool +
    epoll loop, main.c:4819, collapsed to a threading server — the GTS
    critical section is a single atomic bump either way)."""

    def __init__(self, core: GtmCore, host: str = "127.0.0.1",
                 port: int = 0):
        self.core = core
        core_ref = core

        class Handler(socketserver.BaseRequestHandler):
            def handle(self):
                # owners whose resq slots were acquired over THIS
                # connection: reaped in finish() on disconnect
                # (reference: gtm_resqueue per-connection cleanup)
                self.resq_owners: set = set()
                while True:
                    try:
                        msg = recv_msg(self.request)
                    except (ConnectionError, EOFError):
                        return
                    if msg is None:
                        return
                    op = msg.get("op")
                    # inbound trace context → handler span; compacted
                    # subtree rides the reply (manual open/close: resp
                    # is assembled across the whole if-chain)
                    sx = xray.server_span(msg, op or "",
                                          node="gtm").open()
                    try:
                        if op == "gts":
                            resp = {"ts": core_ref.next_gts()}
                        elif op == "gts_batch":
                            n = msg.get("n", 1)
                            resp = {"ts": [core_ref.next_gts()
                                           for _ in range(n)]}
                        elif op == "txid":
                            resp = {"txid": core_ref.next_txid()}
                        elif op == "begin":
                            resp = {"txid": core_ref.next_txid(),
                                    "ts": core_ref.next_gts()}
                        elif op == "seq_next":
                            resp = {"v": core_ref.seq_next(
                                msg["name"], msg.get("cache", 1))}
                        elif op == "seq_create":
                            core_ref.seq_create(msg["name"],
                                                msg.get("start", 1),
                                                msg.get("increment", 1))
                            resp = {"ok": True}
                        elif op == "prepare":
                            core_ref.prepare_txn(msg["gid"],
                                                 msg["participants"],
                                                 msg["txid"])
                            resp = {"ok": True}
                        elif op == "commit":
                            core_ref.commit_txn(msg["gid"], msg["ts"])
                            resp = {"ok": True}
                        elif op == "abort":
                            core_ref.abort_txn(msg["gid"])
                            resp = {"ok": True}
                        elif op == "forget":
                            core_ref.forget_txn(msg["gid"])
                            resp = {"ok": True}
                        elif op == "verdict":
                            resp = {"state": core_ref.txn_verdict(
                                msg["gid"])}
                        elif op == "prepared_list":
                            resp = {"prepared": core_ref.prepared_list()}
                        elif op == "barrier_create":
                            core_ref.barrier_create(msg["name"],
                                                    msg["gts"])
                            resp = {"ok": True}
                        elif op == "barrier_list":
                            resp = {"barriers": core_ref.barrier_list()}
                        elif op == "stats":
                            resp = {"stats": core_ref.stats()}
                        elif op == "seq_list":
                            resp = {"seqs": core_ref.seq_list()}
                        elif op == "resq_acquire":
                            owner = msg.get("owner", "")
                            if owner:
                                self.resq_owners.add(owner)
                            # wire passthrough: the release arrives as
                            # its own message; disconnect/lease reap
                            # covers a peer that never sends it
                            resp = {"ok2": core_ref.resq_acquire(  # otblint: disable=slot-discipline
                                msg["group"], msg["cap"], owner,
                                msg.get("lease_s", 30.0))}
                        elif op == "resq_release":
                            core_ref.resq_release(msg["group"],
                                                  msg.get("owner", ""))
                            resp = {"ok": True}
                        elif op == "resq_counts":
                            resp = {"counts": core_ref.resq_counts()}
                        elif op == "resq_disconnect":
                            resp = {"freed": core_ref.resq_disconnect(
                                msg.get("owner", ""))}
                        elif op == "cat_gen":
                            resp = {"gen": core_ref.catalog_gen()}
                        elif op == "cat_gen_bump":
                            resp = {"gen": core_ref.bump_catalog_gen()}
                        elif op == "ping":
                            resp = {"pong": True}
                        else:
                            resp = {"error": f"unknown op {op!r}"}
                    except Exception as e:  # serve errors, don't die
                        resp = {"error": str(e)}
                    sx.close()
                    sx.attach(resp)
                    send_msg(self.request, resp)

            def finish(self):
                for owner in getattr(self, "resq_owners", ()):
                    try:
                        core_ref.resq_disconnect(owner)
                    except Exception:
                        pass
                super().finish()

        class Server(socketserver.ThreadingTCPServer):
            allow_reuse_address = True
            daemon_threads = True

        self._server = Server((host, port), Handler)
        self.host, self.port = self._server.server_address
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        daemon=True)

    def start(self):
        self._thread.start()
        return self

    def stop(self):
        self._server.shutdown()
        self._server.server_close()


class GtmClient:
    """Per-backend GTM connection (reference: access/transam/gtm.c
    InitGTM/GetGlobalTimestampGTM)."""

    def __init__(self, host: str, port: int):
        self.addr = (host, port)
        self._sock: Optional[socket.socket] = None
        self._lock = locks.Lock("gtm.server.GtmClient._lock")

    def _conn(self) -> socket.socket:
        if self._sock is None:
            self._sock = socket.create_connection(self.addr, timeout=10)
        return self._sock

    # the per-client lock IS the wire serializer — one request/response
    # conversation per socket at a time; the hold is bounded by the
    # socket timeout, so the RPC-under-lock here is the design
    def call(self, **msg) -> dict:  # otblint: disable=lock-blocking
        xray.inject(msg)
        op = msg.get("op", "")
        # wait-event attribution: timestamp/slot grants are the two
        # GTM waits tuners actually chase; everything else is generic
        ev = "gts-grant" if op in ("gts", "gts_batch", "begin") \
            else ("gtm-slot" if op == "resq_acquire" else "gtm-rpc")
        with self._lock:
            for attempt in (0, 1):
                try:
                    s = self._conn()
                    # chaos points: tests arm gtm.send/gtm.recv to
                    # simulate GTM loss without killing the server.
                    # wait_event's enter/exit touch the wait register
                    # + histograms (opaque to the callgraph):
                    # may-acquire: obs.xray._WLOCK
                    # may-acquire: obs.metrics.Registry._lock
                    # may-acquire: obs.metrics.metric._lock
                    with xray.wait_event(ev):
                        send_msg(s, msg, fault="gtm.send")
                        # expect_reply: a close while the GTM owes an
                        # answer is a WireError, never "no message"
                        resp = recv_msg(s, expect_reply=True,
                                        fault="gtm.recv")
                    xray.absorb(resp, node="gtm", op=op)
                    if "error" in resp:
                        raise RuntimeError(f"gtm error: {resp['error']}")
                    return resp
                except (ConnectionError, OSError, EOFError):
                    self.close()
                    if attempt:
                        raise
            raise ConnectionError("unreachable")

    def close(self):
        if self._sock is not None:
            try:
                self._sock.close()
            finally:
                self._sock = None

    # typed helpers (mirror GtmCore's surface so Cluster can use either)
    def next_gts(self) -> int:
        return self.call(op="gts")["ts"]

    def next_txid(self) -> int:
        return self.call(op="txid")["txid"]

    def begin(self) -> tuple[int, int]:
        r = self.call(op="begin")
        return r["txid"], r["ts"]

    def seq_create(self, name, start=1, increment=1):
        self.call(op="seq_create", name=name, start=start,
                  increment=increment)

    def seq_next(self, name, cache=1) -> int:
        return self.call(op="seq_next", name=name, cache=cache)["v"]

    def prepare_txn(self, gid, participants, txid):
        self.call(op="prepare", gid=gid, participants=participants,
                  txid=txid)

    def commit_txn(self, gid, ts):
        self.call(op="commit", gid=gid, ts=ts)

    def abort_txn(self, gid):
        self.call(op="abort", gid=gid)

    def forget_txn(self, gid):
        self.call(op="forget", gid=gid)

    def txn_verdict(self, gid) -> str:
        return self.call(op="verdict", gid=gid)["state"]

    def prepared_list(self) -> dict:
        return self.call(op="prepared_list")["prepared"]

    def barrier_create(self, name, gts):
        self.call(op="barrier_create", name=name, gts=int(gts))

    def barrier_list(self) -> dict:
        return self.call(op="barrier_list")["barriers"]

    def stats(self) -> dict:
        return self.call(op="stats")["stats"]

    def seq_list(self) -> dict:
        return self.call(op="seq_list")["seqs"]

    def resq_acquire(self, group: str, cap: int, owner: str = "",
                     lease_s: float = 30.0) -> bool:
        return self.call(op="resq_acquire", group=group, cap=cap,
                         owner=owner, lease_s=lease_s)["ok2"]

    def resq_release(self, group: str, owner: str = "") -> None:
        self.call(op="resq_release", group=group, owner=owner)

    def resq_disconnect(self, owner: str) -> int:
        return self.call(op="resq_disconnect", owner=owner)["freed"]

    def resq_counts(self) -> dict:
        return self.call(op="resq_counts")["counts"]

    def catalog_gen(self) -> int:
        return self.call(op="cat_gen")["gen"]

    def bump_catalog_gen(self) -> int:
        return self.call(op="cat_gen_bump")["gen"]
