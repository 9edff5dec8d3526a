"""Cluster lifecycle tool — the pgxc_ctl / opentenbase_ctl analog
(reference: contrib/pgxc_ctl README.md:96-123, contrib/opentenbase_ctl).

Subcommands:
  init     <dir> --datanodes N        lay out a cluster directory
  start    <dir>                      start gtm + datanode servers
                                      (in this process, threaded; prints
                                      addresses and serves until ^C)
  shell    <dir> [--connect host:port,...]   interactive SQL shell
  status   <dir>                      node liveness (health-map analog)

Python -m entry: python -m opentenbase_tpu.cli.ctl <cmd> ...
"""

from __future__ import annotations

import argparse
import json
import os
import sys


def cmd_init(args):
    os.makedirs(args.dir, exist_ok=True)
    cfg = {"datanodes": args.datanodes, "gtm_port": args.gtm_port,
           "dn_base_port": args.dn_base_port, "cn_port": args.cn_port}
    with open(os.path.join(args.dir, "cluster.json"), "w") as f:
        json.dump(cfg, f, indent=2)
    # build the initial catalog (node registry + shard map)
    from ..parallel.cluster import Cluster
    Cluster(n_datanodes=args.datanodes, datadir=args.dir).checkpoint()
    from ..net.cn_server import default_users_path, write_users
    from ..net.pgwire import write_pg_users
    write_users(default_users_path(args.dir),
                {args.user: args.password})
    # add the md5 verifier so the PostgreSQL-protocol port (libpq
    # drivers) authenticates the same user
    write_pg_users(default_users_path(args.dir),
                   {args.user: args.password})
    print(f"initialized cluster dir {args.dir} "
          f"({args.datanodes} datanodes, sql user {args.user!r})")


def _load_cfg(d):
    with open(os.path.join(d, "cluster.json")) as f:
        return json.load(f)


def cmd_start(args):
    cfg = _load_cfg(args.dir)
    # arm the persistent XLA compilation cache BEFORE anything compiles:
    # a restarted cluster re-reads every compiled program from disk
    # instead of paying the compile wall again (ISSUE 1)
    from ..exec.plancache import enable_persistent_cache
    enable_persistent_cache()
    from ..gtm.server import GtmCore, GtmServer
    from ..net.dn_server import DnServer
    gtm_core = GtmCore(os.path.join(args.dir, "gtm.json"))
    gtm = GtmServer(gtm_core, port=cfg["gtm_port"]).start()
    print(f"gtm listening on {gtm.host}:{gtm.port}")
    catalog_path = os.path.join(args.dir, "catalog.json")
    servers = []
    factories = []

    def make_factory(i):
        def factory():
            return DnServer(i, os.path.join(args.dir, f"dn{i}"),
                            catalog_path, gtm_addr=(gtm.host, gtm.port),
                            port=cfg["dn_base_port"] + i).start()
        return factory

    for i in range(cfg["datanodes"]):
        factories.append(make_factory(i))
        srv = factories[i]()
        servers.append(srv)
        print(f"dn{i} listening on {srv.host}:{srv.port}")
    # client-facing SQL listener over the started TCP datanodes
    from ..exec.dist_session import ClusterSession
    from ..net.cn_server import CnServer, default_users_path
    from ..parallel.cluster import Cluster
    cluster = Cluster.connect(catalog_path,
                              [(s.host, s.port) for s in servers],
                              (gtm.host, gtm.port))
    users = default_users_path(args.dir)
    cluster.ensure_monitor(auto_failover=True)
    cn = CnServer(lambda: ClusterSession(cluster),
                  users_path=users if os.path.exists(users) else None,
                  port=cfg.get("cn_port", 7900)).start()
    print(f"cn listening on {cn.host}:{cn.port}")
    # PostgreSQL-protocol front door (psql/psycopg2/JDBC) one port up
    from ..net.pgwire import PgWireServer
    pg = PgWireServer(lambda: ClusterSession(cluster),
                      users_path=users if os.path.exists(users)
                      else None,
                      port=cfg.get("pg_port",
                                   cfg.get("cn_port", 7900) + 1)).start()
    print(f"pg wire listening on {pg.host}:{pg.port}")
    addrs = {"gtm": [gtm.host, gtm.port],
             "datanodes": [[s.host, s.port] for s in servers],
             "cn": [cn.host, cn.port],
             "pg": [pg.host, pg.port]}
    with open(os.path.join(args.dir, "addresses.json"), "w") as f:
        json.dump(addrs, f)
    print("cluster up (supervised); ^C to stop")
    try:
        Supervisor(servers, factories, catalog_path).run(interval=5.0)
    except KeyboardInterrupt:
        for s in servers:
            s.stop()
        gtm.stop()


class Supervisor:
    """Datanode watchdog: ping each server, restart dead ones from
    their data directories (reference: the postmaster restarting dead
    children, postmaster.c, + the cluster monitor's health map,
    nodemgr.c:1122 PgxcNodeGetHealthMap)."""

    def __init__(self, servers: list, factories: list,
                 catalog_path: str = ""):
        self.servers = servers          # mutated in place on restart
        self.factories = factories      # index -> () -> started server
        self.catalog_path = catalog_path

    def _fenced(self, i: int) -> bool:
        """True when the shared catalog no longer points at this
        server's address — a failover promoted the standby, and
        resurrecting the old primary here would split-brain the slot
        (reference: the fencing step of pgxc_ctl failover)."""
        if not self.catalog_path or not os.path.exists(
                self.catalog_path):
            return False
        try:
            from ..catalog.catalog import Catalog
            cat = Catalog.load(self.catalog_path)
            srv = self.servers[i]
            for nd in cat.datanodes():
                if nd.index == i and nd.port and \
                        (nd.host, nd.port) != (srv.host, srv.port):
                    return True
        except Exception:
            return False
        return False

    def _alive(self, i: int) -> bool:
        """Fresh connection per probe, closed afterwards: liveness means
        'the acceptor answers NOW' — a pooled socket can outlive a dead
        listener and mask the failure."""
        from ..net.dn_server import RemoteDataNode
        srv = self.servers[i]
        proxy = None
        try:
            proxy = RemoteDataNode(i, srv.host, srv.port)
            return proxy.ping()
        except Exception:
            return False
        finally:
            if proxy is not None:
                try:
                    proxy.close()
                except Exception:
                    pass

    def check_once(self) -> list[int]:
        """Ping every datanode; recreate the dead ones (recovery replays
        their WAL).  Returns the restarted indexes.  A failed restart is
        logged and retried next tick — one sick node must not kill the
        watchdog (the postmaster keeps supervising too)."""
        restarted = []
        for i in range(len(self.servers)):
            if self._alive(i):
                continue
            if self._fenced(i):
                continue    # failover moved this slot: do not resurrect
            try:
                self.servers[i].stop()
            except Exception:
                pass
            try:
                self.servers[i] = self.factories[i]()
            except Exception as e:
                print(f"supervisor: dn{i} restart failed "
                      f"({type(e).__name__}: {e}); retrying next tick")
                continue
            restarted.append(i)
        return restarted

    def run(self, interval: float = 5.0):
        import time
        while True:
            time.sleep(interval)
            for i in self.check_once():
                srv = self.servers[i]
                print(f"supervisor: restarted dn{i} on "
                      f"{srv.host}:{srv.port}")


def _connect(args):
    from ..exec.dist_session import ClusterSession
    from ..parallel.cluster import Cluster
    addrpath = os.path.join(args.dir, "addresses.json")
    if os.path.exists(addrpath):
        with open(addrpath) as f:
            addrs = json.load(f)
        cluster = Cluster.connect(
            os.path.join(args.dir, "catalog.json"),
            [tuple(a) for a in addrs["datanodes"]],
            tuple(addrs["gtm"]))
    else:
        cluster = Cluster(datadir=args.dir)   # embedded (centralized) mode
    return ClusterSession(cluster)


def cmd_dump(args):
    """pg_dump analog: one reloadable SQL script (cli/dump.py)."""
    from .dump import dump_sql
    s = _connect(args)
    script = dump_sql(s)
    with open(args.out, "w") as f:
        f.write(script)
    print(f"dumped {script.count(chr(10))} lines to {args.out}")


def cmd_load(args):
    """pg_restore analog: replay a dump script."""
    from .dump import restore_sql
    s = _connect(args)
    with open(args.file) as f:
        n = restore_sql(s, f.read())
    print(f"restored {n} statements from {args.file}")


def cmd_shell(args):
    if getattr(args, "connect", None):
        return _remote_shell(args)
    s = _connect(args)
    print("opentenbase_tpu shell — \\q to quit")
    buf = []
    while True:
        try:
            line = input("otb=# " if not buf else "otb-# ")
        except (EOFError, KeyboardInterrupt):
            print()
            return
        if line.strip() in ("\\q", "exit", "quit"):
            return
        buf.append(line)
        if not line.rstrip().endswith(";"):
            continue
        sql = "\n".join(buf)
        buf = []
        try:
            for r in s.execute(sql):
                if r.names:
                    print(" | ".join(r.names))
                    print("-+-".join("-" * len(n) for n in r.names))
                    for row in r.rows:
                        print(" | ".join(str(v) for v in row))
                    print(f"({len(r.rows)} row"
                          f"{'s' if len(r.rows) != 1 else ''})")
                else:
                    print(r.command
                          + (f" {r.rowcount}" if r.rowcount else ""))
        except Exception as e:
            print(f"ERROR: {type(e).__name__}: {e}")


def _remote_shell(args):
    """Wire-protocol client shell: connects to a CN server like psql
    connects to a backend (reference: src/bin/psql over libpq)."""
    from ..net.cn_server import CnClient
    host, port = args.connect.rsplit(":", 1)
    c = CnClient(host, int(port), user=args.user,
                 password=args.password)
    print(f"connected to {args.connect} as {args.user} — \\q to quit")
    buf = []
    while True:
        try:
            line = input("otb=# " if not buf else "otb-# ")
        except (EOFError, KeyboardInterrupt):
            print()
            c.close()
            return
        if line.strip() in ("\\q", "exit", "quit"):
            c.close()
            return
        buf.append(line)
        if not line.rstrip().endswith(";"):
            continue
        sql = "\n".join(buf)
        buf = []
        try:
            for r in c.execute(sql):
                if r["names"]:
                    print(" | ".join(r["names"]))
                    print("-+-".join("-" * len(n) for n in r["names"]))
                    for row in r["rows"]:
                        print(" | ".join(str(v) for v in row))
                    print(f"({len(r['rows'])} row"
                          f"{'s' if len(r['rows']) != 1 else ''})")
                else:
                    print(r["command"]
                          + (f" {r['rowcount']}" if r["rowcount"]
                             else ""))
        except RuntimeError as e:
            print(f"ERROR: {e}")


def cmd_restore(args):
    """Restore the whole cluster to a named barrier (reference: PITR to
    a CREATE BARRIER point, pgxc/barrier/barrier.c).  Run against a
    STOPPED cluster dir (embedded mode re-attaches the datadirs)."""
    from ..parallel.cluster import Cluster
    cluster = Cluster(datadir=args.dir)
    cluster.restore_barrier(args.barrier)
    cluster.checkpoint()
    print(f"cluster {args.dir} restored to barrier {args.barrier!r}")


def cmd_barriers(args):
    from ..parallel.cluster import Cluster
    cluster = Cluster(datadir=args.dir)
    bl = cluster.gtm.barrier_list()
    if not bl:
        print("no barriers")
    for name, info in sorted(bl.items(), key=lambda kv: kv[1]["gts"]):
        print(f"{name}\tgts={info['gts']}")


def cmd_status(args):
    addrpath = os.path.join(args.dir, "addresses.json")
    if not os.path.exists(addrpath):
        print("cluster not started (no addresses.json)")
        return
    with open(addrpath) as f:
        addrs = json.load(f)
    from ..gtm.server import GtmClient
    from ..net.dn_server import RemoteDataNode
    try:
        GtmClient(*addrs["gtm"]).call(op="ping")
        print(f"gtm {addrs['gtm'][0]}:{addrs['gtm'][1]}: up")
    except Exception:
        print(f"gtm {addrs['gtm'][0]}:{addrs['gtm'][1]}: DOWN")
    for i, (h, p) in enumerate(addrs["datanodes"]):
        ok = RemoteDataNode(i, h, p).ping()
        print(f"dn{i} {h}:{p}: {'up' if ok else 'DOWN'}")


def main(argv=None):
    ap = argparse.ArgumentParser(prog="opentenbase_tpu_ctl")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("init")
    p.add_argument("dir")
    p.add_argument("--datanodes", type=int, default=2)
    p.add_argument("--gtm-port", type=int, default=7777)
    p.add_argument("--dn-base-port", type=int, default=7800)
    p.add_argument("--cn-port", type=int, default=7900)
    p.add_argument("--user", default="otb")
    p.add_argument("--password", default="otb")
    p.set_defaults(fn=cmd_init)
    p = sub.add_parser("start")
    p.add_argument("dir")
    p.set_defaults(fn=cmd_start)
    p = sub.add_parser("shell")
    p.add_argument("dir", nargs="?", default=".")
    p.add_argument("--connect", help="host:port of a running CN server")
    p.add_argument("--user", default="otb")
    p.add_argument("--password", default="otb")
    p.set_defaults(fn=cmd_shell)
    p = sub.add_parser("status")
    p.add_argument("dir")
    p.set_defaults(fn=cmd_status)
    p = sub.add_parser("restore")
    p.add_argument("dir")
    p.add_argument("--barrier", required=True)
    p.set_defaults(fn=cmd_restore)
    p = sub.add_parser("dump")
    p.add_argument("dir", nargs="?", default=".")
    p.add_argument("--out", required=True)
    p.set_defaults(fn=cmd_dump)
    p = sub.add_parser("load")
    p.add_argument("dir", nargs="?", default=".")
    p.add_argument("--file", required=True)
    p.set_defaults(fn=cmd_load)
    p = sub.add_parser("barriers")
    p.add_argument("dir")
    p.set_defaults(fn=cmd_barriers)
    args = ap.parse_args(argv)
    args.fn(args)


if __name__ == "__main__":
    main()
