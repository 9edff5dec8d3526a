"""The served stack as PR 22's smoke proved it on the chip: an in-process
Cluster (GTM + WAL + checkpoints under a run directory) fronted by the CN
wire server, and CnClients over TCP from this same process (one process per
chip; a client thread never touches jax).  Copied from chip_smoke.py
(start_stack, phase_load) and parametrised by the configuration file."""

import os
import time

import pandas as pd

from . import datagen

CLIENT_TIMEOUT_S = 1100.0   # a cold reply waits minutes for the compiler


class Stack:
    def __init__(self, n_datanodes, datadir):
        from opentenbase_tpu.exec.dist_session import ClusterSession
        from opentenbase_tpu.net.cn_server import CnServer
        from opentenbase_tpu.parallel.cluster import Cluster
        self.datadir = datadir
        self.cluster = Cluster(n_datanodes=n_datanodes, datadir=datadir)
        self.sessions = []      # server-side sessions, in connection order
        self.clients = []

        def make_session():
            s = ClusterSession(self.cluster)
            self.sessions.append(s)
            return s

        self.server = CnServer(make_session).start()

    def connect(self):
        """A new TCP connection; returns (client, its server-side session).
        The session exists once the startup reply has arrived, and
        connections are made one at a time, so it is the newest."""
        from opentenbase_tpu.net.cn_server import CnClient
        client = CnClient(self.server.host, self.server.port,
                          timeout=CLIENT_TIMEOUT_S)
        self.clients.append(client)
        return client, self.sessions[-1]

    def stop(self):
        for c in self.clients:
            try:
                c.close()
            except OSError:
                pass
        self.server.stop()


def load_tpch(stack, client, data, copy_tables, copy_dir):
    """DDL over the wire; `copy_tables` by COPY over the wire from .tbl
    files, the rest by the bulk column path (bench.py's).  Returns seconds
    per table."""
    from opentenbase_tpu.exec.dist_session import ClusterSession
    from opentenbase_tpu.tpch.schema import SCHEMA
    client.execute(SCHEMA)
    bulk = ClusterSession(stack.cluster)
    took = {}
    for tname in datagen.LOAD_ORDER:
        t0 = time.perf_counter()
        table = data[tname]
        n = len(next(iter(table.values())))
        if tname in copy_tables:
            path = os.path.join(copy_dir, f"{tname}.tbl")
            pd.DataFrame(datagen.to_tbl_frame(
                table, datagen.DATE_COLS.get(tname, ()))).to_csv(
                path, sep="|", header=False, index=False)
            res = client.execute(
                f"copy {tname} from '{path}' with (delimiter '|')")
            got = res[0]["rowcount"]
        else:
            got = bulk._insert_rows(
                stack.cluster.catalog.table(tname), table, n)
        if got != n:
            raise RuntimeError(f"load {tname}: {got} rows of {n}")
        took[tname] = time.perf_counter() - t0
    return took
