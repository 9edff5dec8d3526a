"""The one general load generator.  A traffic mix is a data file:

  loop         "closed": each client sends its next statement when the reply
               to the last has arrived (the only kind so far)
  clients      TCP connections, one thread each
  statements   [{"name", "share"}]: files under statements/
  order        "rotation": the statements in turn, pool members in turn;
               "shares": blocks that hold each statement in exactly its
               share, each block in an order drawn from the seed
  pool         how many parameter sets a "pool" statement has
  key_law      how "key" parameters are drawn ({"theta": 0.99} = YCSB zipf)
  warm_each    statements of draw "each" that every client runs in set-up
  served_tiers tiers that may serve a reply; any other counts as failed
  trace        {"seconds"}: how long of the window the traced run profiles

A statement file gives `steps` (SQL templates run in order, each a wire
statement of its own; `check` is "rows" for a reply compared with the
reference or "ack" for a write that must report one row), `params` (domains,
see params.py), `draw` ("pool": drawn once per pool member in set-up and
warmed there; "each": drawn anew for every request), `pinned` (parameter
values the same for every seed, one dict per pool member, dealt to the
members in an order drawn from the seed: for parameters whose every value
is a compiled program of its own), the `reference` module and the reply's
`float_cols`.
"""

import math
import threading
import time
from fractions import Fraction

import numpy as np

from . import files, params as params_mod


class Statement:
    def __init__(self, name):
        spec = files.statement(name)
        self.name = name
        self.steps = spec["steps"]
        self.domains = spec.get("params", {})
        self.draw = spec.get("draw", "each")
        self.pinned = spec.get("pinned", [])
        self.float_cols = tuple(spec.get("float_cols", ()))
        self.setup_sql = spec.get("setup_sql", [])
        self.key_columns = [(d["table"], d["column"])
                            for d in self.domains.values()
                            if d["kind"] == "key"]
        self.reference = files.reference(spec["reference"])

    def setup_statements(self):
        """The statement's set-up SQL, one wire call each.  An entry is SQL
        text, or {"sql", "start", "repeat"}: with a "row" template one
        statement of `repeat` rows ({rows}), without it the statement
        `repeat` times ({i} counts from `start` in both)."""
        for sql in self.setup_sql:
            if isinstance(sql, dict):
                span = range(sql["start"], sql["start"] + sql["repeat"])
                if "row" in sql:
                    sql = sql["sql"].format(rows=", ".join(
                        sql["row"].format(i=i) for i in span))
                else:
                    sql = ";\n".join(sql["sql"].format(i=i) for i in span)
            yield sql


class Request:
    """One run of a statement: its parameters and, per step, the reply."""
    __slots__ = ("stmt", "params", "expected", "steps")

    def __init__(self, stmt, params, expected=None):
        self.stmt, self.params, self.expected = stmt, params, expected
        self.steps = []     # (class, t0, t1, reply | None, error | None, stats)


class Mix:
    def __init__(self, name, seed, data):
        self.spec = files.traffic(name)
        if self.spec.get("loop", "closed") != "closed":
            raise ValueError("only closed loops are generated so far")
        self.seed = seed
        self.data = data
        self.clients = int(self.spec["clients"])
        self.statements = [Statement(s["name"])
                           for s in self.spec["statements"]]
        shares = np.asarray([float(s.get("share", 1))
                             for s in self.spec["statements"]])
        # "shares" order: every block of requests holds each statement in
        # exactly its share (19 reads and 1 write in 20 for 0.95/0.05), in
        # an order drawn from the seed — so every seed does the same work
        fr = [Fraction(float(s)).limit_denominator(100)
              for s in shares / shares.sum()]
        size = math.lcm(*(f.denominator for f in fr))
        self.block = np.repeat(np.arange(len(fr)),
                               [int(f * size) for f in fr])
        self.served_tiers = set(self.spec["served_tiers"])
        self.shared = {}        # the references' shared pre-computations
        self.key_laws = {}
        for st in self.statements:
            for table, col in st.key_columns:
                if (table, col) not in self.key_laws:
                    self.key_laws[(table, col)] = params_mod.KeyLaw(
                        data[table][col], self.spec.get("key_law"), seed)
        self.pools = {}         # statement name -> [Request template]

    # -- set-up ----------------------------------------------------------
    def build_pools(self):
        """Draw each pool statement's parameter sets from the seed and
        compute their references (outside the window)."""
        for i, st in enumerate(self.statements):
            if st.draw != "pool":
                continue
            rng = np.random.default_rng([self.seed, 0x706f6f6c, i])
            size = int(self.spec.get("pool", 1))
            pinned = [st.pinned[j % len(st.pinned)] if st.pinned else {}
                      for j in rng.permutation(size)]
            members = []
            for fixed in pinned:
                p = dict(fixed, **params_mod.draw(st.domains, rng,
                                                  self.key_laws))
                members.append((p, st.reference.expected(
                    self.data, p, self.shared)))
            self.pools[st.name] = members

    def warm_requests(self, client):
        """What one client runs in set-up: every pool member once (client 0
        only — programs and staged tables are the cluster's, shared by all
        connections) and `warm_each` draws of each "each" statement."""
        out = []
        rng = np.random.default_rng([self.seed, 0x7761726d, client])
        for st in self.statements:
            if st.draw == "pool":
                if client == 0:
                    out += [Request(st, p, want)
                            for p, want in self.pools[st.name]]
            else:
                for n in range(int(self.spec.get("warm_each", 1))):
                    out.append(Request(st, params_mod.draw(
                        st.domains, rng, self.key_laws, client, n)))
        return out

    def plan(self, client):
        """The endless sequence of one client's requests in the window."""
        rng = np.random.default_rng([self.seed, 0x72756e, client])
        fresh = int(self.spec.get("warm_each", 1))
        turn = 0
        while True:
            if self.spec.get("order", "shares") == "rotation":
                st = self.statements[turn % len(self.statements)]
                member = turn // len(self.statements)
            else:
                if turn % len(self.block) == 0:
                    block = rng.permutation(self.block)
                st = self.statements[int(block[turn % len(self.block)])]
                member = turn
            if st.draw == "pool":
                pool = self.pools[st.name]
                p, want = pool[member % len(pool)]
                yield Request(st, p, want)
            else:
                yield Request(st, params_mod.draw(
                    st.domains, rng, self.key_laws, client, fresh + turn))
            turn += 1

    # -- driving ---------------------------------------------------------
    def run_request(self, req, client, session=None, annotate=None):
        """Send the request's steps over the wire, one reply each.  With a
        `session` (traced run) the server-side per-statement stats are read
        right after each reply; `annotate` wraps each wire call in a span on
        the profiler's clock."""
        for step in req.stmt.steps:
            sql = step["sql"].format(**req.params)
            reply = error = stats = None
            span = annotate(f"bench:{step['class']}") if annotate else None
            t0 = time.perf_counter()
            try:
                if span is not None:
                    with span:
                        reply = self._send(client, step, sql)
                else:
                    reply = self._send(client, step, sql)
            except Exception as e:      # noqa: BLE001 — a failed statement
                error = f"{type(e).__name__}: {e}"
            t1 = time.perf_counter()
            if session is not None:
                stats = session.last_query_stats()
            req.steps.append((step["class"], t0, t1, reply, error, stats))
        return req

    @staticmethod
    def _send(client, step, sql):
        if step["check"] == "ack":
            return client.execute(sql)[-1]["rowcount"]
        return client.query(sql)

    def drive(self, conns, seconds, traced=False, annotate=None,
              on_start=None):
        """Closed loops over `conns` [(client, session)] for `seconds`.  No
        request starts after the deadline; those in flight finish and count.
        Returns (requests, t_start, time of the last reply) on the host's
        perf_counter."""
        done = [[] for _ in conns]
        go = threading.Event()
        t_start = [0.0]

        def loop(i):
            client, session = conns[i]
            plan = self.plan(i)
            go.wait()
            deadline = t_start[0] + seconds
            while time.perf_counter() < deadline:
                done[i].append(self.run_request(
                    next(plan), client, session if traced else None,
                    annotate))

        threads = [threading.Thread(target=loop, args=(i,), daemon=True)
                   for i in range(len(conns))]
        for t in threads:
            t.start()
        t_start[0] = time.perf_counter()
        if on_start is not None:
            on_start(t_start[0])
        go.set()
        for t in threads:
            t.join()
        requests = [r for d in done for r in d]
        # the window ends with the last reply, not with the join
        t_end = max([s[2] for r in requests for s in r.steps] + [t_start[0]])
        return requests, t_start[0], t_end

    # -- checking --------------------------------------------------------
    def check(self, req, limits):
        """(failures, avg_gap, ulp_gap) of one finished request against the
        reference: every step is a statement, and one that erred, was not
        acknowledged or differs from the reference fails."""
        from . import compare
        failures, avg_gap, ulp_gap = [], 0.0, 0.0
        want = req.expected
        for step, (cls, _t0, _t1, reply, error, _st) in zip(req.stmt.steps,
                                                            req.steps):
            where = f"{req.stmt.name}/{cls} {req.params}"
            if error is not None:
                failures.append(f"{where}: {error}")
            elif step["check"] == "ack":
                if reply != 1:
                    failures.append(f"{where}: acknowledged {reply!r}")
            else:
                if want is None:
                    want = req.stmt.reference.expected(
                        self.data, req.params, self.shared)
                bad, a, u = compare.rows_gap(reply, want,
                                             req.stmt.float_cols)
                avg_gap, ulp_gap = max(avg_gap, a), max(ulp_gap, u)
                if bad is None and a > limits["avg_rel_gap"]:
                    bad = f"an AVG column off by {a} (relative)"
                if bad is None and u > limits["decimal_ulp_gap"]:
                    bad = f"a DECIMAL column off by {u} ulps"
                if bad:
                    failures.append(f"{where}: {bad}")
        return failures, avg_gap, ulp_gap
