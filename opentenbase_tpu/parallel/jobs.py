"""Scheduled jobs — the DBMS_JOB / pg_dbms_job analog.

Reference analog: postmaster/job_scheduler.c + pg_job.c (catalog
pg_dbms_job): Oracle-style scheduled statements run by a background
launcher.  Here: jobs are catalog entries ({interval seconds, SQL
text}), executed by one daemon thread per cluster through a dedicated
session — so a job is a plain statement with the full SQL surface
(triggers fire, constraints hold, audit records).  Run accounting
(runs, failures, last error) feeds the otb_jobs stat view.

DDL surface:
    CREATE JOB name SCHEDULE <seconds> AS '<sql>'
    DROP JOB [IF EXISTS] name
"""

from __future__ import annotations

import threading
import time

from ..sql import ast as A


class JobError(Exception):
    pass


def ddl(cluster, stmt):
    """Apply job DDL; returns a command tag or None."""
    cat = cluster.catalog
    if isinstance(stmt, A.CreateJobStmt):
        if stmt.name in cat.jobs:
            raise JobError(f"job {stmt.name!r} already exists")
        if stmt.interval_s <= 0:
            raise JobError("job interval must be positive")
        from ..sql.parser import parse_sql
        try:
            parse_sql(stmt.sql)
        except Exception as e:
            raise JobError(f"job SQL does not parse: {e}") from None
        cat.jobs[stmt.name] = {"interval_s": float(stmt.interval_s),
                               "sql": stmt.sql}
        cluster._save_catalog()
        ensure_scheduler(cluster)
        return "CREATE JOB"
    if isinstance(stmt, A.DropJobStmt):
        if stmt.name not in cat.jobs:
            if stmt.if_exists:
                return "DROP JOB"
            raise JobError(f"job {stmt.name!r} does not exist")
        del cat.jobs[stmt.name]
        cluster._save_catalog()
        return "DROP JOB"
    return None


_JOB_DDL_TYPES = None   # resolved lazily (A.CreateJobStmt at import is fine)


def ensure_scheduler(cluster) -> "JobScheduler":
    sch = getattr(cluster, "_job_scheduler", None)
    if sch is None or not sch.is_alive():
        sch = cluster._job_scheduler = JobScheduler(cluster)
        sch.start()
    return sch


def resume_jobs(cluster) -> None:
    """Restart survival: a cluster initializing with
    non-empty persisted catalog.jobs starts the launcher immediately —
    previously only the CREATE JOB DDL path did, so scheduled jobs
    silently stopped after every ctl start / Cluster(datadir=...)."""
    if cluster.catalog.jobs:
        ensure_scheduler(cluster)


class JobScheduler(threading.Thread):
    """One launcher per cluster (reference: the job scheduler
    launcher process).  Ticks every `tick` seconds; a job whose
    interval elapsed runs ONCE per elapse (no catch-up bursts after a
    stall — the reference's behavior for missed windows)."""

    def __init__(self, cluster, tick: float = 0.1):
        super().__init__(daemon=True, name="job-scheduler")
        self.cluster = cluster
        self.tick = tick
        self._stop = threading.Event()
        # name -> {"next": monotonic, "runs": n, "failures": n,
        #          "last_error": str}
        self.state: dict[str, dict] = {}

    def stop(self):
        self._stop.set()

    def _session(self):
        from ..exec.dist_session import ClusterSession
        return ClusterSession(self.cluster)

    def run_due(self, now: float = None) -> int:
        """Run every due job once; returns how many ran (exposed
        separately so tests can drive deterministically)."""
        now = time.monotonic() if now is None else now
        ran = 0
        jobs = dict(self.cluster.catalog.jobs)
        for name in list(self.state):
            if name not in jobs:
                del self.state[name]
        for name, j in jobs.items():
            st = self.state.setdefault(
                name, {"next": now, "runs": 0, "failures": 0,
                       "last_error": ""})
            if now < st["next"]:
                continue
            st["next"] = now + j["interval_s"]
            ran += 1
            try:
                self._session().execute(j["sql"])
                st["runs"] += 1
                st["last_error"] = ""
            except Exception as e:    # noqa: BLE001 — recorded, not fatal
                st["failures"] += 1
                st["last_error"] = f"{type(e).__name__}: {e}"[:200]
        return ran

    def run(self):
        while not self._stop.wait(self.tick):
            try:
                self.run_due()
            except Exception:
                pass
