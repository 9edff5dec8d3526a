"""Scheduled jobs — the DBMS_JOB / job_scheduler.c analog
(parallel/jobs.py)."""

import time

import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.executor import ExecError
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.parallel.jobs import ensure_scheduler


def _mk():
    cl = Cluster(n_datanodes=2)
    s = ClusterSession(cl)
    s.execute("create table beats (at bigint) distribute by shard(at)")
    return cl, s


class TestJobs:
    def test_job_runs_on_schedule(self):
        cl, s = _mk()
        s.execute("create sequence beatseq")
        s.execute("create job heartbeat schedule 0.2 as "
                  "'insert into beats values (nextval(''beatseq''))'")
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            n = s.query("select count(*) from beats")[0][0]
            if n >= 3:
                break
            time.sleep(0.1)
        assert s.query("select count(*) from beats")[0][0] >= 3
        rows = s.query("select name, runs, failures from otb_jobs")
        assert rows and rows[0][0] == "heartbeat"
        assert rows[0][1] >= 3 and rows[0][2] == 0
        s.execute("drop job heartbeat")
        n0 = s.query("select count(*) from beats")[0][0]
        time.sleep(0.6)
        assert s.query("select count(*) from beats")[0][0] == n0

    def test_failures_recorded_not_fatal(self):
        cl, s = _mk()
        s.execute("create job bad schedule 0.1 as "
                  "'insert into no_such values (1)'")
        sch = ensure_scheduler(cl)
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            st = sch.state.get("bad", {})
            if st.get("failures", 0) >= 2:
                break
            time.sleep(0.1)
        rows = s.query("select failures, last_error from otb_jobs")
        assert rows[0][0] >= 2 and "no_such" in rows[0][1]
        s.execute("drop job bad")

    def test_ddl_validation(self):
        cl, s = _mk()
        with pytest.raises(ExecError, match="does not parse"):
            s.execute("create job j schedule 1 as 'not sql'")
        with pytest.raises(ExecError, match="positive"):
            s.execute("create job j schedule 0 as 'select 1'")
        with pytest.raises(ExecError, match="does not exist"):
            s.execute("drop job nope")
        s.execute("drop job if exists nope")

    def test_persists_in_catalog(self, tmp_path):
        d = str(tmp_path)
        cl = Cluster(n_datanodes=2, datadir=d)
        s = ClusterSession(cl)
        s.execute("create table jt (k bigint) distribute by shard(k)")
        s.execute("create job pj schedule 60 as "
                  "'insert into jt values (1)'")
        cl.checkpoint()
        cl2 = Cluster(datadir=d)
        assert "pj" in cl2.catalog.jobs
        assert cl2.catalog.jobs["pj"]["interval_s"] == 60.0

    def test_jobs_resume_after_restart(self, tmp_path):
        """Restart survival: a cluster initializing with
        persisted catalog.jobs runs them WITHOUT any new CREATE JOB —
        previously the scheduler only started from the DDL path, so
        every ctl start silently stopped all scheduled work."""
        d = str(tmp_path)
        cl = Cluster(n_datanodes=2, datadir=d)
        s = ClusterSession(cl)
        s.execute("create table rt (k bigint) distribute by shard(k)")
        s.execute("create job rj schedule 0.2 as "
                  "'insert into rt values (7)'")
        cl.checkpoint()
        cl._job_scheduler.stop()          # the "old process" dies
        cl2 = Cluster(datadir=d)          # restart: no CREATE JOB here
        sch = getattr(cl2, "_job_scheduler", None)
        assert sch is not None and sch.is_alive(), \
            "persisted jobs must restart the scheduler on cluster init"
        s2 = ClusterSession(cl2)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if s2.query("select count(*) from rt")[0][0] >= 2:
                break
            time.sleep(0.1)
        assert s2.query("select count(*) from rt")[0][0] >= 2
        s2.execute("drop job rj")
