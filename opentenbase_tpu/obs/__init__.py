"""Observability subsystem — query tracing, unified metrics, slow log.

Reference analog: the DN→CN runtime instrumentation behind EXPLAIN
ANALYZE (commands/explain_dist.c) plus the pgstat views
(pg_stat_activity / pg_stat_statements family).  Three pillars:

- ``obs.trace``  — per-query span trees (wire.recv → parse → plan →
  stage → execute → exchange → finalize{gather,fetch,decode} →
  wire.send, waits as ``wait`` children), each span with its start on
  the statement's timeline and, as ``otb:<name>``, on the profiler's
  clock; a bounded ring of recent traces backing the ``otb_stat_query``
  view, and an opt-in structured slow-query log.
- ``obs.metrics`` — one process-global registry of counters / gauges /
  log-bucket histograms; the engine's existing stat surfaces
  (plancache, bufferpool, EXEC_STATS) register collectors into it, and
  it serves the ``otb_metrics`` view + Prometheus text exposition.
- EXPLAIN ANALYZE (exec/session.py, exec/dist_session.py) runs the
  statement under tracing and annotates the plan printout with actual
  rows / ms / cache behavior.

Purity contract: nothing in this package may be called from code
reachable from a jit/shard_map trace root — instrumentation lives at
the HOST boundaries (session dispatch, staging, program call sites,
materialization), never inside compiled programs.  The two halves of
the naming keep to their sides: ``jax.profiler.TraceAnnotation`` is
entered only here, in ``obs/``, host side (a span's lifetime, a wait's),
and what names the DEVICE's ops is ``jax.named_scope`` — allowed inside
traced closures, because it is metadata on the ops being traced and runs
nothing (ops/kernels.py has the ``otb.`` vocabulary).  The otblint
``obs-purity`` pass enforces all of this statically.
"""

from . import metrics, trace  # noqa: F401
