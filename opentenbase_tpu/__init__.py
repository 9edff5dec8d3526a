"""opentenbase_tpu — a TPU-native distributed SQL (HTAP) framework.

A from-scratch rebuild of the capabilities of OpenTenBase (reference:
/root/reference, a Postgres-XL-derived CN/DN/GTM shared-nothing cluster),
re-architected for TPU:

- DataNode executor hot loops (scan/filter/project, hash join, hash agg,
  sort, expression evaluation — reference src/backend/executor/*) run as
  JAX/XLA kernels over columnar shard batches.
- Inter-datanode hash redistribution (reference FN data plane,
  src/backend/forward + postmaster/forwardsend.c) maps to XLA `all_to_all`
  over ICI via `jax.sharding.Mesh` + `shard_map`.
- The control plane (parser, catalog, planner, GTS timestamp oracle, 2PC)
  is host-side, mirroring the reference's CN/GTM roles.

Layout (≈ reference layer map, SURVEY.md §1):
- catalog/   type system + system catalog (ref src/backend/catalog, pgxc_*)
- storage/   columnar chunk store, WAL, checkpoints (ref src/backend/storage)
- sql/       lexer/parser/analyzer (ref src/backend/parser)
- plan/      logical+physical planner, FQS, distribution (ref optimizer, pgxc/plan)
- exec/      host-side fragment executor over device kernels (ref executor)
- ops/       JAX kernel library (ref execExprInterp/nodeHash/nodeAgg hot loops)
- parallel/  shard map, locator, cluster 2PC, mesh collectives (ref
             pgxc/locator, forward, execRemote.c remote-2PC)
- gtm/       timestamp-oracle service (ref src/gtm); distributed MVCC
             (GTS visibility, ref access/transam + tqual.c) lives in
             storage/ + ops/kernels.py as fused scan kernels
- net/       control-plane RPC between CN/DN processes (ref pooler/pgxcnode)
- cli/       psql-analog shell + cluster ctl (ref src/bin, contrib/pgxc_ctl)
"""

import jax

# The engine is a database: 64-bit keys (e.g. TPC-H orderkey at SF100 exceeds
# int32) and exact int64 decimal arithmetic are part of the storage contract.
jax.config.update("jax_enable_x64", True)

__version__ = "0.1.0"
