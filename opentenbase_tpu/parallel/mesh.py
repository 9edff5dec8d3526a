"""Device-mesh data plane: the FN forwarding plane mapped onto ICI.

Reference analog: the FN shared-memory page pool + sender/receiver
processes streaming tagged tuple pages between datanodes over TCP
(src/backend/forward, postmaster/forwardsend.c:1-16, fnbufpage.h).  On a
TPU pod the same role is played by XLA collectives inside one compiled
program: hash-redistribute == all_to_all over ICI, broadcast == all_gather,
partial/final aggregation == psum — no pages, no sockets, no copies
through host memory.

This module is the multi-chip execution tier: table shards live as
device-sharded arrays over a `jax.sharding.Mesh` (one logical datanode per
device), and whole plan fragments compile to a single shard_map program.
The host-mediated exchange tier (exec/dist.py) remains the general path
(arbitrary plans, multi-process clusters); this tier covers the fragment
shapes where staying on-device end-to-end pays: scan -> redistribute ->
join/aggregate pipelines.

Static-shape contract: all_to_all needs equal-sized buckets, so each
source packs at most `bucket` rows per destination per step (the FnPage
analog: fixed-size pages, HUGE tuples span pages).  The pack is a gather
(ops/kernels.bucket_rows + take_rows: a bucket's slot s takes the s-th
local row bound for that destination, the columns move as 32-bit rows; no
argsort, no scatter).  `redistribute`
returns an overflow count so callers size buckets (power-of-two growth,
like the executor's batch size classes) and re-run if rows would drop.
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops import kernels as K
from ..utils.hashing import splitmix64_jax


def make_mesh(n_devices: Optional[int] = None,
              axis: str = "dn") -> Mesh:
    devs = jax.devices()
    n = n_devices or len(devs)
    if len(devs) < n:
        raise ValueError(f"need {n} devices, have {len(devs)}")
    return Mesh(np.asarray(devs[:n]), axis_names=(axis,))


def shard_columns(mesh: Mesh, cols: dict, nrows: int):
    """Pad columns to a per-device-even size and place them sharded over
    the mesh axis.  Returns (device cols, valid mask)."""
    n_dev = mesh.devices.size
    per = -(-nrows // n_dev)
    padded = per * n_dev
    out = {}
    sh = NamedSharding(mesh, P(mesh.axis_names[0]))
    for name, arr in cols.items():
        a = np.asarray(arr)
        buf = np.zeros((padded, *a.shape[1:]), dtype=a.dtype)
        buf[:nrows] = a[:nrows]
        out[name] = jax.device_put(buf, sh)
    valid = np.zeros(padded, dtype=bool)
    valid[:nrows] = True
    return out, jax.device_put(valid, sh)


def _pack_for_a2a(key_hash, arrs, valid, n_dev: int, bucket: int):
    """Inside shard_map: each slot of a destination's fixed-size bucket
    finds its local row (`kernels.bucket_rows`), the columns come through
    that index in one row gather (`kernels.take_rows`); count overflow.
    The same pack as `exec/mesh_exec._a2a_batch`'s."""
    dest = jnp.where(valid, (key_hash % jnp.uint64(n_dev)).astype(jnp.int32),
                     n_dev)
    src, keep, overflow = K.bucket_rows(dest, n_dev, bucket)
    return K.take_rows(tuple(arrs), src, keep), keep, overflow


def redistribute_program(mesh: Mesh, names: list, key_col: str,
                         bucket: int):
    """The jitted shard_map exchange program behind redistribute():
    fn(valid, *cols in `names` order) -> (mask, overflow_total, *cols)."""
    axis = mesh.axis_names[0]
    n_dev = mesh.devices.size

    def prog(valid_l, *arrs):
        h = splitmix64_jax(arrs[names.index(key_col)].astype(jnp.uint64))
        packed, mask, overflow = _pack_for_a2a(h, arrs, valid_l, n_dev,
                                               bucket)
        out = [jax.lax.all_to_all(p.reshape(n_dev, bucket,
                                            *p.shape[1:]),
                                  axis, 0, 0).reshape(n_dev * bucket,
                                                      *p.shape[2:])
               for p in packed]
        omask = jax.lax.all_to_all(mask.reshape(n_dev, bucket), axis,
                                   0, 0).reshape(-1)
        return (omask, jax.lax.psum(overflow, axis), *out)

    return jax.jit(shard_map(
        prog, mesh=mesh,
        in_specs=(P(axis), *[P(axis)] * len(names)),
        out_specs=(P(axis), P(), *[P(axis)] * len(names))))


def redistribute(mesh: Mesh, cols: dict, valid, key_col: str,
                 bucket: int):  # otblint: sync-boundary
    """Hash-redistribute sharded columns by cols[key_col] so each row
    lands on its owner device: ONE all_to_all per column over ICI.

    Returns (new cols dict, new valid, overflow_total).  overflow > 0
    means some source had more than `bucket` rows for one destination —
    re-run with a larger bucket (size-class growth)."""
    names = list(cols.keys())
    res = redistribute_program(mesh, names, key_col, bucket)(
        valid, *[cols[n] for n in names])
    omask, overflow = res[0], int(jax.device_get(res[1]))
    return dict(zip(names, res[2:])), omask, overflow


def redistribute_auto(mesh: Mesh, cols: dict, valid, key_col: str,
                      start_bucket: int = 256, max_bucket: int = 1 << 20):
    """Size-class retry loop around redistribute (the dynamic-shape
    strategy from SURVEY.md §7.3 applied to the exchange)."""
    bucket = start_bucket
    while True:
        out, omask, overflow = redistribute(mesh, cols, valid, key_col,
                                            bucket)
        if overflow == 0:
            return out, omask, bucket
        if bucket >= max_bucket:
            raise RuntimeError("redistribute bucket overflow at max size")
        bucket *= 2


def psum_partial(mesh: Mesh, fn, cols: dict, valid, n_out: int):
    """Run fn(valid, cols) -> tuple of n_out per-shard partials, psum them
    across the mesh (the partial->final aggregate split as one compiled
    program)."""
    axis = mesh.axis_names[0]
    names = list(cols.keys())

    def prog(valid_l, *arrs):
        parts = fn(valid_l, dict(zip(names, arrs)))
        return tuple(jax.lax.psum(p, axis) for p in parts)

    smapped = shard_map(prog, mesh=mesh,
                        in_specs=(P(axis), *[P(axis)] * len(names)),
                        out_specs=tuple(P() for _ in range(n_out)))
    return jax.jit(smapped)(valid, *[cols[n] for n in names])
