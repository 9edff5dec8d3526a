"""Device dtype policy — the TPU-safe execution mode.

TPU MXU/VPU have no float64 ALU: XLA emulates int64 (as 32-bit pairs —
slower but exact) and at best emulates, at worst refuses, float64.  The
storage formats were TPU-first from day one (DECIMAL = scaled int64,
DATE = int32, TEXT = int32 dictionary codes — catalog/types.py), so the
only f64 on the device path is FLOAT64 columns and float intermediates
(AVG, float division, percentiles).  Two modes:

- "x64" (default when the selected backend is CPU): float compute in
  f64 — bit-matches the pandas/numpy oracles.
- "tpu" (default when the selected backend is a TPU; force with
  OTB_DTYPE_MODE=tpu|x64): NO f64 array is ever created on the device
  path.  FLOAT64 columns stage to HBM as f32, float intermediates
  compute in f32, float<->int bit-pattern tricks (grouping/dedup keys)
  ride the 32-bit pair.  Integer/decimal arithmetic is identical in
  both modes (exact, int64), so TPC-H money aggregates match bit-for-
  bit; pure-float aggregates differ by ~1e-6 relative (f32 rounding).

tests/test_tpu_lowering.py holds the proof: every engine kernel
AOT-lowers for the 'tpu' platform via jax.export, and in tpu mode the
emitted StableHLO contains no f64 tensor type anywhere; a subprocess
suite re-runs engine queries under OTB_DTYPE_MODE=tpu and compares
against x64-mode results.

Reference analog: none — the reference runs on CPUs where double is
native (float8/numeric types, utils/adt).  This module is the price of
(and proof of) targeting a TPU instead.
"""

from __future__ import annotations

import os

import numpy as np

_mode: str | None = None

# Snapshot the env override ONCE at import: mode() is reachable from
# traced code (kernels -> device_float), and a mid-trace os.environ
# read would make compiled programs depend on ambient process state.
_ENV_MODE = os.environ.get("OTB_DTYPE_MODE", "").strip().lower()


# The memo write below runs at most once per process, on the Python
# side of the first trace — never per-execution of a compiled program.
def mode() -> str:  # otblint: disable=trace-purity
    """'x64' or 'tpu'.  Resolved once per process: OTB_DTYPE_MODE wins,
    else follows `jax.default_backend()` — whatever platform JAX picked
    from its environment."""
    global _mode
    if _mode is None:
        if _ENV_MODE in ("x64", "tpu"):
            _mode = _ENV_MODE
        else:
            import jax
            # a platform name (host string), not a traced value
            _mode = ("tpu" if jax.default_backend() == "tpu"  # otblint: disable=host-sync
                     else "x64")
    return _mode


def tpu_safe() -> bool:
    return mode() == "tpu"


def device_float():
    """jnp dtype for float compute on device."""
    import jax.numpy as jnp
    return jnp.float32 if tpu_safe() else jnp.float64


def dev_dtype(t) -> np.dtype:
    """Device array dtype for a SqlType (storage dtype, except FLOAT64
    -> f32 in tpu mode).  Use at every host->device staging boundary
    and wherever a device array is cast to a column's type."""
    dt = t.np_dtype
    if tpu_safe() and dt == np.dtype(np.float64):
        return np.dtype(np.float32)
    return dt


def stage_cast(arr: np.ndarray) -> np.ndarray:
    """Host array -> device-safe host array (cast f64 to f32 in tpu
    mode; everything else passes through)."""
    if tpu_safe() and arr.dtype == np.float64:
        return arr.astype(np.float32)
    return arr


def float_to_bits(arr):
    """Float array -> int64 bit-pattern key (injective; for grouping/
    dedup equality, not ordering).  In tpu mode the pattern rides i32
    sign-extended to i64 so no 64-bit float ever exists."""
    import jax
    import jax.numpy as jnp
    if tpu_safe():
        return jax.lax.bitcast_convert_type(
            arr.astype(jnp.float32), jnp.int32).astype(jnp.int64)
    return jax.lax.bitcast_convert_type(
        arr.astype(jnp.float64), jnp.int64)


def bits_to_float(arr):
    """Inverse of float_to_bits (int64 key back to the device float)."""
    import jax
    import jax.numpy as jnp
    if tpu_safe():
        return jax.lax.bitcast_convert_type(
            arr.astype(jnp.int32), jnp.float32)
    return jax.lax.bitcast_convert_type(arr, jnp.float64)
