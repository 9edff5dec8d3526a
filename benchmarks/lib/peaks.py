"""The table of peaks, keyed by the device_kind jax reports.  A device that
is not in the table is an error, never a default."""

from . import files


def peaks_for(device_kind, table=None):
    table = files.load_json("lib", "peaks.json") if table is None else table
    if device_kind not in table or device_kind.startswith("_"):
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"lib/peaks.json (has: "
                       f"{sorted(k for k in table if not k.startswith('_'))})")
    return table[device_kind]
