"""Native bulk loader binding — C++ parse loop via ctypes, pandas fallback.

Reference analog: commands/copy.c's C attribute parser.  The native library
is built on demand with g++ from native/loader.cpp into the git-ignored
native/build/ (no pip/pybind — plain ctypes over a C ABI); a failed build
falls back to the pandas C engine, which callers can see through
native_available() and the SERVED counters.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import Optional

import numpy as np

from ..catalog.schema import TableDef
from ..catalog.types import TypeKind
from ..utils import locks

_lock = locks.Lock("storage.loader._lock")
_lib = None
_tried = False

_SRC = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "native", "loader.cpp")
_SO = os.path.join(os.path.dirname(_SRC), "build", "libotbloader.so")

# which parser served each load_tbl call (process-wide)
SERVED = {"native": 0, "escaped": 0, "pandas": 0}  # guarded_by: _lock

_KIND = {TypeKind.INT32: 0, TypeKind.INT64: 0, TypeKind.FLOAT64: 1,
         TypeKind.DECIMAL: 2, TypeKind.DATE: 3, TypeKind.TEXT: 4,
         TypeKind.BOOL: 5}



# holding the lock across the (timeout-bounded, once-ever) g++ build is
# the point: concurrent first-callers must not race duplicate compiles
def _get_lib():  # otblint: disable=lock-blocking
    global _lib, _tried
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            if (not os.path.exists(_SO)
                    or os.path.getmtime(_SO) < os.path.getmtime(_SRC)):
                os.makedirs(os.path.dirname(_SO), exist_ok=True)
                # build beside the target, then rename: concurrent
                # processes (test workers) never load a half-written file
                tmp = f"{_SO}.{os.getpid()}.tmp"
                subprocess.run(
                    ["g++", "-O3", "-shared", "-fPIC", _SRC, "-o", tmp],
                    check=True, capture_output=True, timeout=120)
                os.replace(tmp, _SO)
            lib = ctypes.CDLL(_SO)
            lib.otb_count_rows.restype = ctypes.c_longlong
            lib.otb_count_rows.argtypes = [ctypes.c_char_p]
            lib.otb_parse.restype = ctypes.c_longlong
            lib.otb_parse.argtypes = [
                ctypes.c_char_p, ctypes.c_char, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_int),
                ctypes.POINTER(ctypes.c_void_p), ctypes.c_longlong]
            _lib = lib
        except (OSError, subprocess.SubprocessError):
            _lib = None    # no compiler / failed build: pandas serves
        return _lib


def native_available() -> bool:
    return _get_lib() is not None


def load_tbl(path: str, td: TableDef, columns: list[str],
             delimiter: str = "|") -> dict:
    """Parse a delimited file into raw column values keyed by column name
    (TEXT as numpy bytes arrays, DECIMAL as scaled storage ints, DATE as
    day numbers).  Uses the native parser when possible; transparently
    falls back to pandas otherwise (vectors, unbounded text, over-length
    values, missing compiler)."""
    out = _load_native(path, td, columns, delimiter)
    served = "native"
    if out is None:
        # the native parser refuses backslashes (\N NULLs / escapes of
        # the COPY text format) along with its other unsupported inputs;
        # files carrying them take the escape-aware python path
        if _file_has_backslash(path):
            out = _load_text_escaped(path, td, columns, delimiter)
            served = "escaped"
        else:
            out = _load_pandas(path, td, columns, delimiter)
            served = "pandas"
    with _lock:
        SERVED[served] += 1
    return out


def _file_has_backslash(path: str) -> bool:
    with open(path, "rb") as f:
        while True:
            chunk = f.read(1 << 20)
            if not chunk:
                return False
            if b"\\" in chunk:
                return True


def _load_text_escaped(path: str, td: TableDef, columns: list[str],
                       delimiter: str) -> dict:
    """COPY text-format reader: honors backslash escapes and the \\N
    NULL marker (commands/copy.c CopyReadAttributesText analog; the
    slow path — only files containing backslashes come here)."""
    cols: dict[str, list] = {c: [] for c in columns}
    with open(path, "r") as f:
        for line in f:
            line = line.rstrip("\n")
            if not line:
                continue
            # split on UNESCAPED delimiters, keeping raw field text
            raw_fields, cur, esc = [], [], False
            for ch in line:
                if esc:
                    cur.append("\\" + ch)
                    esc = False
                elif ch == "\\":
                    esc = True
                elif ch == delimiter:
                    raw_fields.append("".join(cur))
                    cur = []
                else:
                    cur.append(ch)
            raw_fields.append("".join(cur))
            for c, raw in zip(columns, raw_fields):
                if raw == "\\N":
                    cols[c].append(None)
                    continue
                # unescape: \\ -> \, \n -> newline, \<d> -> d
                out, esc = [], False
                for ch in raw:
                    if esc:
                        out.append("\n" if ch == "n" else ch)
                        esc = False
                    elif ch == "\\":
                        esc = True
                    else:
                        out.append(ch)
                s = "".join(out)
                k = td.column(c).type.kind
                if k in (TypeKind.INT32, TypeKind.INT64):
                    cols[c].append(int(s))
                elif k == TypeKind.FLOAT64:
                    cols[c].append(float(s))
                elif k == TypeKind.BOOL:
                    cols[c].append(s.strip().lower() in
                                   ("t", "true", "1"))
                else:
                    cols[c].append(s)   # decimal/date/text: raw string
    return cols


def _load_pandas(path: str, td: TableDef, columns: list[str],
                 delimiter: str) -> dict:
    import pandas as pd
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    df = pd.read_csv(path, sep=delimiter, header=None,
                     names=columns + ["__trail"], index_col=False,
                     engine="c", na_values=["\\N"],
                     keep_default_na=False)
    if df["__trail"].isna().all():
        df = df.drop(columns="__trail")
    out = {}
    for c in columns:
        s = df[c]
        if s.isna().any():
            out[c] = [None if pd.isna(v) else v for v in s.tolist()]
        else:
            out[c] = s.tolist()
    return out


def _load_native(path: str, td: TableDef, columns: list[str],
                 delimiter: str = "|") -> Optional[dict]:
    lib = _get_lib()
    if lib is None:
        return None
    for c in columns:
        t = td.column(c).type
        if t.kind == TypeKind.VECTOR:
            return None   # vectors go through the python path
        if t.kind == TypeKind.TEXT and t.max_len <= 0:
            return None   # unbounded text: no fixed-width buffer
    if not os.path.exists(path):
        raise FileNotFoundError(path)
    n = lib.otb_count_rows(path.encode())
    if n < 0:
        raise FileNotFoundError(path)
    ncols = len(columns)
    kinds = (ctypes.c_int * ncols)()
    scales = (ctypes.c_int * ncols)()
    outs = (ctypes.c_void_p * ncols)()
    bufs = {}
    for i, cname in enumerate(columns):
        t = td.column(cname).type
        kinds[i] = _KIND[t.kind]
        if t.kind == TypeKind.DECIMAL:
            scales[i] = t.scale
            buf = np.empty(n, dtype=np.int64)
        elif t.kind == TypeKind.TEXT:
            width = t.max_len
            scales[i] = width
            buf = np.zeros(n * width, dtype=np.uint8)
        elif t.kind == TypeKind.DATE:
            scales[i] = 0
            buf = np.empty(n, dtype=np.int32)
        elif t.kind == TypeKind.FLOAT64:
            scales[i] = 0
            buf = np.empty(n, dtype=np.float64)
        else:
            scales[i] = 0
            buf = np.empty(n, dtype=np.int64)
        bufs[cname] = buf
        outs[i] = buf.ctypes.data_as(ctypes.c_void_p)
    got = lib.otb_parse(path.encode(), delimiter.encode()[0:1][0] if
                        isinstance(delimiter, str) else delimiter,
                        ncols, kinds, scales, outs, n)
    if got < 0:
        # over-length text / malformed line: let the general path decide
        return None
    out = {}
    for i, cname in enumerate(columns):
        t = td.column(cname).type
        buf = bufs[cname]
        if t.kind == TypeKind.TEXT:
            width = t.max_len
            # keep as a numpy bytes array: the dictionary encoder uniques
            # it at C speed (per-string python decode would dominate)
            out[cname] = buf[:got * width].view(f"S{width}")
        elif t.kind == TypeKind.INT32:
            out[cname] = buf[:got].astype(np.int32)
        elif t.kind == TypeKind.BOOL:
            out[cname] = buf[:got].astype(np.bool_)
        else:
            out[cname] = buf[:got]
        if t.kind == TypeKind.DECIMAL:
            # already in scaled storage form: mark so encode skips rescale
            out[cname] = _PreScaled(out[cname])
    return out


class _PreScaled(np.ndarray):
    """Marker: decimal values already scaled to storage form."""
    def __new__(cls, arr):
        return np.asarray(arr).view(cls)
