"""Shared pieces of the plain TPC-H references: pandas frames over the
generated arrays.  DECIMAL(15,2) columns become integer cents and are summed
exactly, as the configuration states (decimal arithmetic is exact; AVG is
the exact sum divided once in float64).  `precision="float32"` is the
control: the same queries with every decimal held, multiplied and summed in
float32 — the nearest precision below — which the comparison must refuse."""

import numpy as np
import pandas as pd

from benchmarks.lib.datagen import days, iso  # noqa: F401  (re-exported)


def cents(col, precision):
    if precision == "float32":
        return np.asarray(col, dtype=np.float32) * np.float32(100)
    return np.rint(np.asarray(col, dtype=np.float64) * 100).astype(np.int64)


def hundred(precision):
    return np.float32(100) if precision == "float32" else 100


def total(series, precision):
    """A sum as the precision holds it: exact int64, or a float32 sum."""
    if precision == "float32":      # a running sum, as a scatter-add keeps it
        return float(np.cumsum(series.to_numpy(dtype=np.float32),
                               dtype=np.float32)[-1])
    return int(series.sum())


def text(col):
    a = np.asarray(col)
    return a.astype(str) if a.dtype.kind == "S" else a


def frame(table, int_cols=(), cent_cols=(), text_cols=(), precision="exact"):
    cols = {c: np.asarray(table[c]).astype(np.int64) for c in int_cols}
    cols.update({c: cents(table[c], precision) for c in cent_cols})
    cols.update({c: text(table[c]) for c in text_cols})
    return pd.DataFrame(cols)


def revenue_tables(data, shared, precision):
    """customer, orders and lineitem (rev = price * (100 - disc), in 1e-4
    units) — built once and shared by every statement that joins them."""
    key = ("revenue_tables", precision)
    if key not in shared:
        c = frame(data["customer"], ("c_custkey", "c_nationkey"),
                  text_cols=("c_mktsegment",))
        o = frame(data["orders"], ("o_orderkey", "o_custkey", "o_orderdate",
                                   "o_shippriority"))
        li = frame(data["lineitem"],
                   ("l_orderkey", "l_suppkey", "l_shipdate"),
                   ("l_extendedprice", "l_discount"), precision=precision)
        li["rev"] = li.l_extendedprice * (hundred(precision) - li.l_discount)
        shared[key] = (c, o, li)
    return shared[key]
