"""TPC-H Q4 (order priority checking), DATE: the first day of a quarter
that starts on any month.  The orders of [DATE, DATE + 3 months) that have
at least one lineitem received after its commit date, counted by priority.

Q4 holds no decimal and no AVG: counts, dates and keys are whole numbers far
below 2**24, so float32 (the nearest precision below) answers it exactly
and no limit could refuse that.  Its control arm is therefore the nearest
FORMULATION below: EXISTS answered as an inner join, every order counted
once per late lineitem instead of once, which is what a semi join answered
by expansion without a mask would reply.  The comparison has to refuse it."""

import numpy as np

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    if "q4_orders" not in shared:
        li = data["lineitem"]
        late = np.asarray(li["l_commitdate"]) < np.asarray(li["l_receiptdate"])
        # late lineitems per order, by the orders table's rows
        keys = np.asarray(data["orders"]["o_orderkey"]).astype(np.int64)
        at = np.searchsorted(keys, np.asarray(li["l_orderkey"])[late])
        o = _tpch.frame(data["orders"], ("o_orderdate",),
                        text_cols=("o_orderpriority",))
        o["late_lines"] = np.bincount(at, minlength=len(keys))
        shared["q4_orders"] = o
    o = shared["q4_orders"]
    lo = np.datetime64(params["date"], "M")
    lo, hi = (int(d.astype("datetime64[D]").astype(np.int64))
              for d in (lo, lo + 3))
    o = o[(o.o_orderdate >= lo) & (o.o_orderdate < hi) & (o.late_lines > 0)]
    per_order = o.late_lines if precision != "exact" else o.late_lines.clip(
        upper=1)
    g = per_order.groupby(o.o_orderpriority).sum().sort_index()
    return [(prio, int(n)) for prio, n in g.items()]
