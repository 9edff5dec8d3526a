"""TPC-H Q21 (suppliers who kept orders waiting), NATION: for each supplier
of the nation, the lineitems it delivered late (received after the commit
date) in finished orders (status F) of several suppliers where it was the
ONLY supplier that was late; the first 100 by that count, then by name.

Written by sets, not by the query's EXISTS / NOT EXISTS: per order, the set
of its suppliers and the set of its late suppliers.  A late lineitem of
supplier s counts exactly when the order's suppliers are at least two and
its late suppliers are {s}.  (Two late lineitems of the same supplier in
one order both count: the query counts l1 rows.)

Q21 holds no decimal and no AVG, so float32 answers it exactly.  Its
control arm is the nearest FORMULATION below: the query without its NOT
EXISTS, every late lineitem of a multi-supplier order counted whoever else
was late, which is what an anti join that filters nothing would reply.  The
comparison has to refuse it."""

import numpy as np
import pandas as pd

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    if "q21_waiting" not in shared:
        li = _tpch.frame(data["lineitem"], ("l_orderkey", "l_suppkey",
                                            "l_commitdate", "l_receiptdate"))
        li["late"] = li.l_receiptdate > li.l_commitdate
        by_order = li.groupby("l_orderkey").l_suppkey
        li["suppliers"] = by_order.transform("nunique")
        late = li[li.late].copy()
        late["late_suppliers"] = late.groupby(
            "l_orderkey").l_suppkey.transform("nunique")
        o = _tpch.frame(data["orders"], ("o_orderkey",),
                        text_cols=("o_orderstatus",))
        finished = o.o_orderkey[o.o_orderstatus == "F"]
        late = late[late.l_orderkey.isin(finished) & (late.suppliers > 1)]
        shared["q21_waiting"] = late[["l_suppkey", "late_suppliers"]]
        s = _tpch.frame(data["supplier"], ("s_suppkey", "s_nationkey"),
                        text_cols=("s_name",))
        n = _tpch.frame(data["nation"], ("n_nationkey",),
                        text_cols=("n_name",))
        shared["q21_supplier"] = s.merge(
            n, left_on="s_nationkey", right_on="n_nationkey")
    late, s = shared["q21_waiting"], shared["q21_supplier"]
    if precision == "exact":
        late = late[late.late_suppliers == 1]
    s = s[s.n_name == params["nation"]]
    waits = late.l_suppkey[late.l_suppkey.isin(s.s_suppkey)].value_counts()
    out = pd.DataFrame({"s_suppkey": waits.index, "numwait": waits.values}) \
        .merge(s[["s_suppkey", "s_name"]], on="s_suppkey") \
        .sort_values(["numwait", "s_name"], ascending=[False, True]).head(100)
    return [(r.s_name, int(r.numwait)) for r in out.itertuples()]
