"""TPC-H Q5 (local supplier volume), REGION and the year from DATE."""

import numpy as np

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    c, o, li = _tpch.revenue_tables(data, shared, precision)
    if "q5_dims" not in shared:
        shared["q5_dims"] = (
            _tpch.frame(data["supplier"], ("s_suppkey", "s_nationkey")),
            _tpch.frame(data["nation"], ("n_nationkey", "n_regionkey"),
                        text_cols=("n_name",)),
            _tpch.frame(data["region"], ("r_regionkey",),
                        text_cols=("r_name",)))
    s, n, r = shared["q5_dims"]
    lo = _tpch.days(params["date"])
    hi = int((np.datetime64(params["date"], "Y") + 1).astype("datetime64[D]")
             .astype(np.int64))
    o = o[(o.o_orderdate >= lo) & (o.o_orderdate < hi)]
    df = c.merge(o, left_on="c_custkey", right_on="o_custkey")
    df = df.merge(li, left_on="o_orderkey", right_on="l_orderkey")
    df = df.merge(s, left_on="l_suppkey", right_on="s_suppkey")
    df = df[df.c_nationkey == df.s_nationkey]
    df = df.merge(n, left_on="s_nationkey", right_on="n_nationkey")
    df = df.merge(r[r.r_name == params["region"]], left_on="n_regionkey",
                  right_on="r_regionkey")
    g = df.groupby("n_name")["rev"].sum().reset_index().sort_values(
        "rev", ascending=False)
    num = float if precision == "float32" else int
    return [(r_.n_name, num(r_.rev) / 10**4) for r_ in g.itertuples()]
