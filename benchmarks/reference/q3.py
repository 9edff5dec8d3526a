"""TPC-H Q3 (shipping priority), SEGMENT and DATE."""

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    c, o, li = _tpch.revenue_tables(data, shared, precision)
    day = _tpch.days(params["date"])
    df = c[c.c_mktsegment == params["segment"]].merge(
        o[o.o_orderdate < day], left_on="c_custkey", right_on="o_custkey")
    df = df.merge(li[li.l_shipdate > day],
                  left_on="o_orderkey", right_on="l_orderkey")
    g = df.groupby(["l_orderkey", "o_orderdate", "o_shippriority"])[
        "rev"].sum().reset_index().sort_values(
        ["rev", "o_orderdate"], ascending=[False, True]).head(10)
    num = float if precision == "float32" else int
    return [(int(r.l_orderkey), num(r.rev) / 10**4, _tpch.iso(r.o_orderdate),
             int(r.o_shippriority)) for r in g.itertuples()]
