"""TPC-H Q1 (pricing summary), DELTA days before 1998-12-01."""

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    key = ("q1_frame", precision)
    if key not in shared:
        li = _tpch.frame(
            data["lineitem"], ("l_shipdate",),
            ("l_quantity", "l_extendedprice", "l_discount", "l_tax"),
            ("l_returnflag", "l_linestatus"), precision)
        h = _tpch.hundred(precision)
        li["dp"] = li.l_extendedprice * (h - li.l_discount)
        li["ch"] = li.dp * (h + li.l_tax)
        shared[key] = li
    li = shared[key]
    li = li[li.l_shipdate <= _tpch.days("1998-12-01") - int(params["delta"])]
    rows = []
    for (rf, ls), g in li.groupby(["l_returnflag", "l_linestatus"],
                                  sort=True):
        n = len(g)
        sq, sp, sdp, sch, sd = (_tpch.total(g[c], precision) for c in (
            "l_quantity", "l_extendedprice", "dp", "ch", "l_discount"))
        rows.append((rf, ls, sq / 100, sp / 100, sdp / 10**4, sch / 10**6,
                     sq / 100 / n, sp / 100 / n, sd / 100 / n, n))
    return rows
