"""TPC-H Q18 (large volume customer), QUANTITY: the orders whose lineitems'
quantities sum to more than QUANTITY, with their customer, the first 100 by
`o_totalprice desc, o_orderdate`.

The SQL leaves the order of rows equal in both sort keys open; this
reference puts them by `o_orderkey` (the program's order among such rows is
its own: two of a reply's at most 100 orders sharing a total to the cent
AND a day has not been seen at any seed; the statement's file says so).  In
float32 (the control) an order's quantities still sum exactly (at most
seven lines of at most 50.00), its total price does not: cents past 2**24
have no float32."""

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    key = ("q18_orders", precision)
    if key not in shared:
        li = _tpch.frame(data["lineitem"], ("l_orderkey",), ("l_quantity",),
                         precision=precision)
        o = _tpch.frame(data["orders"],
                        ("o_orderkey", "o_custkey", "o_orderdate"),
                        ("o_totalprice",), precision=precision)
        qty = li.groupby("l_orderkey").l_quantity.sum()
        shared[key] = o.merge(qty.rename("qty").reset_index(),
                              left_on="o_orderkey", right_on="l_orderkey")
        shared["q18_customer"] = _tpch.frame(
            data["customer"], ("c_custkey",), text_cols=("c_name",))
    o = shared[key]
    o = o[o.qty > int(params["quantity"]) * _tpch.hundred(precision)].merge(
        shared["q18_customer"], left_on="o_custkey", right_on="c_custkey")
    o = o.sort_values(["o_totalprice", "o_orderdate", "o_orderkey"],
                      ascending=[False, True, True]).head(100)
    num = float if precision == "float32" else int
    return [(r.c_name, int(r.c_custkey), int(r.o_orderkey),
             _tpch.iso(r.o_orderdate), num(r.o_totalprice) / 100,
             num(r.qty) / 100) for r in o.itertuples()]
