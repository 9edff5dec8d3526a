"""TPC-H Q17 (small-quantity-order revenue), BRAND and CONTAINER: the
yearly revenue lost if the lineitems of those parts whose quantity is under
a fifth of the part's average quantity were no longer taken.

`l_quantity < 0.2 * avg(l_quantity)` is decided in integers, as PostgreSQL's
numeric decides it: with the part's quantities summed in cents over its
`count` lineitems, `5 * qty * count < sum`.  A tie (`5 * qty * count ==
sum`) is "not less", whatever a float's 0.2 * (sum / count) would round
to.  The reply, `sum(l_extendedprice) / 7.0`, is ONE float64 division of
the exact sum in cents (NULL over no row), compared as a `float_cols`
column.  In float32 (the control) the comparison is made as a float program
makes it, in the column's units: quantity / 100 < 0.2 * (sum / count / 100),
all float32, and the prices are a float32 running sum.  On whole-number
quantities (the generator's) no rounding moves that comparison; where
quantities have cents, some ties come out "less"."""

import numpy as np

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    key = ("q17_lineitem", precision)
    if key not in shared:
        li = _tpch.frame(data["lineitem"], ("l_partkey",),
                         ("l_quantity", "l_extendedprice"),
                         precision=precision)
        by_part = li.groupby("l_partkey").l_quantity
        if precision == "float32":
            unit = _tpch.hundred(precision)
            avg = by_part.sum().astype(np.float32) \
                / by_part.count().astype(np.float32) / unit
            li["small"] = li.l_quantity / unit < np.float32(0.2) \
                * li.l_partkey.map(avg).to_numpy(dtype=np.float32)
        else:
            qsum, n = by_part.transform("sum"), by_part.transform("count")
            li["small"] = 5 * li.l_quantity * n < qsum
        shared[key] = li[li.small]
    li = shared[key]
    if "q17_part" not in shared:
        shared["q17_part"] = _tpch.frame(
            data["part"], ("p_partkey",),
            text_cols=("p_brand", "p_container"))
    p = shared["q17_part"]
    keys = p.p_partkey[(p.p_brand == params["brand"])
                       & (p.p_container == params["container"])]
    price = li.l_extendedprice[li.l_partkey.isin(keys)]
    if not len(price):
        return [(None,)]
    return [(_tpch.total(price, precision) / 100 / 7.0,)]
