"""TPC-H Q22 (global sales opportunity), I1..I7: by country code (the first
two characters of c_phone), the customers of those seven codes who have
placed no order and whose balance is above the average POSITIVE balance of
the seven codes' customers: how many, and their balances' sum.

`c_acctbal > avg(c_acctbal)` is decided in integers, as PostgreSQL's
numeric decides it: with the positive balances summed in cents over their
`count` customers, `cents * count > sum`; a balance exactly on the average
is "not above".  The sum of balances is exact cents, divided by 100 once.
In float32 (the control) the average is a float32 running sum over a
float32 count, compared with float32 balances, and the balances' sum a
float32 running sum: cents past 2**24 have no float32."""

import numpy as np

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    key = ("q22_customer", precision)
    if key not in shared:
        c = _tpch.frame(data["customer"], ("c_custkey",), ("c_acctbal",),
                        text_cols=("c_phone",), precision=precision)
        c["code"] = c.c_phone.str[:2]
        c["no_order"] = ~c.c_custkey.isin(
            np.unique(np.asarray(data["orders"]["o_custkey"])))
        shared[key] = c
    c = shared[key]
    c = c[c.code.isin([str(params[f"i{j}"]) for j in range(1, 8)])]
    positive = c.c_acctbal[c.c_acctbal > 0]
    if not len(positive):
        return []
    if precision == "float32":
        avg = np.float32(_tpch.total(positive, precision)) \
            / np.float32(len(positive))
        above = c.c_acctbal > avg
    else:
        above = c.c_acctbal * len(positive) > int(positive.sum())
    c = c[above & c.no_order]
    num = float if precision == "float32" else int
    return [(code, int(len(g)), num(_tpch.total(g.c_acctbal, precision))
             / 100) for code, g in sorted(c.groupby("code"))]
