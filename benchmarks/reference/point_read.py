"""A point SELECT on orders by key, answered from the generated arrays."""

import numpy as np

from benchmarks.reference import _tpch


def expected(data, params, shared, precision="exact"):
    o = data["orders"]
    i = int(np.searchsorted(o["o_orderkey"], params["key"]))
    if i >= len(o["o_orderkey"]) or o["o_orderkey"][i] != params["key"]:
        return []
    price = o["o_totalprice"][i]
    if precision == "float32":
        price = float(np.float32(price))
    else:
        price = int(np.rint(price * 100)) / 100
    return [(int(o["o_orderkey"][i]), int(o["o_custkey"][i]), price,
             _tpch.iso(o["o_orderdate"][i]))]
