"""TPC-H Q13 (customer distribution), WORD1 and WORD2: how many customers
have placed how many orders, not counting the orders whose comment holds
WORD1 followed, anywhere later, by WORD2; a customer with no such order
counts under 0 (the outer join's null-extended row, which count(o_orderkey)
skips).

Q13 holds no decimal and no AVG: counts far below 2**24, which float32 (the
nearest precision below) holds exactly, so no limit could refuse that
arm.  Its control arm is therefore the nearest FORMULATION below: the outer
join answered as an inner join, so that the customers with no counted order
are gone and the `c_count = 0` row with them.  The comparison has to refuse
it."""

from collections import Counter

import numpy as np


def expected(data, params, shared, precision="exact"):
    w1, w2 = params["word1"].encode(), params["word2"].encode()
    key = ("q13_counts", w1, w2)
    if key not in shared:
        o = data["orders"]
        comment = np.asarray(o["o_comment"])
        first = np.char.find(comment, w1)
        # `%w1%w2%`: the earliest w1 leaves the most room for a w2 after it
        like = (first >= 0) & (np.char.find(
            comment, w2, np.where(first >= 0, first + len(w1), 0)) >= 0)
        custkey = np.asarray(data["customer"]["c_custkey"]).astype(np.int64)
        per_key = np.bincount(np.asarray(o["o_custkey"])[~like],
                              minlength=int(custkey.max()) + 1)
        shared[key] = per_key[custkey]
    counts = shared[key]
    if precision != "exact":
        counts = counts[counts > 0]
    dist = Counter(counts.tolist())
    return sorted(((int(c), int(n)) for c, n in dist.items()),
                  key=lambda r: (-r[1], -r[0]))
