"""The comparison that decides `correct`: a reply against the plain
reference's rows.  Text, dates, counts and row order compare exactly.  A
DECIMAL arrives on the wire as a float64, and a sum past 2**53 units (Q1's
sum_charge at SF1 is ~1e17 millionths) cannot arrive exactly: such a column
is compared in units in the last place of the reference's float64, and the
columns a statement lists as `float_cols` (AVG) by relative gap."""

import math


def rows_gap(got, want, float_cols=()):
    """(mismatch, avg_gap, ulp_gap): `mismatch` is None or a description of
    the first exact difference; `avg_gap` the widest relative gap over the
    float columns; `ulp_gap` the widest gap, in ulps of the reference, over
    the other columns that hold floats (0.0 where there are none)."""
    if len(got) != len(want):
        return f"{len(got)} rows, reference has {len(want)}", 0.0, 0.0
    avg_gap, ulp_gap, first = 0.0, 0.0, None
    for i, (g, w) in enumerate(zip(got, want)):
        if len(g) != len(w):
            return f"row {i}: arity {len(g)} vs {len(w)}", avg_gap, ulp_gap
        for j, (a, b) in enumerate(zip(g, w)):
            if j in float_cols and a is not None and b is not None:
                avg_gap = max(avg_gap,
                              abs(a - b) / max(abs(a), abs(b), 1e-300))
            elif isinstance(a, float) and isinstance(b, float):
                ulp_gap = max(ulp_gap, abs(a - b) / math.ulp(b))
            elif a != b and first is None:
                first = f"row {i} col {j}: {a!r} vs reference {b!r}"
    return first, avg_gap, ulp_gap
