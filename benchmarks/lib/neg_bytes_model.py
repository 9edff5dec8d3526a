"""Bytes the kernels of the `tpch_sf1_neg` cell must move, from the loaded
shapes and the widths the program holds (lib/bytes_model.py's idea, for
kernels that file does not know).  A model is a function of the loaded
data; a roofline metric names one."""

# per probe row of `ops/kernels.range_differs`: its match range (lo and
# count, int32 each), its own minor (l_suppkey as the program stages it,
# int32) and its validity (1) come in, the range's first and last minor
# are gathered (int32 each) and one verdict (1) goes out
RANGE_DIFFERS_ROW = 4 + 4 + 4 + 1 + 4 + 4 + 1


def q21_range_differs(data):
    """Q21 answers EXISTS and NOT EXISTS over lineitem by that kernel, each
    over every row of l1: two calls a reply."""
    return 2 * len(data["lineitem"]["l_orderkey"]) * RANGE_DIFFERS_ROW


MODELS = {"q21_range_differs": q21_range_differs}
