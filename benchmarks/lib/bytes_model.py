"""Bytes a statement must read, from the loaded shapes and the widths the
program stores (copied idea: bench.py `_gb_touched`, with the real column
widths instead of 8 bytes everywhere).  A model is a function of the loaded
data; a roofline metric names one."""

# bytes per stored value, by TPC-H column type as the program holds it on
# the device: DECIMAL(15,2) as scaled int64, DATE as int32 days, text as
# int32 dictionary codes, integer as int32, bigint as int64
WIDTH = {"decimal": 8, "date": 4, "text": 4, "integer": 4, "bigint": 8}

Q1_COLUMNS = {"l_quantity": "decimal", "l_extendedprice": "decimal",
              "l_discount": "decimal", "l_tax": "decimal",
              "l_returnflag": "text", "l_linestatus": "text",
              "l_shipdate": "date"}


def q1_scan(data):
    """Q1 reads seven lineitem columns once, whole: 44 bytes a row."""
    rows = len(data["lineitem"]["l_orderkey"])
    return rows * sum(WIDTH[t] for t in Q1_COLUMNS.values())


MODELS = {"q1_scan": q1_scan}
