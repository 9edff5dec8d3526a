"""The benchmark's seeded TPC-H data: the same seed gives the same tables.

A copy of `opentenbase_tpu/tpch/datagen.py`'s laws (row counts per scale
factor, key sparsity, column widths and value domains, the cross-table
relations the 22 queries lean on) that builds every column as ONE numpy
array — text as fixed-width bytes — instead of row by row in Python, so
that SF1 takes seconds of a run's set-up and the program's loader takes its
vectorised dictionary path.  It is not dbgen and not bit-compatible with the
program's generator; the plain references read the very arrays made here, so
the comparison needs neither.  Dates are int days since 1970-01-01, decimals
are floats already rounded to cents.
"""

import numpy as np

REGIONS = ["AFRICA", "AMERICA", "ASIA", "EUROPE", "MIDDLE EAST"]
NATIONS = [
    ("ALGERIA", 0), ("ARGENTINA", 1), ("BRAZIL", 1), ("CANADA", 1),
    ("EGYPT", 4), ("ETHIOPIA", 0), ("FRANCE", 3), ("GERMANY", 3),
    ("INDIA", 2), ("INDONESIA", 2), ("IRAN", 4), ("IRAQ", 4),
    ("JAPAN", 2), ("JORDAN", 4), ("KENYA", 0), ("MOROCCO", 0),
    ("MOZAMBIQUE", 0), ("PERU", 1), ("CHINA", 2), ("ROMANIA", 3),
    ("SAUDI ARABIA", 4), ("VIETNAM", 2), ("RUSSIA", 3),
    ("UNITED KINGDOM", 3), ("UNITED STATES", 1),
]
SEGMENTS = ["AUTOMOBILE", "BUILDING", "FURNITURE", "MACHINERY", "HOUSEHOLD"]
PRIORITIES = ["1-URGENT", "2-HIGH", "3-MEDIUM", "4-NOT SPECIFIED", "5-LOW"]
SHIPMODES = ["REG AIR", "AIR", "RAIL", "SHIP", "TRUCK", "MAIL", "FOB"]
INSTRUCTS = ["DELIVER IN PERSON", "COLLECT COD", "NONE", "TAKE BACK RETURN"]
CONTAINERS = [f"{a} {b}" for a in ("SM", "LG", "MED", "JUMBO", "WRAP")
              for b in ("CASE", "BOX", "BAG", "JAR", "PKG", "PACK", "CAN",
                        "DRUM")]
TYPE_SYLL1 = ["STANDARD", "SMALL", "MEDIUM", "LARGE", "ECONOMY", "PROMO"]
TYPE_SYLL2 = ["ANODIZED", "BURNISHED", "PLATED", "POLISHED", "BRUSHED"]
TYPE_SYLL3 = ["TIN", "NICKEL", "BRASS", "STEEL", "COPPER"]
P_NAME_WORDS = ["almond", "antique", "aquamarine", "azure", "beige", "bisque",
                "black", "blanched", "blue", "blush", "brown", "burlywood",
                "burnished", "chartreuse", "chiffon", "chocolate", "coral",
                "cornflower", "cornsilk", "cream", "cyan", "dark", "deep",
                "dim", "dodger", "drab", "firebrick", "floral", "forest",
                "frosted", "gainsboro", "ghost", "goldenrod", "green", "grey",
                "honeydew", "hot", "hotpink", "indian", "ivory", "khaki"]
COMMENT_WORDS = ["carefully", "final", "deposits", "requests", "special",
                 "regular", "express", "furiously", "quickly", "silent",
                 "pending", "ironic", "even", "bold", "blithely", "accounts",
                 "packages", "theodolites", "Customer", "Complaints",
                 "unusual", "slyly", "asymptotes", "instructions"]

LOAD_ORDER = ("region", "nation", "supplier", "customer", "part",
              "partsupp", "orders", "lineitem")
DATE_COLS = {"orders": ["o_orderdate"],
             "lineitem": ["l_shipdate", "l_commitdate", "l_receiptdate"]}

_EPOCH = np.datetime64("1970-01-01", "D")


def days(iso):
    return int((np.datetime64(iso, "D") - _EPOCH).astype(np.int64))


def iso(day):
    return str(_EPOCH + np.timedelta64(int(day), "D"))


STARTDATE = days("1992-01-01")
ENDDATE = days("1998-08-02")


def _s(words):
    return np.asarray(words, dtype="S")


def _join(a, b, sep=b" "):
    return np.char.add(np.char.add(a, sep), b)


def _phrase_table(words, k):
    """All len(words)**k phrases of k words, in mixed-radix index order."""
    t = _s(words)
    out = t
    for _ in range(k - 1):
        out = _join(out[:, None], t[None, :]).reshape(-1)
    return out


class _Phrases:
    """n phrases of `nwords` words each drawn uniformly from COMMENT_WORDS,
    built from precomputed tables of 3- and 4-word phrases."""

    def __init__(self):
        self._tables = {}

    def table(self, k):
        if k not in self._tables:
            self._tables[k] = _phrase_table(COMMENT_WORDS, k)
        return self._tables[k]

    def draw(self, rng, n, nwords):
        base = len(COMMENT_WORDS)
        parts, left = [], nwords
        while left:
            k = 4 if left >= 4 and left != 5 else min(left, 3)
            parts.append(self.table(k)[rng.integers(0, base ** k, n)])
            left -= k
        out = parts[0]
        for p in parts[1:]:
            out = _join(out, p)
        return out


def _tight(a):
    """The same bytes at the narrowest fixed width."""
    return a.astype(f"S{max(int(np.char.str_len(a).max()), 1)}")


def _numbered(prefix, keys, width=9):
    return _tight(np.char.add(prefix.encode(),
                              np.char.zfill(keys.astype("S"), width)))


def _phones(rng, nation):
    n = len(nation)
    out = (nation + 11).astype("S")
    for lo, hi in ((100, 999), (100, 999), (1000, 9999)):
        out = _join(out, rng.integers(lo, hi, n).astype("S"), b"-")
    return _tight(out)


def generate(sf=1.0, seed=19980802):
    """{table: {column: np.ndarray}} for the eight TPC-H tables."""
    rng = np.random.default_rng(seed)
    ph = _Phrases()
    out = {}

    out["region"] = {
        "r_regionkey": np.arange(5, dtype=np.int64),
        "r_name": _s(REGIONS),
        "r_comment": ph.draw(rng, 5, 5),
    }
    out["nation"] = {
        "n_nationkey": np.arange(25, dtype=np.int64),
        "n_name": _s([n for n, _ in NATIONS]),
        "n_regionkey": np.asarray([r for _, r in NATIONS], dtype=np.int64),
        "n_comment": ph.draw(rng, 25, 5),
    }

    n_supp = max(int(10000 * sf), 20)
    sk = np.arange(1, n_supp + 1, dtype=np.int64)
    supp_nation = rng.integers(0, 25, n_supp).astype(np.int64)
    out["supplier"] = {
        "s_suppkey": sk,
        "s_name": _numbered("Supplier#", sk),
        "s_address": ph.draw(rng, n_supp, 3),
        "s_nationkey": supp_nation,
        "s_phone": _phones(rng, supp_nation),
        "s_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_supp), 2),
        "s_comment": ph.draw(rng, n_supp, 8),
    }

    n_cust = max(int(150000 * sf), 100)
    ck = np.arange(1, n_cust + 1, dtype=np.int64)
    cust_nation = rng.integers(0, 25, n_cust).astype(np.int64)
    out["customer"] = {
        "c_custkey": ck,
        "c_name": _numbered("Customer#", ck),
        "c_address": ph.draw(rng, n_cust, 3),
        "c_nationkey": cust_nation,
        "c_phone": _phones(rng, cust_nation),
        "c_acctbal": np.round(rng.uniform(-999.99, 9999.99, n_cust), 2),
        "c_mktsegment": _s(SEGMENTS)[rng.integers(0, 5, n_cust)],
        "c_comment": ph.draw(rng, n_cust, 8),
    }

    n_part = max(int(200000 * sf), 200)
    pk = np.arange(1, n_part + 1, dtype=np.int64)
    brand_m = rng.integers(1, 6, n_part)
    brand_n = rng.integers(1, 6, n_part)
    types = _s(TYPE_SYLL1)[:, None, None]
    types = _join(_join(types, _s(TYPE_SYLL2)[None, :, None]),
                  _s(TYPE_SYLL3)[None, None, :]).reshape(-1)
    name_words = _s(P_NAME_WORDS)[rng.integers(0, len(P_NAME_WORDS),
                                               (n_part, 5))]
    p_name = name_words[:, 0]
    for j in range(1, 5):
        p_name = _join(p_name, name_words[:, j])
    pprice = np.round(90000 + (pk % 200901) / 10 + 100 * (pk % 1000), 2) / 100
    out["part"] = {
        "p_partkey": pk,
        "p_name": p_name,
        "p_mfgr": _tight(np.char.add(b"Manufacturer#",
                                     brand_m.astype("S"))),
        "p_brand": _tight(np.char.add(
            b"Brand#", (brand_m * 10 + brand_n).astype("S"))),
        "p_type": types[rng.integers(0, len(types), n_part)],
        "p_size": rng.integers(1, 51, n_part).astype(np.int64),
        "p_container": _s(CONTAINERS)[rng.integers(0, len(CONTAINERS),
                                                   n_part)],
        "p_retailprice": pprice,
        "p_comment": ph.draw(rng, n_part, 3),
    }

    # partsupp: 4 suppliers per part
    ps_pk = np.repeat(pk, 4)
    n_ps = len(ps_pk)
    ps_sk = ((ps_pk + (np.tile(np.arange(4), n_part)
                       * (n_supp // 4 + 1))) % n_supp) + 1
    out["partsupp"] = {
        "ps_partkey": ps_pk,
        "ps_suppkey": ps_sk.astype(np.int64),
        "ps_availqty": rng.integers(1, 10000, n_ps).astype(np.int64),
        "ps_supplycost": np.round(rng.uniform(1.00, 1000.00, n_ps), 2),
        "ps_comment": ph.draw(rng, n_ps, 8),
    }

    n_ord = max(int(1500000 * sf), 1000)
    ok = np.arange(1, n_ord + 1, dtype=np.int64) * 4 - 3  # sparse keys
    # dbgen never gives an order to custkey % 3 == 0 (Q13/Q22 lean on it)
    o_ck = rng.integers(1, n_cust + 1, n_ord).astype(np.int64)
    o_ck = np.where(o_ck % 3 == 0, (o_ck % (n_cust - 1)) + 1, o_ck)
    o_ck = np.where(o_ck % 3 == 0, o_ck + 1, o_ck)
    o_date = rng.integers(STARTDATE, ENDDATE - 151, n_ord).astype(np.int64)
    o_prio = _s(PRIORITIES)[rng.integers(0, 5, n_ord)]
    clerks = _numbered("Clerk#", np.arange(0, 1001, dtype=np.int64))
    o_clerk = clerks[rng.integers(1, 1001, n_ord)]
    o_comment = ph.draw(rng, n_ord, 6)

    # lineitem: 1..7 per order
    nlines = rng.integers(1, 8, n_ord)
    starts = np.cumsum(nlines) - nlines
    l_ok = np.repeat(ok, nlines)
    l_odate = np.repeat(o_date, nlines)
    n_li = len(l_ok)
    l_pk = rng.integers(1, n_part + 1, n_li).astype(np.int64)
    # the supplier is one of the part's four partsupp rows
    pick = rng.integers(0, 4, n_li)
    l_sk = ((l_pk + pick * (n_supp // 4 + 1)) % n_supp) + 1
    qty = rng.integers(1, 51, n_li).astype(np.int64)
    eprice = np.round(qty * pprice[l_pk - 1], 2)
    disc = rng.integers(0, 11, n_li) / 100.0
    tax = rng.integers(0, 9, n_li) / 100.0
    shipdate = l_odate + rng.integers(1, 122, n_li)
    commitdate = l_odate + rng.integers(30, 91, n_li)
    receiptdate = shipdate + rng.integers(1, 31, n_li)
    cutoff = days("1995-06-17")
    returnflag = np.where(receiptdate <= cutoff,
                          _s(["R", "A"])[rng.integers(0, 2, n_li)], b"N")
    is_f = shipdate <= cutoff
    out["lineitem"] = {
        "l_orderkey": l_ok,
        "l_partkey": l_pk,
        "l_suppkey": l_sk.astype(np.int64),
        "l_linenumber": np.arange(n_li, dtype=np.int64)
        - np.repeat(starts, nlines) + 1,
        "l_quantity": qty.astype(np.float64),
        "l_extendedprice": eprice,
        "l_discount": disc,
        "l_tax": tax,
        "l_returnflag": returnflag,
        "l_linestatus": np.where(is_f, b"F", b"O"),
        "l_shipdate": shipdate.astype(np.int64),
        "l_commitdate": commitdate.astype(np.int64),
        "l_receiptdate": receiptdate.astype(np.int64),
        "l_shipinstruct": _s(INSTRUCTS)[rng.integers(0, 4, n_li)],
        "l_shipmode": _s(SHIPMODES)[rng.integers(0, 7, n_li)],
        "l_comment": ph.draw(rng, n_li, 4),
    }

    # orders' derived columns: the total in exact cents, the status from
    # the lines' statuses
    cents = np.rint(eprice * 100).astype(np.int64)
    n_f = np.add.reduceat(is_f.astype(np.int64), starts)
    out["orders"] = {
        "o_orderkey": ok,
        "o_custkey": o_ck,
        "o_orderstatus": np.where(n_f == nlines, b"F",
                                  np.where(n_f == 0, b"O", b"P")),
        "o_totalprice": np.add.reduceat(cents, starts) / 100.0,
        "o_orderdate": o_date,
        "o_orderpriority": o_prio,
        "o_clerk": o_clerk,
        "o_shippriority": np.zeros(n_ord, dtype=np.int64),
        "o_comment": o_comment,
    }
    return {t: out[t] for t in LOAD_ORDER}


def to_tbl_frame(table, date_cols):
    """Columns as COPY text: bytes decoded, dates as ISO strings."""
    cols = {}
    for c, v in table.items():
        if c in date_cols:
            cols[c] = (_EPOCH + v.astype("timedelta64[D]")).astype(str)
        elif v.dtype.kind == "S":
            cols[c] = v.astype(str)
        else:
            cols[c] = v
    return cols
