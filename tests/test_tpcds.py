"""TPC-DS: all 99 queries vs pandas oracles — single node and 4-DN
cluster (BASELINE config 5 path; reference: the TPC-DS templates
through OpenTenBase's PG grammar).  The strict mesh assertion at the
bottom proves the device data plane carries the distributed runs with
zero SILENT fallbacks."""

import itertools
import os

import numpy as np
import pandas as pd
import pytest

from opentenbase_tpu.exec.dist_session import ClusterSession
from opentenbase_tpu.exec.session import LocalNode, Session
from opentenbase_tpu.parallel.cluster import Cluster
from opentenbase_tpu.tpcds import datagen
from opentenbase_tpu.tpcds.queries import Q
from opentenbase_tpu.tpcds.schema import SCHEMA

SF = float(os.environ.get("OTB_TPCDS_SF", "0.3"))


@pytest.fixture(scope="module")
def data():
    return datagen.generate(sf=SF)


@pytest.fixture(scope="module")
def frames(data):
    return {name: pd.DataFrame(dict(cols))
            for name, cols in data.items()}


@pytest.fixture(scope="module")
def sess(data):
    s = Session(LocalNode())
    s.execute(SCHEMA)
    for tname, cols in data.items():
        td = s.node.catalog.table(tname)
        st = s.node.stores[tname]
        s._insert_rows(td, st, cols,
                       len(next(iter(cols.values()))))
    return s


@pytest.fixture(scope="module")
def cs(data):
    s = ClusterSession(Cluster(n_datanodes=4))
    s.execute(SCHEMA)
    for tname, cols in data.items():
        td = s.cluster.catalog.table(tname)
        s._insert_rows(td, cols, len(next(iter(cols.values()))))
    return s


_tests_run = itertools.count(1)


@pytest.fixture(autouse=True)
def _bound_live_executables():
    """Every live XLA:CPU executable holds a few hundred memory mappings,
    and this suite's programs, with the eager kernels' jit caches that no
    budget of exec/plancache.py covers, reach the kernel's limit of 65,530
    mappings a process about three quarters of the way through: the next
    mmap fails and the worker dies with a segmentation fault in whichever
    test crosses the line (q4, q11, q21, q23, q24, q58, q82, q83 by turns,
    as the kernels grew).  Dropping the compiled programs every 15 tests
    keeps the process under it; recompiles cost seconds and only here."""
    yield
    if next(_tests_run) % 15 == 0:
        import jax
        from opentenbase_tpu.exec import plancache
        jax.clear_caches()
        for cache in list(plancache._REGISTRY):
            cache.clear()


def rows_equal(got, want, tol=1e-6):
    assert len(got) == len(want), f"{len(got)} rows != {len(want)}"
    for g, w in zip(got, want):
        assert len(g) == len(w)
        for a, b in zip(g, w):
            if isinstance(a, float) or isinstance(b, float):
                assert a == pytest.approx(b, rel=tol), (g, w)
            else:
                assert a == b, (g, w)


def _r2(x):
    return float(np.round(x, 10))


class TestTpcdsStarter:
    def _q3(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.i_manager_id <= 20) & (m.d_moy == 11)]
        g = (m.groupby(["d_year", "i_brand_id", "i_brand"],
                       as_index=False)
             .agg(sum_agg=("ss_ext_sales_price", "sum")))
        g = g.sort_values(["d_year", "sum_agg", "i_brand_id"],
                          ascending=[True, False, True]).head(100)
        return [(int(r.d_year), int(r.i_brand_id), r.i_brand,
                 _r2(r.sum_agg)) for r in g.itertuples()]

    def test_q3(self, sess, frames):
        rows_equal(sess.query(Q[3]), self._q3(frames))

    def test_q3_distributed(self, cs, frames):
        rows_equal(cs.query(Q[3]), self._q3(frames))

    def _q42(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.d_moy == 12) & (m.d_year == 1999)]
        g = (m.groupby(["d_year", "i_category_id", "i_category"],
                       as_index=False)
             .agg(rev=("ss_ext_sales_price", "sum")))
        g = g.sort_values(["rev", "d_year", "i_category_id",
                           "i_category"],
                          ascending=[False, True, True, True]).head(100)
        return [(int(r.d_year), int(r.i_category_id), r.i_category,
                 _r2(r.rev)) for r in g.itertuples()]

    def test_q42(self, sess, frames):
        rows_equal(sess.query(Q[42]), self._q42(frames))

    def _q52(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.d_moy == 12) & (m.d_year == 1999)]
        g = (m.groupby(["d_year", "i_brand_id", "i_brand"],
                       as_index=False)
             .agg(p=("ss_ext_sales_price", "sum")))
        g = g.sort_values(["d_year", "p", "i_brand_id"],
                          ascending=[True, False, True]).head(100)
        return [(int(r.d_year), int(r.i_brand_id), r.i_brand, _r2(r.p))
                for r in g.itertuples()]

    def test_q52(self, sess, frames):
        rows_equal(sess.query(Q[52]), self._q52(frames))

    def _q55(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.i_manager_id <= 10) & (m.d_moy == 11)
              & (m.d_year == 2000)]
        g = (m.groupby(["i_brand_id", "i_brand"], as_index=False)
             .agg(p=("ss_ext_sales_price", "sum")))
        g = g.sort_values(["p", "i_brand_id"],
                          ascending=[False, True]).head(100)
        return [(int(r.i_brand_id), r.i_brand, _r2(r.p))
                for r in g.itertuples()]

    def test_q55(self, sess, frames):
        rows_equal(sess.query(Q[55]), self._q55(frames))

    def test_q55_distributed(self, cs, frames):
        rows_equal(cs.query(Q[55]), self._q55(frames))

    def _q67(self, f):
        m = f["store_sales"].merge(
            f["item"], left_on="ss_item_sk", right_on="i_item_sk")
        g = (m.groupby(["i_category", "i_brand"], as_index=False)
             .agg(rev=("ss_ext_sales_price", "sum")))
        g["rk"] = g.groupby("i_category")["rev"].rank(
            method="min", ascending=False).astype(int)
        g = g[g.rk <= 3].sort_values(["i_category", "rk", "i_brand"])
        return [(r.i_category, r.i_brand, _r2(r.rev), int(r.rk))
                for r in g.itertuples()]

    def test_q67_window_rank(self, sess, frames):
        rows_equal(sess.query(Q[67]), self._q67(frames))

    def test_q67_distributed(self, cs, frames):
        rows_equal(cs.query(Q[67]), self._q67(frames))

    def _q12(self, f):
        m = f["web_sales"].merge(
            f["item"], left_on="ws_item_sk", right_on="i_item_sk")
        m = m[m.i_category.isin(["Books", "Music"])]
        g = (m.groupby(["i_category", "i_class"], as_index=False)
             .agg(rev=("ws_ext_sales_price", "sum")))
        g["ratio"] = g.rev * 100.0 / g.groupby("i_category")[
            "rev"].transform("sum")
        g = g.sort_values(["i_category", "ratio"])
        return [(r.i_category, r.i_class, _r2(r.rev), r.ratio)
                for r in g.itertuples()]

    def test_q12_revenue_ratio(self, sess, frames):
        rows_equal(sess.query(Q[12]), self._q12(frames))

    def _q51(self, f):
        wi = f["web_sales"].merge(
            f["item"], left_on="ws_item_sk", right_on="i_item_sk")
        wi = wi[wi.i_class == "c1"].groupby("ws_sold_date_sk")[
            "ws_ext_sales_price"].sum()
        si = f["store_sales"].merge(
            f["item"], left_on="ss_item_sk", right_on="i_item_sk")
        si = si[si.i_class == "c1"].groupby("ss_sold_date_sk")[
            "ss_ext_sales_price"].sum()
        merged = pd.merge(wi.rename("web"), si.rename("store"),
                          how="outer", left_index=True,
                          right_index=True).sort_index().head(200)
        out = []
        for dsk, r in merged.iterrows():
            out.append((int(dsk),
                        None if pd.isna(r.web) else _r2(r.web),
                        None if pd.isna(r.store) else _r2(r.store)))
        return out

    def test_q51_full_join_ctes(self, sess, frames):
        rows_equal(sess.query(Q[51]), self._q51(frames))

    def _chans(self, f):
        s = set(f["store_sales"].ss_customer_sk)
        c = set(f["catalog_sales"].cs_bill_customer_sk)
        w = set(f["web_sales"].ws_bill_customer_sk)
        return s, c, w

    def test_q38_intersect(self, sess, frames):
        s, c, w = self._chans(frames)
        assert sess.query(Q[38]) == [(len(s & c & w),)]

    def test_q38_distributed(self, cs, frames):
        s, c, w = self._chans(frames)
        assert cs.query(Q[38]) == [(len(s & c & w),)]

    def test_q87_except(self, sess, frames):
        s, c, w = self._chans(frames)
        assert sess.query(Q[87]) == [(len(s - c - w),)]

    def _q54(self, f):
        fb = f["store_sales"].groupby("ss_customer_sk")[
            "ss_sold_date_sk"].min().rename("first_dsk").reset_index()
        m = (f["store_sales"]
             .merge(fb, on="ss_customer_sk")
             .merge(f["date_dim"], left_on="first_dsk",
                    right_on="d_date_sk"))
        m = m[m.d_year == 1999]
        return [(len(m), _r2(m.ss_ext_sales_price.sum()))]

    def test_q54_cte_agg_join(self, sess, frames):
        rows_equal(sess.query(Q[54]), self._q54(frames))


def _rank_min(vals, desc=False):
    """SQL rank() (ties share the min rank) over a list of values."""
    order = sorted(vals, reverse=desc)
    return [order.index(v) + 1 for v in vals]


def _nl(v):
    """Sort key: NULLS LAST."""
    return (v is None, v)


class TestTpcdsExpansion:
    """Round-3 query set: returns, demographics, addresses, inventory,
    promotions, correlated-scalar rewrites, ROLLUP+grouping()+rank."""

    # -- Q1: returners above 1.2x their store's average ----------------
    def _q1(self, f):
        sr = f["store_returns"].merge(
            f["date_dim"], left_on="sr_returned_date_sk",
            right_on="d_date_sk")
        sr = sr[sr.d_year == 1999]
        ctr = sr.groupby(["sr_customer_sk", "sr_store_sk"],
                         as_index=False).agg(tot=("sr_return_amt", "sum"))
        avg = ctr.groupby("sr_store_sk")["tot"].transform("mean")
        sel = ctr[ctr.tot > 1.2 * avg]
        return [(int(c),) for c in sorted(sel.sr_customer_sk)[:100]]

    def test_q1(self, sess, frames):
        rows_equal(sess.query(Q[1]), self._q1(frames))

    def test_q1_distributed(self, cs, frames):
        rows_equal(cs.query(Q[1]), self._q1(frames))

    # -- Q5: channel rollup --------------------------------------------
    def _q5(self, f):
        def chan(df, dcol, scol, pcol, label):
            m = df.merge(f["date_dim"], left_on=dcol,
                         right_on="d_date_sk")
            m = m[m.d_year == 1999]
            return (label, m[scol].sum(), m[pcol].sum())
        rows = sorted([
            chan(f["store_sales"], "ss_sold_date_sk",
                 "ss_ext_sales_price", "ss_net_profit", "store channel"),
            chan(f["catalog_sales"], "cs_sold_date_sk",
                 "cs_ext_sales_price", "cs_net_profit",
                 "catalog channel"),
            chan(f["web_sales"], "ws_sold_date_sk",
                 "ws_ext_sales_price", "ws_net_profit", "web channel")])
        total = (None, sum(r[1] for r in rows),
                 sum(r[2] for r in rows))
        return [(r[0], _r2(r[1]), _r2(r[2])) for r in rows + [total]]

    def test_q5(self, sess, frames):
        rows_equal(sess.query(Q[5]), self._q5(frames))

    def test_q5_distributed(self, cs, frames):
        rows_equal(cs.query(Q[5]), self._q5(frames))

    # -- Q6: states buying premium items -------------------------------
    def _q6(self, f):
        it = f["item"].copy()
        cavg = it.groupby("i_category")["i_current_price"].transform(
            "mean")
        it = it[it.i_current_price > 1.2 * cavg]
        m = f["store_sales"].merge(
            f["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[(m.d_year == 1999) & (m.d_moy == 5)]
        m = (m.merge(f["customer"], left_on="ss_customer_sk",
                     right_on="c_customer_sk")
             .merge(f["customer_address"], left_on="c_current_addr_sk",
                    right_on="ca_address_sk")
             .merge(it, left_on="ss_item_sk", right_on="i_item_sk"))
        g = m.groupby("ca_state").size().reset_index(name="cnt")
        g = g[g.cnt >= 2].sort_values(["cnt", "ca_state"]).head(100)
        return [(r.ca_state, int(r.cnt)) for r in g.itertuples()]

    def test_q6(self, sess, frames):
        rows_equal(sess.query(Q[6]), self._q6(frames))

    # -- Q7: demographic averages --------------------------------------
    def _q7(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
             .merge(f["customer_demographics"], left_on="ss_cdemo_sk",
                    right_on="cd_demo_sk")
             .merge(f["promotion"], left_on="ss_promo_sk",
                    right_on="p_promo_sk"))
        m = m[(m.cd_gender == "M") & (m.cd_marital_status == "S")
              & (m.cd_education_status == "Secondary")
              & ((m.p_channel_email == "N") | (m.p_channel_event == "N"))
              & (m.d_year == 1999)]
        g = (m.groupby("i_item_sk", as_index=False)
             .agg(a1=("ss_quantity", "mean"),
                  a2=("ss_list_price", "mean"),
                  a3=("ss_coupon_amt", "mean"),
                  a4=("ss_sales_price", "mean"))
             .sort_values("i_item_sk").head(100))
        return [(int(r.i_item_sk), r.a1, r.a2, r.a3, r.a4)
                for r in g.itertuples()]

    def test_q7(self, sess, frames):
        rows_equal(sess.query(Q[7]), self._q7(frames))

    # -- Q9: bucket averages via scalar subqueries ---------------------
    def _q9(self, f):
        ss = f["store_sales"]
        out = []
        for lo, hi in ((1, 5), (6, 10), (11, 15), (16, 20)):
            out.append(ss[(ss.ss_quantity >= lo)
                          & (ss.ss_quantity <= hi)]
                       .ss_ext_sales_price.mean())
        out.append(len(ss))
        return [tuple(out)]

    def test_q9(self, sess, frames):
        rows_equal(sess.query(Q[9]), self._q9(frames))

    def test_q9_distributed(self, cs, frames):
        rows_equal(cs.query(Q[9]), self._q9(frames))

    # -- Q13: OR'd demographic bands -----------------------------------
    def _q13(self, f):
        m = (f["store_sales"]
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["customer_demographics"], left_on="ss_cdemo_sk",
                    right_on="cd_demo_sk")
             .merge(f["household_demographics"], left_on="ss_hdemo_sk",
                    right_on="hd_demo_sk")
             .merge(f["customer_address"], left_on="ss_addr_sk",
                    right_on="ca_address_sk"))
        m = m[m.d_year == 1999]
        m = m[((m.cd_marital_status == "M")
               & (m.cd_education_status == "Advanced Degree")
               & (m.hd_dep_count == 3))
              | ((m.cd_marital_status == "S")
                 & (m.cd_education_status == "College")
                 & (m.hd_dep_count == 1))]
        m = m[m.ca_state.isin(["TN", "GA", "OH"])]
        return [(m.ss_quantity.mean(), m.ss_ext_sales_price.mean(),
                 _r2(m.ss_net_profit.sum()))]

    def test_q13(self, sess, frames):
        rows_equal(sess.query(Q[13]), self._q13(frames))

    # -- Q15: catalog revenue by state ---------------------------------
    def _q15(self, f):
        m = (f["catalog_sales"]
             .merge(f["customer"], left_on="cs_bill_customer_sk",
                    right_on="c_customer_sk")
             .merge(f["customer_address"], left_on="c_current_addr_sk",
                    right_on="ca_address_sk")
             .merge(f["date_dim"], left_on="cs_sold_date_sk",
                    right_on="d_date_sk"))
        m = m[(m.d_year == 1999) & (m.d_moy.isin([1, 2, 3]))]
        g = (m.groupby("ca_state", as_index=False)
             .agg(total=("cs_ext_sales_price", "sum"))
             .sort_values("ca_state"))
        return [(r.ca_state, _r2(r.total)) for r in g.itertuples()]

    def test_q15(self, sess, frames):
        rows_equal(sess.query(Q[15]), self._q15(frames))

    def test_q15_distributed(self, cs, frames):
        rows_equal(cs.query(Q[15]), self._q15(frames))

    # -- Q18: geographic rollup of demographic averages ----------------
    def _q18(self, f):
        m = (f["catalog_sales"]
             .merge(f["date_dim"], left_on="cs_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["customer_demographics"],
                    left_on="cs_bill_cdemo_sk", right_on="cd_demo_sk")
             .merge(f["customer"], left_on="cs_bill_customer_sk",
                    right_on="c_customer_sk")
             .merge(f["customer_address"], left_on="c_current_addr_sk",
                    right_on="ca_address_sk"))
        m = m[(m.cd_education_status == "College") & (m.d_year == 1999)]
        rows = []
        g0 = m.groupby(["ca_state", "ca_city"], as_index=False).agg(
            q=("cs_quantity", "mean"), p=("cs_sales_price", "mean"))
        rows += [(r.ca_state, r.ca_city, r.q, r.p)
                 for r in g0.itertuples()]
        g1 = m.groupby("ca_state", as_index=False).agg(
            q=("cs_quantity", "mean"), p=("cs_sales_price", "mean"))
        rows += [(r.ca_state, None, r.q, r.p) for r in g1.itertuples()]
        rows.append((None, None, m.cs_quantity.mean(),
                     m.cs_sales_price.mean()))
        rows.sort(key=lambda r: (_nl(r[0]), _nl(r[1])))
        return rows[:100]

    def test_q18(self, sess, frames):
        rows_equal(sess.query(Q[18]), self._q18(frames))

    # -- Q19: manager-slice brand revenue ------------------------------
    def _q19(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.i_manager_id >= 5) & (m.i_manager_id <= 15)
              & (m.d_moy == 11) & (m.d_year == 1999)]
        g = (m.groupby(["i_brand_id", "i_brand"], as_index=False)
             .agg(p=("ss_ext_sales_price", "sum")))
        g = g.sort_values(["p", "i_brand_id"],
                          ascending=[False, True]).head(100)
        return [(int(r.i_brand_id), r.i_brand, _r2(r.p))
                for r in g.itertuples()]

    def test_q19(self, sess, frames):
        rows_equal(sess.query(Q[19]), self._q19(frames))

    # -- Q22: inventory rollup -----------------------------------------
    def _q22(self, f):
        m = (f["inventory"]
             .merge(f["date_dim"], left_on="inv_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="inv_item_sk",
                    right_on="i_item_sk"))
        m = m[(m.d_month_seq >= 348) & (m.d_month_seq <= 359)]
        rows = []
        g0 = m.groupby(["i_category", "i_brand"], as_index=False).agg(
            qoh=("inv_quantity_on_hand", "mean"))
        rows += [(r.i_category, r.i_brand, r.qoh)
                 for r in g0.itertuples()]
        g1 = m.groupby("i_category", as_index=False).agg(
            qoh=("inv_quantity_on_hand", "mean"))
        rows += [(r.i_category, None, r.qoh) for r in g1.itertuples()]
        rows.append((None, None, m.inv_quantity_on_hand.mean()))
        rows.sort(key=lambda r: (r[2], _nl(r[0]), _nl(r[1])))
        return rows[:100]

    def test_q22(self, sess, frames):
        rows_equal(sess.query(Q[22]), self._q22(frames))

    def test_q22_distributed(self, cs, frames):
        rows_equal(cs.query(Q[22]), self._q22(frames))

    # -- Q25: store buy -> return -> catalog re-buy --------------------
    def _q25(self, f):
        m = (f["store_sales"]
             .merge(f["store_returns"],
                    left_on=["ss_ticket", "ss_item_sk"],
                    right_on=["sr_ticket", "sr_item_sk"])
             .merge(f["catalog_sales"],
                    left_on=["sr_customer_sk", "sr_item_sk"],
                    right_on=["cs_bill_customer_sk", "cs_item_sk"])
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk"))
        g = (m.groupby(["i_item_sk", "s_store_sk"], as_index=False)
             .agg(sp=("ss_net_profit", "sum"),
                  ra=("sr_return_amt", "sum"),
                  cp=("cs_net_profit", "sum"))
             .sort_values(["i_item_sk", "s_store_sk"]).head(100))
        return [(int(r.i_item_sk), int(r.s_store_sk), _r2(r.sp),
                 _r2(r.ra), _r2(r.cp)) for r in g.itertuples()]

    def test_q25(self, sess, frames):
        rows_equal(sess.query(Q[25]), self._q25(frames))

    # -- Q34: bulk tickets by buy potential ----------------------------
    def _q34(self, f):
        m = f["store_sales"].merge(
            f["household_demographics"], left_on="ss_hdemo_sk",
            right_on="hd_demo_sk")
        m = m[m.hd_buy_potential == "1001-5000"]
        g = (m.groupby(["ss_ticket", "ss_customer_sk"])
             .size().reset_index(name="cnt"))
        g = g[(g.cnt >= 2) & (g.cnt <= 10)]
        g = g.merge(f["customer"], left_on="ss_customer_sk",
                    right_on="c_customer_sk")
        g = g.sort_values(["c_last_name", "c_first_name",
                           "ss_ticket"]).head(100)
        return [(r.c_last_name, r.c_first_name, int(r.ss_ticket),
                 int(r.cnt)) for r in g.itertuples()]

    def test_q34(self, sess, frames):
        rows_equal(sess.query(Q[34]), self._q34(frames))

    def test_q34_distributed(self, cs, frames):
        rows_equal(cs.query(Q[34]), self._q34(frames))

    # -- Q36: margin rollup + rank-within-parent -----------------------
    def _q36(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk"))
        m = m[m.d_year == 1999]
        rows = []
        g0 = m.groupby(["i_category", "i_class"], as_index=False).agg(
            p=("ss_net_profit", "sum"), s=("ss_ext_sales_price", "sum"))
        for cat, sub in g0.groupby("i_category"):
            margins = list(sub.p / sub.s)
            ranks = _rank_min(margins)
            for (r, rk) in zip(sub.itertuples(), ranks):
                rows.append((r.p / r.s, cat, r.i_class, 0, rk))
        g1 = m.groupby("i_category", as_index=False).agg(
            p=("ss_net_profit", "sum"), s=("ss_ext_sales_price", "sum"))
        margins = list(g1.p / g1.s)
        ranks = _rank_min(margins)
        for (r, rk) in zip(g1.itertuples(), ranks):
            rows.append((r.p / r.s, r.i_category, None, 1, rk))
        rows.append((m.ss_net_profit.sum() / m.ss_ext_sales_price.sum(),
                     None, None, 2, 1))
        rows.sort(key=lambda r: (-r[3], _nl(r[1]), _nl(r[2]), r[4]))
        return rows

    def test_q36(self, sess, frames):
        rows_equal(sess.query(Q[36]), self._q36(frames))

    def test_q36_distributed(self, cs, frames):
        rows_equal(cs.query(Q[36]), self._q36(frames))

    # -- Q37: price-band items with mid inventory ----------------------
    def _q37(self, f):
        it = f["item"]
        it = it[(it.i_current_price >= 20) & (it.i_current_price <= 50)]
        inv = (f["inventory"]
               .merge(f["date_dim"], left_on="inv_date_sk",
                      right_on="d_date_sk"))
        inv = inv[(inv.d_month_seq >= 348) & (inv.d_month_seq <= 353)
                  & (inv.inv_quantity_on_hand >= 100)
                  & (inv.inv_quantity_on_hand <= 500)]
        m = (it.merge(inv, left_on="i_item_sk", right_on="inv_item_sk")
             .merge(f["catalog_sales"], left_on="i_item_sk",
                    right_on="cs_item_sk"))
        g = (m.groupby(["i_item_sk", "i_current_price"], as_index=False)
             .size().sort_values("i_item_sk").head(100))
        return [(int(r.i_item_sk), r.i_current_price)
                for r in g.itertuples()]

    def test_q37(self, sess, frames):
        rows_equal(sess.query(Q[37]), self._q37(frames))

    # -- Q40: warehouse net sales around a cutoff ----------------------
    def _q40(self, f):
        m = f["catalog_sales"].merge(
            f["catalog_returns"][["cr_order", "cr_item_sk",
                                  "cr_return_amount"]],
            left_on=["cs_order", "cs_item_sk"],
            right_on=["cr_order", "cr_item_sk"], how="left")
        m = (m.merge(f["warehouse"], left_on="cs_warehouse_sk",
                     right_on="w_warehouse_sk")
             .merge(f["item"], left_on="cs_item_sk",
                    right_on="i_item_sk")
             .merge(f["date_dim"], left_on="cs_sold_date_sk",
                    right_on="d_date_sk"))
        m = m[(m.i_current_price >= 10) & (m.i_current_price <= 60)]
        net = m.cs_sales_price - m.cr_return_amount.fillna(0)
        m = m.assign(before=net.where(m.d_date < "1999-06-01", 0.0),
                     after=net.where(m.d_date >= "1999-06-01", 0.0))
        g = (m.groupby(["w_state", "i_item_sk"], as_index=False)
             .agg(b=("before", "sum"), a=("after", "sum"))
             .sort_values(["w_state", "i_item_sk"]).head(100))
        return [(r.w_state, int(r.i_item_sk), _r2(r.b), _r2(r.a))
                for r in g.itertuples()]

    def test_q40(self, sess, frames):
        rows_equal(sess.query(Q[40]), self._q40(frames))

    # -- Q43: day-of-week pivot ----------------------------------------
    def _q43(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk"))
        m = m[m.d_year == 1999]
        out = []
        for name, sub in m.groupby("s_store_name"):
            def dsum(d):
                return _r2(sub.ss_ext_sales_price.where(
                    sub.d_dow == d, 0.0).sum())
            out.append((name, dsum(0), dsum(1), dsum(5), dsum(6)))
        return out

    def test_q43(self, sess, frames):
        rows_equal(sess.query(Q[43]), self._q43(frames))

    # -- Q46: per-ticket amounts for dep/vehicle households ------------
    def _q46(self, f):
        m = (f["store_sales"]
             .merge(f["household_demographics"], left_on="ss_hdemo_sk",
                    right_on="hd_demo_sk")
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk"))
        m = m[(m.hd_dep_count == 4) | (m.hd_vehicle_count == 3)]
        g = (m.groupby(["ss_ticket", "ss_customer_sk"], as_index=False)
             .agg(amt=("ss_coupon_amt", "sum"),
                  profit=("ss_net_profit", "sum")))
        g = g.merge(f["customer"], left_on="ss_customer_sk",
                    right_on="c_customer_sk")
        g = g.sort_values(["c_last_name", "c_first_name",
                           "ss_ticket"]).head(100)
        return [(r.c_last_name, r.c_first_name, int(r.ss_ticket),
                 _r2(r.amt), _r2(r.profit)) for r in g.itertuples()]

    def test_q46(self, sess, frames):
        rows_equal(sess.query(Q[46]), self._q46(frames))

    # -- Q48: OR'd quantity bands --------------------------------------
    def _q48(self, f):
        m = (f["store_sales"]
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["customer_demographics"], left_on="ss_cdemo_sk",
                    right_on="cd_demo_sk")
             .merge(f["customer_address"], left_on="ss_addr_sk",
                    right_on="ca_address_sk"))
        m = m[m.d_year == 1999]
        m = m[((m.cd_marital_status == "M")
               & (m.cd_education_status == "Advanced Degree")
               & (m.ss_sales_price >= 10.00)
               & (m.ss_sales_price <= 150.00))
              | ((m.cd_marital_status == "S")
                 & (m.cd_education_status == "College")
                 & (m.ss_sales_price >= 5.00)
                 & (m.ss_sales_price <= 100.00))]
        m = m[m.ca_state.isin(["TN", "GA", "OH", "TX"])]
        return [(int(m.ss_quantity.sum()),)]

    def test_q48(self, sess, frames):
        rows_equal(sess.query(Q[48]), self._q48(frames))

    # -- Q50: return-latency buckets -----------------------------------
    def _q50(self, f):
        m = (f["store_sales"]
             .merge(f["store_returns"],
                    left_on=["ss_ticket", "ss_item_sk"],
                    right_on=["sr_ticket", "sr_item_sk"])
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
             .merge(f["date_dim"], left_on="sr_returned_date_sk",
                    right_on="d_date_sk"))
        m = m[m.d_year == 1999]
        lag = m.sr_returned_date_sk - m.ss_sold_date_sk
        m = m.assign(d30=(lag <= 30).astype(int),
                     d60=((lag > 30) & (lag <= 60)).astype(int),
                     d90=(lag > 60).astype(int))
        g = (m.groupby("s_store_name", as_index=False)
             .agg(a=("d30", "sum"), b=("d60", "sum"), c=("d90", "sum"))
             .sort_values("s_store_name"))
        return [(r.s_store_name, int(r.a), int(r.b), int(r.c))
                for r in g.itertuples()]

    def test_q50(self, sess, frames):
        rows_equal(sess.query(Q[50]), self._q50(frames))

    def test_q50_distributed(self, cs, frames):
        rows_equal(cs.query(Q[50]), self._q50(frames))

    # -- Q53: manufacturers deviating from their monthly average -------
    def _q53(self, f):
        m = (f["store_sales"]
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk"))
        m = m[(m.d_year == 1999)
              & (m.i_category.isin(["Books", "Music", "Sports"]))]
        g = (m.groupby(["i_manufact_id", "d_moy"], as_index=False)
             .agg(s=("ss_sales_price", "sum")))
        g["avg"] = g.groupby("i_manufact_id")["s"].transform("mean")
        g = g[abs(g.s - g["avg"]) > 0.1 * g["avg"]]
        g = g.sort_values(["i_manufact_id", "d_moy"]).head(100)
        return [(int(r.i_manufact_id), int(r.d_moy), _r2(r.s), r.avg)
                for r in g.itertuples()]

    def test_q53(self, sess, frames):
        rows_equal(sess.query(Q[53]), self._q53(frames))

    # -- Q61: promoted vs total revenue --------------------------------
    def _q61(self, f):
        base = f["store_sales"].merge(
            f["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        base = base[base.d_year == 1999]
        promo = base.merge(f["promotion"], left_on="ss_promo_sk",
                           right_on="p_promo_sk")
        promo = promo[(promo.p_channel_email == "Y")
                      | (promo.p_channel_event == "Y")]
        return [(_r2(promo.ss_ext_sales_price.sum()),
                 _r2(base.ss_ext_sales_price.sum()))]

    def test_q61(self, sess, frames):
        rows_equal(sess.query(Q[61]), self._q61(frames))

    # -- Q65: low-revenue store items ----------------------------------
    def _q65(self, f):
        m = f["store_sales"].merge(
            f["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[(m.d_month_seq >= 348) & (m.d_month_seq <= 359)]
        sa = (m.groupby(["ss_store_sk", "ss_item_sk"], as_index=False)
              .agg(rev=("ss_sales_price", "sum")))
        sa["ave"] = sa.groupby("ss_store_sk")["rev"].transform("mean")
        sel = sa[sa.rev <= 0.1 * sa.ave]
        sel = (sel.merge(f["store"], left_on="ss_store_sk",
                         right_on="s_store_sk")
               .merge(f["item"], left_on="ss_item_sk",
                      right_on="i_item_sk"))
        sel = sel.sort_values(["s_store_name", "i_item_sk"]).head(100)
        return [(r.s_store_name, int(r.i_item_sk), _r2(r.rev))
                for r in sel.itertuples()]

    def test_q65(self, sess, frames):
        rows_equal(sess.query(Q[65]), self._q65(frames))

    def test_q65_distributed(self, cs, frames):
        rows_equal(cs.query(Q[65]), self._q65(frames))

    # -- Q70: profit rollup over geography + rank ----------------------
    def _q70(self, f):
        m = (f["store_sales"]
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
             .merge(f["store"], left_on="ss_store_sk",
                    right_on="s_store_sk"))
        m = m[m.d_year == 1999]
        rows = []
        g0 = m.groupby(["s_state", "s_county"], as_index=False).agg(
            p=("ss_net_profit", "sum"))
        for st, sub in g0.groupby("s_state"):
            ranks = _rank_min(list(sub.p), desc=True)
            for r, rk in zip(sub.itertuples(), ranks):
                rows.append((_r2(r.p), st, r.s_county, 0, rk))
        g1 = m.groupby("s_state", as_index=False).agg(
            p=("ss_net_profit", "sum"))
        ranks = _rank_min(list(g1.p), desc=True)
        for r, rk in zip(g1.itertuples(), ranks):
            rows.append((_r2(r.p), r.s_state, None, 1, rk))
        rows.append((_r2(m.ss_net_profit.sum()), None, None, 2, 1))
        rows.sort(key=lambda r: (-r[3], _nl(r[1]), _nl(r[2]), r[4]))
        return rows

    def test_q70(self, sess, frames):
        rows_equal(sess.query(Q[70]), self._q70(frames))

    def test_q70_distributed(self, cs, frames):
        rows_equal(cs.query(Q[70]), self._q70(frames))

    # -- Q81: catalog returners above their state's average ------------
    def _q81(self, f):
        m = (f["catalog_returns"]
             .merge(f["date_dim"], left_on="cr_returned_date_sk",
                    right_on="d_date_sk")
             .merge(f["customer"], left_on="cr_returning_customer_sk",
                    right_on="c_customer_sk")
             .merge(f["customer_address"], left_on="c_current_addr_sk",
                    right_on="ca_address_sk"))
        m = m[m.d_year == 1999]
        ctr = (m.groupby(["cr_returning_customer_sk", "ca_state"],
                         as_index=False)
               .agg(tot=("cr_return_amount", "sum")))
        avg = ctr.groupby("ca_state")["tot"].transform("mean")
        sel = ctr[ctr.tot > 1.2 * avg].sort_values(
            "cr_returning_customer_sk").head(100)
        return [(int(r.cr_returning_customer_sk), _r2(r.tot))
                for r in sel.itertuples()]

    def test_q81(self, sess, frames):
        rows_equal(sess.query(Q[81]), self._q81(frames))

    def test_q81_distributed(self, cs, frames):
        rows_equal(cs.query(Q[81]), self._q81(frames))

    # -- Q98: class revenue share within category ----------------------
    def _q98(self, f):
        m = (f["store_sales"]
             .merge(f["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
             .merge(f["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk"))
        m = m[(m.d_year == 1999)
              & (m.i_category.isin(["Books", "Home", "Sports"]))]
        g = (m.groupby(["i_category", "i_class"], as_index=False)
             .agg(rev=("ss_ext_sales_price", "sum")))
        g["ratio"] = g.rev * 100.0 / g.groupby("i_category")[
            "rev"].transform("sum")
        g = g.sort_values(["i_category", "i_class"])
        return [(r.i_category, r.i_class, _r2(r.rev), r.ratio)
                for r in g.itertuples()]

    def test_q98(self, sess, frames):
        rows_equal(sess.query(Q[98]), self._q98(frames))


def _r2(x):
    return round(float(x), 2)


class TestRound4BatchA:
    """Round-4 expansion queries vs pandas oracles, run on the CLUSTER
    session (device mesh default-on)."""

    def test_q2_dow_ratio(self, cs, frames):
        ws, cs_, dd = (frames["web_sales"], frames["catalog_sales"],
                       frames["date_dim"])
        u = pd.concat([
            ws[["ws_sold_date_sk", "ws_ext_sales_price"]].rename(
                columns={"ws_sold_date_sk": "sk",
                         "ws_ext_sales_price": "p"}),
            cs_[["cs_sold_date_sk", "cs_ext_sales_price"]].rename(
                columns={"cs_sold_date_sk": "sk",
                         "cs_ext_sales_price": "p"})])
        m = u.merge(dd, left_on="sk", right_on="d_date_sk")
        g = m.groupby(["d_dow", "d_year"]).p.sum().reset_index()
        a = g[g.d_year == 1999].set_index("d_dow").p
        b = g[g.d_year == 2000].set_index("d_dow").p
        want = [(int(dow), _r2(a[dow]), _r2(b[dow]),
                 pytest.approx(float(b[dow] / a[dow]), rel=1e-6))
                for dow in sorted(set(a.index) & set(b.index))]
        got = [(r[0], _r2(r[1]), _r2(r[2]), r[3])
               for r in cs.query(Q[2])]
        assert got == want

    def test_q8_store_profit_county_filter(self, cs, frames):
        ss, dd, st, ca = (frames["store_sales"], frames["date_dim"],
                          frames["store"],
                          frames["customer_address"])
        counties = ca.groupby("ca_county").size()
        counties = set(counties[counties >= 5].index)
        m = ss.merge(dd, left_on="ss_sold_date_sk",
                     right_on="d_date_sk")
        m = m[m.d_year == 1999].merge(st, left_on="ss_store_sk",
                                      right_on="s_store_sk")
        m = m[m.s_county.isin(counties)]
        g = m.groupby("s_store_name").ss_net_profit.sum()
        want = [(k, _r2(v)) for k, v in sorted(g.items())]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[8])]
        assert got == want

    def test_q20_catalog_revenue_share(self, cs, frames):
        m = frames["catalog_sales"].merge(
            frames["item"], left_on="cs_item_sk",
            right_on="i_item_sk")
        m = m[m.i_category.isin(["Books", "Home"])]
        g = m.groupby(["i_category", "i_class"]
                      ).cs_ext_sales_price.sum().reset_index()
        g["ratio"] = g.cs_ext_sales_price * 100.0 / \
            g.groupby("i_category").cs_ext_sales_price.transform("sum")
        g = g.sort_values(["i_category", "ratio"])
        want = [(r.i_category, r.i_class, _r2(r.cs_ext_sales_price),
                 pytest.approx(float(r.ratio), rel=1e-6))
                for r in g.itertuples()]
        got = [(r[0], r[1], _r2(r[2]), r[3]) for r in cs.query(Q[20])]
        assert got == want

    def test_q26_catalog_demo_avgs(self, cs, frames):
        m = frames["catalog_sales"].merge(
            frames["customer_demographics"],
            left_on="cs_bill_cdemo_sk", right_on="cd_demo_sk")
        m = m[(m.cd_gender == "F") & (m.cd_marital_status == "M")]
        m = m.merge(frames["item"], left_on="cs_item_sk",
                    right_on="i_item_sk")
        g = m.groupby("i_brand").agg(a1=("cs_quantity", "mean"),
                                     a2=("cs_sales_price", "mean"),
                                     a3=("cs_ext_sales_price", "mean"))
        want = [(k, pytest.approx(float(r.a1), rel=1e-6),
                 pytest.approx(float(r.a2), rel=1e-6),
                 pytest.approx(float(r.a3), rel=1e-6))
                for k, r in g.sort_index().iterrows()][:100]
        got = cs.query(Q[26])
        assert [tuple(r) for r in got] == want

    def test_q27_store_demo_avgs(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["customer_demographics"], left_on="ss_cdemo_sk",
            right_on="cd_demo_sk")
        m = m[(m.cd_gender == "M")
              & (m.cd_education_status == "College")]
        m = m.merge(frames["date_dim"], left_on="ss_sold_date_sk",
                    right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        g = m.groupby(["i_brand", "s_state"]).agg(
            a1=("ss_quantity", "mean"), a2=("ss_list_price", "mean"),
            a3=("ss_coupon_amt", "mean"),
            a4=("ss_sales_price", "mean"))
        want = [(k[0], k[1]) + tuple(
                    pytest.approx(float(v), rel=1e-6) for v in r)
                for k, r in g.sort_index().iterrows()][:100]
        got = cs.query(Q[27])
        assert [tuple(r) for r in got] == want

    def test_q28_buckets(self, cs, frames):
        ss = frames["store_sales"]
        row = []
        for lo, hi in ((0, 5), (6, 10), (11, 15)):
            b = ss[(ss.ss_quantity >= lo) & (ss.ss_quantity <= hi)]
            row += [pytest.approx(float(b.ss_list_price.mean()),
                                  rel=1e-6),
                    len(b), b.ss_list_price.nunique()]
        got = list(cs.query(Q[28])[0])
        assert got == row

    def test_q33_manufact_channels(self, cs, frames):
        frames_ = frames

        def chan(f, dk, ik, pk):
            m = frames_[f].merge(frames_["date_dim"], left_on=dk,
                                 right_on="d_date_sk")
            m = m[(m.d_year == 1999) & (m.d_moy == 3)]
            m = m.merge(frames_["item"], left_on=ik,
                        right_on="i_item_sk")
            m = m[m.i_category == "Books"]
            return m.groupby("i_manufact_id")[pk].sum()

        tot = (chan("store_sales", "ss_sold_date_sk", "ss_item_sk",
                    "ss_ext_sales_price").add(
               chan("catalog_sales", "cs_sold_date_sk", "cs_item_sk",
                    "cs_ext_sales_price"), fill_value=0).add(
               chan("web_sales", "ws_sold_date_sk", "ws_item_sk",
                    "ws_ext_sales_price"), fill_value=0))
        want = sorted(((int(k), _r2(v)) for k, v in tot.items()),
                      key=lambda kv: (kv[1], kv[0]))[:100]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[33])]
        assert got == want

    def test_q41_manufact_band(self, cs, frames):
        it = frames["item"]
        counts = it.groupby("i_manufact_id").size()
        multi = set(counts[counts >= 2].index)
        sel = it[(it.i_current_price >= 20)
                 & (it.i_current_price <= 60)
                 & it.i_manufact_id.isin(multi)]
        want = [(int(v),) for v in
                sorted(sel.i_manufact_id.unique())][:100]
        assert cs.query(Q[41]) == want

    def test_q44_best_worst(self, cs, frames):
        g = frames["store_sales"].groupby(
            "ss_item_sk").ss_net_profit.mean()
        desc = g.rank(method="min", ascending=False)
        asc = g.rank(method="min", ascending=True)
        best = {int(r): k for k, r in desc.items() if r <= 10}
        worst = {int(r): k for k, r in asc.items()}
        want = [(int(best[i]), int(worst[i]))
                for i in sorted(best) if i in worst]
        got = [tuple(r) for r in cs.query(Q[44])]
        assert got == want

    def test_q45_web_by_city(self, cs, frames):
        m = frames["web_sales"].merge(
            frames["customer"], left_on="ws_bill_customer_sk",
            right_on="c_customer_sk")
        m = m.merge(frames["customer_address"],
                    left_on="c_current_addr_sk",
                    right_on="ca_address_sk")
        m = m.merge(frames["date_dim"], left_on="ws_sold_date_sk",
                    right_on="d_date_sk")
        m = m[(m.d_year == 1999) & (m.d_moy >= 1) & (m.d_moy <= 3)]
        g = m.groupby(["ca_county", "ca_city"]
                      ).ws_sales_price.sum().reset_index()
        g = g.sort_values(["ca_county", "ca_city",
                           "ws_sales_price"]).head(100)
        want = [(r.ca_county, r.ca_city, _r2(r.ws_sales_price))
                for r in g.itertuples()]
        got = [(r[0], r[1], _r2(r[2])) for r in cs.query(Q[45])]
        assert got == want

    def _union_channel_sum(self, frames, key, year, moy):
        def chan(f, dk, ik, pk):
            m = frames[f].merge(frames["date_dim"], left_on=dk,
                                right_on="d_date_sk")
            m = m[(m.d_year == year) & (m.d_moy == moy)]
            m = m.merge(frames["item"], left_on=ik,
                        right_on="i_item_sk")
            return m.groupby(key)[pk].sum()

        return (chan("store_sales", "ss_sold_date_sk", "ss_item_sk",
                     "ss_ext_sales_price").add(
                chan("catalog_sales", "cs_sold_date_sk", "cs_item_sk",
                     "cs_ext_sales_price"), fill_value=0).add(
                chan("web_sales", "ws_sold_date_sk", "ws_item_sk",
                     "ws_ext_sales_price"), fill_value=0))

    def test_q56_brand_channels(self, cs, frames):
        tot = self._union_channel_sum(frames, "i_brand_id", 1999, 2)
        want = sorted(((int(k), _r2(v)) for k, v in tot.items()),
                      key=lambda kv: (kv[1], kv[0]))[:100]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[56])]
        assert got == want

    def test_q60_category_channels(self, cs, frames):
        tot = self._union_channel_sum(frames, "i_category_id",
                                      2000, 9)
        want = sorted(((int(k), _r2(v)) for k, v in tot.items()),
                      key=lambda kv: (kv[1], kv[0]))[:100]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[60])]
        assert got == want

    def test_q62_ship_buckets(self, cs, frames):
        m = frames["web_sales"].merge(
            frames["warehouse"], left_on="ws_warehouse_sk",
            right_on="w_warehouse_sk")
        m = m.merge(frames["ship_mode"], left_on="ws_ship_mode_sk",
                    right_on="sm_ship_mode_sk")
        m = m.merge(frames["web_site"], left_on="ws_web_site_sk",
                    right_on="web_site_sk")
        lag = m.ws_ship_date_sk - m.ws_sold_date_sk
        m = m.assign(d30=(lag <= 30).astype(int),
                     d60=((lag > 30) & (lag <= 60)).astype(int),
                     d90=(lag > 60).astype(int))
        g = m.groupby(["w_warehouse_name", "sm_type", "web_name"]
                      )[["d30", "d60", "d90"]].sum()
        want = [k + (int(r.d30), int(r.d60), int(r.d90))
                for k, r in g.sort_index().iterrows()][:100]
        got = [tuple(r) for r in cs.query(Q[62])]
        assert got == want

    def test_q63_manager_window(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        m = m[m.i_manager_id <= 8]
        g = m.groupby(["i_manager_id", "d_moy"]
                      ).ss_sales_price.sum().reset_index()
        g["avg_m"] = g.groupby("i_manager_id"
                               ).ss_sales_price.transform("mean")
        g = g[g.ss_sales_price > 1.1 * g.avg_m]
        g = g.sort_values(["i_manager_id", "d_moy"]).head(100)
        want = [(int(r.i_manager_id), int(r.d_moy),
                 _r2(r.ss_sales_price),
                 pytest.approx(float(r.avg_m), rel=1e-6))
                for r in g.itertuples()]
        got = [(r[0], r[1], _r2(r[2]), r[3]) for r in cs.query(Q[63])]
        assert got == want

    def test_q73_ticket_counts(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        m = m.merge(frames["household_demographics"],
                    left_on="ss_hdemo_sk", right_on="hd_demo_sk")
        m = m[m.hd_vehicle_count > 1]
        g = m.groupby(["ss_ticket", "ss_customer_sk"]
                      ).size().reset_index(name="cnt")
        g = g[(g.cnt >= 3) & (g.cnt <= 8)]
        g = g.merge(frames["customer"], left_on="ss_customer_sk",
                    right_on="c_customer_sk")
        g = g.sort_values(["cnt", "c_last_name", "c_first_name",
                           "ss_ticket"],
                          ascending=[False, True, True, True])
        want = [(r.c_last_name, r.c_first_name, int(r.ss_ticket),
                 int(r.cnt)) for r in g.head(100).itertuples()]
        got = [tuple(r) for r in cs.query(Q[73])]
        assert got == want

    def test_q88_count_slices(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["household_demographics"], left_on="ss_hdemo_sk",
            right_on="hd_demo_sk")
        want = tuple(int((m.hd_dep_count == d).sum())
                     for d in (1, 2, 3, 4))
        assert tuple(cs.query(Q[88])[0]) == want

    def test_q89_class_deviation(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        m = m[m.i_category.isin(["Books", "Music"])]
        g = m.groupby(["i_category", "i_class", "s_store_name",
                       "d_moy"]).ss_sales_price.sum().reset_index()
        g["avg_m"] = g.groupby(["i_category", "i_class",
                                "s_store_name"]
                               ).ss_sales_price.transform("mean")
        g = g[(g.avg_m > 0)
              & (g.ss_sales_price - g.avg_m > 0.1 * g.avg_m)]
        g = g.sort_values(["i_category", "i_class", "s_store_name",
                           "d_moy"]).head(100)
        want = [(r.i_category, r.i_class, r.s_store_name,
                 int(r.d_moy), _r2(r.ss_sales_price),
                 pytest.approx(float(r.avg_m), rel=1e-6))
                for r in g.itertuples()]
        got = [tuple(r) for r in cs.query(Q[89])]
        assert got == want

    def test_q90_dow_ratio(self, cs, frames):
        m = frames["web_sales"].merge(
            frames["customer"], left_on="ws_bill_customer_sk",
            right_on="c_customer_sk")
        m = m.merge(frames["household_demographics"],
                    left_on="c_current_hdemo_sk",
                    right_on="hd_demo_sk")
        m = m[m.hd_dep_count == 3]
        m = m.merge(frames["date_dim"], left_on="ws_sold_date_sk",
                    right_on="d_date_sk")
        am = int((m.d_dow <= 2).sum())
        pm = int((m.d_dow >= 4).sum())
        got = cs.query(Q[90])[0][0]
        assert got == pytest.approx(am / pm, rel=1e-9)

    def test_q91_call_center_returns(self, cs, frames):
        m = frames["catalog_returns"].merge(
            frames["call_center"], left_on="cr_call_center_sk",
            right_on="cc_call_center_sk")
        m = m.merge(frames["date_dim"],
                    left_on="cr_returned_date_sk",
                    right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["customer"],
                    left_on="cr_returning_customer_sk",
                    right_on="c_customer_sk")
        m = m.merge(frames["customer_demographics"],
                    left_on="c_current_cdemo_sk",
                    right_on="cd_demo_sk")
        m = m[m.cd_education_status.isin(["College",
                                          "Advanced Degree"])]
        g = m.groupby(["cc_name", "cd_marital_status",
                       "cd_education_status"]
                      ).cr_return_amount.sum().reset_index()
        g = g.sort_values(["cr_return_amount", "cc_name",
                           "cd_marital_status"],
                          ascending=[False, True, True]).head(100)
        want = [(r.cc_name, r.cd_marital_status,
                 r.cd_education_status, _r2(r.cr_return_amount))
                for r in g.itertuples()]
        got = [(r[0], r[1], r[2], _r2(r[3])) for r in cs.query(Q[91])]
        assert got == want

    def test_q93_net_of_returns(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["store_returns"], how="left",
            left_on=["ss_ticket", "ss_item_sk"],
            right_on=["sr_ticket", "sr_item_sk"])
        act = np.where(m.sr_return_quantity.notna(),
                       (m.ss_quantity - m.sr_return_quantity)
                       * m.ss_sales_price,
                       m.ss_quantity * m.ss_sales_price)
        g = m.assign(act=act).groupby("ss_customer_sk"
                                      ).act.sum().reset_index()
        g = g.sort_values(["act", "ss_customer_sk"],
                          ascending=[False, True]).head(100)
        want = [(int(r.ss_customer_sk),
                 pytest.approx(float(r.act), rel=1e-6))
                for r in g.itertuples()]
        got = [tuple(r) for r in cs.query(Q[93])]
        assert got == want

    def test_q96_count(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["household_demographics"], left_on="ss_hdemo_sk",
            right_on="hd_demo_sk")
        m = m[m.hd_dep_count == 2]
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        want = int((m.s_state == "TN").sum())
        assert cs.query(Q[96]) == [(want,)]

    def test_q99_catalog_ship_buckets(self, cs, frames):
        m = frames["catalog_sales"].merge(
            frames["warehouse"], left_on="cs_warehouse_sk",
            right_on="w_warehouse_sk")
        m = m.merge(frames["ship_mode"], left_on="cs_ship_mode_sk",
                    right_on="sm_ship_mode_sk")
        m = m.merge(frames["call_center"],
                    left_on="cs_call_center_sk",
                    right_on="cc_call_center_sk")
        lag = m.cs_ship_date_sk - m.cs_sold_date_sk
        m = m.assign(d30=(lag <= 30).astype(int),
                     d60=((lag > 30) & (lag <= 60)).astype(int),
                     d90=(lag > 60).astype(int))
        g = m.groupby(["w_warehouse_name", "sm_type", "cc_name"]
                      )[["d30", "d60", "d90"]].sum()
        want = [k + (int(r.d30), int(r.d60), int(r.d90))
                for k, r in g.sort_index().iterrows()][:100]
        got = [tuple(r) for r in cs.query(Q[99])]
        assert got == want


class TestRound4BatchB:
    """Second round-4 batch: CTE year-over-year, correlated subqueries,
    exists/not-exists, channel unions, inventory, full joins."""

    def _year_totals(self, frames):
        ss = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk").merge(
            frames["customer"], left_on="ss_customer_sk",
            right_on="c_customer_sk")
        ws = frames["web_sales"].merge(
            frames["date_dim"], left_on="ws_sold_date_sk",
            right_on="d_date_sk").merge(
            frames["customer"], left_on="ws_bill_customer_sk",
            right_on="c_customer_sk")
        s = ss.groupby(["c_customer_sk", "d_year"]
                       ).ss_ext_sales_price.sum()
        w = ws.groupby(["c_customer_sk", "d_year"]
                       ).ws_ext_sales_price.sum()
        return s, w

    def _growth_cids(self, frames):
        s, w = self._year_totals(frames)
        out = []
        for cid in sorted({k[0] for k in s.index}):
            try:
                s1, s2 = s[(cid, 1999)], s[(cid, 2000)]
                w1, w2 = w[(cid, 1999)], w[(cid, 2000)]
            except KeyError:
                continue
            if s1 > 0 and w1 > 0 and w2 / w1 > s2 / s1:
                out.append(cid)
        return out[:100]

    def test_q4_growth(self, cs, frames):
        want = [(int(c),) for c in self._growth_cids(frames)]
        assert cs.query(Q[4]) == want

    def test_q74_growth_names(self, cs, frames):
        cust = frames["customer"].set_index("c_customer_sk")
        want = [(int(c), cust.loc[c, "c_last_name"],
                 cust.loc[c, "c_first_name"])
                for c in self._growth_cids(frames)]
        assert [tuple(r) for r in cs.query(Q[74])] == want

    def test_q11_totals(self, cs, frames):
        s, w = self._year_totals(frames)
        out = []
        for cid in sorted({k[0] for k in s.index}):
            try:
                s2, w2 = s[(cid, 2000)], w[(cid, 2000)]
            except KeyError:
                continue
            if s2 > 0:
                out.append((int(cid), _r2(s2), _r2(w2)))
        want = out[:100]
        got = [(r[0], _r2(r[1]), _r2(r[2])) for r in cs.query(Q[11])]
        assert got == want

    def _active_custs(self, frames, fact, custkey, datekey):
        m = frames[fact].merge(frames["date_dim"], left_on=datekey,
                               right_on="d_date_sk")
        return set(m[m.d_year == 1999][custkey])

    def test_q10_demo_counts(self, cs, frames):
        c = frames["customer"].merge(
            frames["customer_address"], left_on="c_current_addr_sk",
            right_on="ca_address_sk")
        c = c[c.ca_county.isin(["county_0", "county_1", "county_2"])]
        store = self._active_custs(frames, "store_sales",
                                   "ss_customer_sk",
                                   "ss_sold_date_sk")
        web = self._active_custs(frames, "web_sales",
                                 "ws_bill_customer_sk",
                                 "ws_sold_date_sk")
        c = c[c.c_customer_sk.isin(store & web)]
        c = c.merge(frames["customer_demographics"],
                    left_on="c_current_cdemo_sk",
                    right_on="cd_demo_sk")
        g = c.groupby(["cd_gender", "cd_marital_status",
                       "cd_education_status"]).size()
        want = [k + (int(v),) for k, v in g.sort_index().items()][:100]
        assert [tuple(r) for r in cs.query(Q[10])] == want

    def test_q35_demo_avgs(self, cs, frames):
        store = self._active_custs(frames, "store_sales",
                                   "ss_customer_sk",
                                   "ss_sold_date_sk")
        web = self._active_custs(frames, "web_sales",
                                 "ws_bill_customer_sk",
                                 "ws_sold_date_sk")
        c = frames["customer"]
        c = c[c.c_customer_sk.isin(store & web)]
        c = c.merge(frames["customer_demographics"],
                    left_on="c_current_cdemo_sk",
                    right_on="cd_demo_sk")
        g = c.groupby(["cd_gender", "cd_marital_status"]).agg(
            cnt=("cd_dep_count", "size"),
            avg_dep=("cd_dep_count", "mean"))
        want = [k + (int(r.cnt),
                     pytest.approx(float(r.avg_dep), rel=1e-6))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[35])] == want

    def test_q69_store_not_web(self, cs, frames):
        store = self._active_custs(frames, "store_sales",
                                   "ss_customer_sk",
                                   "ss_sold_date_sk")
        web = self._active_custs(frames, "web_sales",
                                 "ws_bill_customer_sk",
                                 "ws_sold_date_sk")
        c = frames["customer"]
        c = c[c.c_customer_sk.isin(store - web)]
        c = c.merge(frames["customer_demographics"],
                    left_on="c_current_cdemo_sk",
                    right_on="cd_demo_sk")
        g = c.groupby(["cd_gender", "cd_marital_status"]).size()
        want = [k + (int(v),) for k, v in g.sort_index().items()][:100]
        assert [tuple(r) for r in cs.query(Q[69])] == want

    def test_q14_cross_channel_items(self, cs, frames):
        items = (set(frames["store_sales"].ss_item_sk)
                 & set(frames["catalog_sales"].cs_item_sk)
                 & set(frames["web_sales"].ws_item_sk))
        m = frames["store_sales"]
        m = m[m.ss_item_sk.isin(items)].merge(
            frames["item"], left_on="ss_item_sk",
            right_on="i_item_sk")
        g = m.groupby("i_brand_id").ss_ext_sales_price.sum()
        want = [(int(k), _r2(v))
                for k, v in g.sort_index().items()][:100]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[14])]
        assert got == want

    def test_q16_q94_unreturned(self, cs, frames):
        for fact, rets, okey, rkey, price, profit, qn in (
                ("catalog_sales", "catalog_returns", "cs_order",
                 "cr_order", "cs_ext_sales_price", "cs_net_profit",
                 16),
                ("web_sales", "web_returns", "ws_order", "wr_order",
                 "ws_ext_sales_price", "ws_net_profit", 94)):
            f = frames[fact]
            lag = (f[okey.split("_")[0] + "_ship_date_sk"]
                   - f[okey.split("_")[0] + "_sold_date_sk"])
            sel = f[(lag > 60)
                    & ~f[okey].isin(set(frames[rets][rkey]))]
            want = (sel[okey].nunique(), _r2(sel[price].sum()),
                    _r2(sel[profit].sum()))
            got = cs.query(Q[qn])[0]
            assert (got[0], _r2(got[1]), _r2(got[2])) == want, qn

    def test_q95_returned(self, cs, frames):
        f = frames["web_sales"]
        sel = f[f.ws_order.isin(set(frames["web_returns"].wr_order))]
        want = (sel.ws_order.nunique(),
                _r2(sel.ws_ext_sales_price.sum()))
        got = cs.query(Q[95])[0]
        assert (got[0], _r2(got[1])) == want

    def _chain(self, frames):
        m = frames["store_sales"].merge(
            frames["store_returns"],
            left_on=["ss_ticket", "ss_item_sk"],
            right_on=["sr_ticket", "sr_item_sk"])
        m = m.merge(frames["catalog_sales"],
                    left_on=["sr_customer_sk", "sr_item_sk"],
                    right_on=["cs_bill_customer_sk", "cs_item_sk"])
        return m.merge(frames["item"], left_on="ss_item_sk",
                       right_on="i_item_sk")

    def test_q17_chain_avgs(self, cs, frames):
        g = self._chain(frames).groupby("i_brand").agg(
            cnt=("ss_quantity", "size"), a=("ss_quantity", "mean"),
            b=("sr_return_quantity", "mean"),
            c=("cs_quantity", "mean"))
        want = [(k, int(r.cnt), pytest.approx(float(r.a), rel=1e-6),
                 pytest.approx(float(r.b), rel=1e-6),
                 pytest.approx(float(r.c), rel=1e-6))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[17])] == want

    def test_q29_chain_sums(self, cs, frames):
        g = self._chain(frames).groupby("i_brand").agg(
            a=("ss_quantity", "sum"), b=("sr_return_quantity", "sum"),
            c=("cs_quantity", "sum"))
        want = [(k, int(r.a), int(r.b), int(r.c))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[29])] == want

    def test_q64_chain_store(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["store_returns"],
            left_on=["ss_ticket", "ss_item_sk"],
            right_on=["sr_ticket", "sr_item_sk"])
        m = m.merge(frames["catalog_sales"],
                    left_on=["sr_customer_sk", "sr_item_sk"],
                    right_on=["cs_bill_customer_sk", "cs_item_sk"])
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        g = m.groupby(["i_brand", "s_store_name"]).agg(
            cnt=("ss_sales_price", "size"),
            sr=("ss_sales_price", "sum"),
            cr=("cs_ext_sales_price", "sum"))
        want = [k + (int(r.cnt), _r2(r.sr), _r2(r.cr))
                for k, r in g.sort_index().iterrows()][:100]
        got = [(r[0], r[1], r[2], _r2(r[3]), _r2(r[4]))
               for r in cs.query(Q[64])]
        assert got == want

    def test_q21_inventory_pivot(self, cs, frames):
        m = frames["inventory"].merge(
            frames["warehouse"], left_on="inv_warehouse_sk",
            right_on="w_warehouse_sk")
        m = m.merge(frames["item"], left_on="inv_item_sk",
                    right_on="i_item_sk")
        m = m.merge(frames["date_dim"], left_on="inv_date_sk",
                    right_on="d_date_sk")
        before = np.where(m.d_date < "1999-06-01",
                          m.inv_quantity_on_hand, 0)
        after = np.where(m.d_date >= "1999-06-01",
                         m.inv_quantity_on_hand, 0)
        g = m.assign(b=before, a=after).groupby(
            ["w_warehouse_name", "i_brand"])[["b", "a"]].sum()
        want = [k + (int(r.b), int(r.a))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[21])] == want

    def test_q23_frequent_best(self, cs, frames):
        ss = frames["store_sales"]
        freq = ss.groupby("ss_item_sk").size()
        freq = set(freq[freq > 8].index)
        tot = ss.groupby("ss_customer_sk").ss_ext_sales_price.sum()
        best = set(tot[tot > 0.8 * tot.max()].index)
        c = frames["catalog_sales"]
        sel = c[c.cs_item_sk.isin(freq)
                & c.cs_bill_customer_sk.isin(best)]
        want = _r2(sel.cs_ext_sales_price.sum())
        assert _r2(cs.query(Q[23])[0][0]) == want

    def test_q24_returned_rebought(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["store_returns"],
            left_on=["ss_ticket", "ss_item_sk"],
            right_on=["sr_ticket", "sr_item_sk"])
        m = m.merge(frames["customer"], left_on="ss_customer_sk",
                    right_on="c_customer_sk")
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        m = m[m.i_current_price > 50]
        g = m.groupby(["c_last_name", "c_first_name"]
                      ).ss_sales_price.sum()
        g = g[g > 100]
        want = [k + (_r2(v),) for k, v in g.sort_index().items()][:100]
        got = [(r[0], r[1], _r2(r[2])) for r in cs.query(Q[24])]
        assert got == want

    def test_q30_above_state_avg(self, cs, frames):
        m = frames["web_returns"].merge(
            frames["date_dim"], left_on="wr_returned_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["customer"],
                    left_on="wr_returning_customer_sk",
                    right_on="c_customer_sk")
        m = m.merge(frames["customer_address"],
                    left_on="c_current_addr_sk",
                    right_on="ca_address_sk")
        g = m.groupby(["wr_returning_customer_sk", "ca_state"]
                      ).wr_return_amt.sum().reset_index()
        avg = g.groupby("ca_state").wr_return_amt.transform("mean")
        sel = g[g.wr_return_amt > 1.2 * avg]
        sel = sel.sort_values("wr_returning_customer_sk").head(100)
        want = [(int(r.wr_returning_customer_sk),
                 _r2(r.wr_return_amt)) for r in sel.itertuples()]
        got = [(r[0], _r2(r[1])) for r in cs.query(Q[30])]
        assert got == want

    def test_q31_county_growth(self, cs, frames):
        def month_sum(fact, dk, ck, pk):
            m = frames[fact].merge(frames["date_dim"], left_on=dk,
                                   right_on="d_date_sk")
            m = m[m.d_year == 1999]
            m = m.merge(frames["customer"], left_on=ck,
                        right_on="c_customer_sk")
            m = m.merge(frames["customer_address"],
                        left_on="c_current_addr_sk",
                        right_on="ca_address_sk")
            return m.groupby(["ca_county", "d_moy"])[pk].sum()

        s = month_sum("store_sales", "ss_sold_date_sk",
                      "ss_customer_sk", "ss_ext_sales_price")
        w = month_sum("web_sales", "ws_sold_date_sk",
                      "ws_bill_customer_sk", "ws_ext_sales_price")
        want = []
        for county in sorted({k[0] for k in s.index}):
            try:
                s1, s2 = s[(county, 1)], s[(county, 2)]
                w1, w2 = w[(county, 1)], w[(county, 2)]
            except KeyError:
                continue
            if s1 > 0 and w1 > 0:
                want.append((county,
                             pytest.approx(float(s2 / s1), rel=1e-6),
                             pytest.approx(float(w2 / w1),
                                           rel=1e-6)))
        assert [tuple(r) for r in cs.query(Q[31])] == want

    def test_q32_q92_excess(self, cs, frames):
        for fact, ik, pk, qn in (
                ("catalog_sales", "cs_item_sk", "cs_ext_sales_price",
                 32),
                ("web_sales", "ws_item_sk", "ws_ext_sales_price",
                 92)):
            f = frames[fact].merge(frames["item"], left_on=ik,
                                   right_on="i_item_sk")
            f = f[f.i_manufact_id <= 4]
            avg = frames[fact].groupby(ik)[pk].mean()
            sel = f[f[pk] > 1.3 * f[ik].map(avg)]
            want = _r2(sel[pk].sum()) if len(sel) else None
            got = cs.query(Q[qn])[0][0]
            assert (got is None and want is None) or \
                _r2(got) == want, qn

    def test_q39_inventory_pairs(self, cs, frames):
        m = frames["inventory"].merge(
            frames["warehouse"], left_on="inv_warehouse_sk",
            right_on="w_warehouse_sk")
        m = m.merge(frames["date_dim"], left_on="inv_date_sk",
                    right_on="d_date_sk")
        m = m[m.d_year == 1999]
        g = m.groupby(["w_warehouse_name", "inv_item_sk", "d_moy"]
                      ).inv_quantity_on_hand.agg(
                          ["mean", "max", "min"])
        g["spread"] = g["max"] - g["min"]
        want = []
        for (wn, item) in sorted({(k[0], k[1]) for k in g.index}):
            try:
                r1 = g.loc[(wn, item, 1)]
                r2 = g.loc[(wn, item, 2)]
            except KeyError:
                continue
            if r1["spread"] > r1["mean"] * 0.5:
                want.append((wn, int(item),
                             pytest.approx(float(r1["mean"]),
                                           rel=1e-6),
                             pytest.approx(float(r2["mean"]),
                                           rel=1e-6)))
        assert [tuple(r) for r in cs.query(Q[39])] == want[:100]

    def _monthly(self, frames, fact, dk, gk, pk, dim=None,
                 dimkeys=None):
        m = frames[fact].merge(frames["date_dim"], left_on=dk,
                               right_on="d_date_sk")
        m = m[m.d_year == 1999]
        if dim:
            m = m.merge(frames[dim], left_on=dimkeys[0],
                        right_on=dimkeys[1])
        return m.groupby([gk, "d_moy"])[pk].sum()

    def test_q47_lag_lead(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["item"], left_on="ss_item_sk",
                    right_on="i_item_sk")
        g = m.groupby(["i_brand", "d_moy"]).ss_sales_price.sum()
        want = []
        for brand in sorted({k[0] for k in g.index}):
            moys = sorted(k[1] for k in g.index if k[0] == brand)
            for moy in moys:
                if (brand, moy - 1) in g.index and \
                        (brand, moy + 1) in g.index:
                    want.append((brand, int(moy),
                                 _r2(g[(brand, moy)]),
                                 _r2(g[(brand, moy - 1)]),
                                 _r2(g[(brand, moy + 1)])))
        want = want[:100]
        got = [(r[0], r[1], _r2(r[2]), _r2(r[3]), _r2(r[4]))
               for r in cs.query(Q[47])]
        assert got == want

    def test_q57_call_center_lag(self, cs, frames):
        m = frames["catalog_sales"].merge(
            frames["date_dim"], left_on="cs_sold_date_sk",
            right_on="d_date_sk")
        m = m[m.d_year == 1999]
        m = m.merge(frames["call_center"],
                    left_on="cs_call_center_sk",
                    right_on="cc_call_center_sk")
        g = m.groupby(["cc_name", "d_moy"]).cs_sales_price.sum()
        want = []
        for cc in sorted({k[0] for k in g.index}):
            moys = sorted(k[1] for k in g.index if k[0] == cc)
            for moy in moys:
                if (cc, moy - 1) in g.index and \
                        (cc, moy + 1) in g.index:
                    want.append((cc, int(moy), _r2(g[(cc, moy)]),
                                 _r2(g[(cc, moy - 1)]),
                                 _r2(g[(cc, moy + 1)])))
        want = want[:100]
        got = [(r[0], r[1], _r2(r[2]), _r2(r[3]), _r2(r[4]))
               for r in cs.query(Q[57])]
        assert got == want

    def test_q49_return_ranks(self, cs, frames):
        def ratios(sales, rets, sk, rk, qcol, rqcol):
            m = frames[sales].merge(
                frames[rets], left_on=[sk[0], sk[1]],
                right_on=[rk[0], rk[1]])
            g = m.groupby(sk[1]).apply(
                lambda d: d[rqcol].sum() / d[qcol].sum(),
                include_groups=False)
            return g

        out = []
        for chan, args in (
                ("web", ("web_sales", "web_returns",
                         ("ws_order", "ws_item_sk"),
                         ("wr_order", "wr_item_sk"), "ws_quantity",
                         "wr_return_quantity")),
                ("catalog", ("catalog_sales", "catalog_returns",
                             ("cs_order", "cs_item_sk"),
                             ("cr_order", "cr_item_sk"),
                             "cs_quantity", "cr_return_quantity"))):
            g = ratios(*args)
            rank = g.rank(method="min")
            for item, rr in g.items():
                if rank[item] <= 10:
                    out.append((chan, int(item),
                                pytest.approx(float(rr), rel=1e-6),
                                int(rank[item])))
        out.sort(key=lambda r: (r[0], r[3], r[1]))
        assert [tuple(r) for r in cs.query(Q[49])] == out

    def test_q58_equal_share(self, cs, frames):
        s = frames["store_sales"].groupby(
            "ss_item_sk").ss_ext_sales_price.sum()
        c = frames["catalog_sales"].groupby(
            "cs_item_sk").cs_ext_sales_price.sum()
        w = frames["web_sales"].groupby(
            "ws_item_sk").ws_ext_sales_price.sum()
        want = []
        for item in sorted(set(s.index) & set(c.index)
                           & set(w.index)):
            sv, cv, wv = s[item], c[item], w[item]
            if 0.5 * cv <= sv <= 2.0 * cv and \
                    0.5 * wv <= sv <= 2.0 * wv:
                want.append((int(item), _r2(sv), _r2(cv), _r2(wv)))
        want = want[:100]
        got = [(r[0], _r2(r[1]), _r2(r[2]), _r2(r[3]))
               for r in cs.query(Q[58])]
        assert got == want

    def test_q59_dow_year_ratio(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["date_dim"], left_on="ss_sold_date_sk",
            right_on="d_date_sk")
        m = m.merge(frames["store"], left_on="ss_store_sk",
                    right_on="s_store_sk")
        g = m.groupby(["s_store_name", "d_dow", "d_year"]
                      ).ss_sales_price.sum()
        want = []
        for (sn, dow) in sorted({(k[0], k[1]) for k in g.index}):
            try:
                y, z = g[(sn, dow, 1999)], g[(sn, dow, 2000)]
            except KeyError:
                continue
            if y > 0:
                want.append((sn, int(dow), _r2(y), _r2(z),
                             pytest.approx(float(z / y), rel=1e-6)))
        want = want[:100]
        got = [(r[0], r[1], _r2(r[2]), _r2(r[3]), r[4])
               for r in cs.query(Q[59])]
        assert got == want

    def test_q66_warehouse_mode(self, cs, frames):
        u = pd.concat([
            frames["web_sales"][[
                "ws_warehouse_sk", "ws_ship_mode_sk",
                "ws_sold_date_sk", "ws_quantity",
                "ws_ext_sales_price"]].set_axis(
                ["wsk", "smk", "dsk", "qty", "rev"], axis=1),
            frames["catalog_sales"][[
                "cs_warehouse_sk", "cs_ship_mode_sk",
                "cs_sold_date_sk", "cs_quantity",
                "cs_ext_sales_price"]].set_axis(
                ["wsk", "smk", "dsk", "qty", "rev"], axis=1)])
        m = u.merge(frames["warehouse"], left_on="wsk",
                    right_on="w_warehouse_sk")
        m = m.merge(frames["ship_mode"], left_on="smk",
                    right_on="sm_ship_mode_sk")
        m = m.merge(frames["date_dim"], left_on="dsk",
                    right_on="d_date_sk")
        m = m[m.d_year == 1999]
        g = m.groupby(["w_warehouse_name", "sm_type", "d_moy"]
                      )[["qty", "rev"]].sum()
        want = [k[:2] + (int(k[2]), int(r.qty), _r2(r.rev))
                for k, r in g.sort_index().iterrows()][:100]
        got = [(r[0], r[1], r[2], r[3], _r2(r[4]))
               for r in cs.query(Q[66])]
        assert got == want

    def test_q72_low_stock(self, cs, frames):
        m = frames["catalog_sales"].merge(
            frames["inventory"],
            left_on=["cs_item_sk", "cs_warehouse_sk"],
            right_on=["inv_item_sk", "inv_warehouse_sk"])
        m = m.merge(frames["warehouse"], left_on="inv_warehouse_sk",
                    right_on="w_warehouse_sk")
        m = m.merge(frames["item"], left_on="cs_item_sk",
                    right_on="i_item_sk")
        m = m[m.i_manager_id <= 5]
        low = (m.inv_quantity_on_hand < m.cs_quantity).astype(int)
        g = m.assign(low=low).groupby(
            ["i_brand", "w_warehouse_name"]).agg(
            cnt=("low", "size"), low=("low", "sum"))
        want = [k + (int(r.cnt), int(r.low))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[72])] == want

    def test_q75_prior_year(self, cs, frames):
        def chan(fact, ik, dk, qk, pk):
            m = frames[fact].merge(frames["item"], left_on=ik,
                                   right_on="i_item_sk")
            m = m.merge(frames["date_dim"], left_on=dk,
                        right_on="d_date_sk")
            return m.groupby(["d_year", "i_brand_id"]).agg(
                cnt=(qk, "sum"), amt=(pk, "sum"))

        tot = (chan("store_sales", "ss_item_sk", "ss_sold_date_sk",
                    "ss_quantity", "ss_ext_sales_price")
               .add(chan("catalog_sales", "cs_item_sk",
                         "cs_sold_date_sk", "cs_quantity",
                         "cs_ext_sales_price"), fill_value=0)
               .add(chan("web_sales", "ws_item_sk",
                         "ws_sold_date_sk", "ws_quantity",
                         "ws_ext_sales_price"), fill_value=0))
        want = []
        for brand in sorted({k[1] for k in tot.index}):
            try:
                cur = tot.loc[(2000, brand)]
                prev = tot.loc[(1999, brand)]
            except KeyError:
                continue
            if cur.cnt < prev.cnt:
                want.append((int(brand), int(prev.cnt), int(cur.cnt),
                             _r2(cur.amt - prev.amt)))
        want.sort(key=lambda r: (r[3], r[0]))
        want = want[:100]
        got = [(r[0], r[1], r[2], _r2(r[3])) for r in cs.query(Q[75])]
        assert got == want

    def test_q76_channel_counts(self, cs, frames):
        rows = []
        for chan, fact, dk, ik, ck, pk in (
                ("store", "store_sales", "ss_sold_date_sk",
                 "ss_item_sk", "ss_customer_sk",
                 "ss_ext_sales_price"),
                ("web", "web_sales", "ws_sold_date_sk", "ws_item_sk",
                 "ws_bill_customer_sk", "ws_ext_sales_price"),
                ("catalog", "catalog_sales", "cs_sold_date_sk",
                 "cs_item_sk", "cs_bill_customer_sk",
                 "cs_ext_sales_price")):
            m = frames[fact]
            m = m[m[ck].notna()]
            m = m.merge(frames["date_dim"], left_on=dk,
                        right_on="d_date_sk")
            m = m.merge(frames["item"], left_on=ik,
                        right_on="i_item_sk")
            g = m.groupby(["d_year", "i_category"]).agg(
                cnt=(pk, "size"), amt=(pk, "sum"))
            rows += [(chan, int(k[0]), k[1], int(r.cnt), _r2(r.amt))
                     for k, r in g.iterrows()]
        rows.sort(key=lambda r: (r[0], r[1], r[2]))
        want = rows[:100]
        got = [(r[0], r[1], r[2], r[3], _r2(r[4]))
               for r in cs.query(Q[76])]
        assert got == want

    def test_q77_q80_channel_totals(self, cs, frames):
        # Q77: raw channel totals
        want = []
        for chan, sales, ret in (
                ("catalog", frames["catalog_sales"
                                   ].cs_ext_sales_price.sum(),
                 frames["catalog_returns"].cr_return_amount.sum()),
                ("store", frames["store_sales"
                                 ].ss_ext_sales_price.sum(),
                 frames["store_returns"].sr_return_amt.sum()),
                ("web", frames["web_sales"].ws_ext_sales_price.sum(),
                 frames["web_returns"].wr_return_amt.sum())):
            want.append((chan, _r2(sales), _r2(ret)))
        got = [(r[0], _r2(r[1]), _r2(r[2])) for r in cs.query(Q[77])]
        assert got == want
        # Q80: email-promo-filtered channel totals
        p = frames["promotion"]
        no_email = set(p[p.p_channel_email == "N"].p_promo_sk)
        ss = frames["store_sales"]
        ss = ss[ss.ss_promo_sk.isin(no_email)]
        ws = frames["web_sales"]
        ws = ws[ws.ws_promo_sk.isin(no_email)]
        want80 = [
            ("store", _r2(ss.ss_ext_sales_price.sum()),
             _r2(frames["store_returns"].sr_return_amt.sum()),
             _r2(ss.ss_net_profit.sum())),
            ("web", _r2(ws.ws_ext_sales_price.sum()),
             _r2(frames["web_returns"].wr_return_amt.sum()),
             _r2(ws.ws_net_profit.sum()))]
        got80 = [(r[0], _r2(r[1]), _r2(r[2]), _r2(r[3]))
                 for r in cs.query(Q[80])]
        assert got80 == want80

    def test_q78_unreturned_items(self, cs, frames):
        m = frames["store_sales"].merge(
            frames["store_returns"], how="left",
            left_on=["ss_ticket", "ss_item_sk"],
            right_on=["sr_ticket", "sr_item_sk"])
        m = m[m.sr_ticket.isna()]
        g = m.groupby(["ss_customer_sk", "ss_item_sk"]
                      ).ss_quantity.sum()
        g = g[g >= 3]
        want = [(int(k[0]), int(k[1]), int(v))
                for k, v in g.sort_index().items()][:100]
        assert [tuple(r) for r in cs.query(Q[78])] == want

    def test_q82_inventory_band(self, cs, frames):
        inv = frames["inventory"]
        items_inv = set(inv[(inv.inv_quantity_on_hand >= 100)
                            & (inv.inv_quantity_on_hand <= 500)
                            ].inv_item_sk)
        it = frames["item"]
        sel = it[(it.i_current_price >= 30)
                 & (it.i_current_price <= 60)
                 & it.i_item_sk.isin(items_inv)
                 & it.i_item_sk.isin(
                     set(frames["store_sales"].ss_item_sk))]
        want = [(int(r.i_item_sk),
                 pytest.approx(float(r.i_current_price), rel=1e-9))
                for r in sel.sort_values("i_item_sk"
                                         ).head(100).itertuples()]
        assert [tuple(r) for r in cs.query(Q[82])] == want

    def test_q83_returned_quantities(self, cs, frames):
        s = frames["store_returns"].groupby(
            "sr_item_sk").sr_return_quantity.sum()
        c = frames["catalog_returns"].groupby(
            "cr_item_sk").cr_return_quantity.sum()
        w = frames["web_returns"].groupby(
            "wr_item_sk").wr_return_quantity.sum()
        want = [(int(k), int(s[k]), int(c[k]), int(w[k]))
                for k in sorted(set(s.index) & set(c.index)
                                & set(w.index))][:100]
        assert [tuple(r) for r in cs.query(Q[83])] == want

    def test_q84_buy_potential(self, cs, frames):
        c = frames["customer"].merge(
            frames["customer_address"], left_on="c_current_addr_sk",
            right_on="ca_address_sk")
        c = c[c.ca_city == "city_1"]
        c = c.merge(frames["household_demographics"],
                    left_on="c_current_hdemo_sk",
                    right_on="hd_demo_sk")
        c = c[c.hd_buy_potential == ">5000"]
        want = [(int(r.c_customer_sk), r.c_last_name, r.c_first_name)
                for r in c.sort_values("c_customer_sk"
                                       ).head(100).itertuples()]
        assert [tuple(r) for r in cs.query(Q[84])] == want

    def test_q85_reason_buckets(self, cs, frames):
        m = frames["web_returns"].merge(
            frames["store_returns"], left_on="wr_item_sk",
            right_on="sr_item_sk")
        m = m.merge(frames["reason"], left_on="sr_reason_sk",
                    right_on="r_reason_sk")
        g = m.groupby("r_reason_desc").agg(
            q=("wr_return_quantity", "mean"),
            a=("wr_return_amt", "mean"))
        want = [(k, pytest.approx(float(r.q), rel=1e-6),
                 pytest.approx(float(r.a), rel=1e-6))
                for k, r in g.sort_index().iterrows()][:100]
        assert [tuple(r) for r in cs.query(Q[85])] == want

    def test_q86_rollup(self, cs, frames):
        m = frames["web_sales"].merge(
            frames["item"], left_on="ws_item_sk",
            right_on="i_item_sk")
        g = m.groupby(["i_category", "i_class"]
                      ).ws_net_profit.sum()
        rows = [(k[0], k[1], _r2(v)) for k, v in g.items()]
        cat = m.groupby("i_category").ws_net_profit.sum()
        rows += [(k, None, _r2(v)) for k, v in cat.items()]
        rows.append((None, None, _r2(m.ws_net_profit.sum())))
        rows.sort(key=lambda r: ((r[0] is None, r[0]),
                                 (r[1] is None, r[1])))
        got = [(r[0], r[1], _r2(r[2])) for r in cs.query(Q[86])]
        assert got == rows

    def test_q97_overlap(self, cs, frames):
        s = set(frames["store_sales"].ss_customer_sk.dropna())
        c = set(frames["catalog_sales"].cs_bill_customer_sk)
        want = (len(s - c), len(c - s), len(s & c))
        assert tuple(cs.query(Q[97])[0]) == want


def test_distributed_queries_ran_on_the_mesh(cs):
    """All distributed TPC-DS runs above must have used the shard_map
    device tier (mesh default-on; zero silent host fallbacks) — the
    TPC-H-style strict assertion, now over the full 99-query set.
    Hybrid plans (device frontier + CN combine) count as mesh."""
    assert cs.fallbacks == [], f"silent host fallbacks: {cs.fallbacks}"
    assert cs.tier_counts.get("host", 0) == 0, cs.tier_counts
    # every scanning SELECT rode the device plane.  'local' is the
    # CN-only tier for FROM-less wrappers (Q9: five scalar init-plans
    # — which DO run on the mesh — under a table-free projection) and
    # 'fqs' is single-shard shipping; neither touches the host
    # exchange tier.
    total = sum(cs.tier_counts.values())
    mesh = cs.tier_counts.get("mesh", 0)
    local = cs.tier_counts.get("local", 0)
    fqs = cs.tier_counts.get("fqs", 0)
    assert mesh >= 1 and mesh + local + fqs == total, cs.tier_counts
    assert local <= 2, cs.tier_counts   # only the Q9/Q61 wrappers
    assert fqs == 0, cs.tier_counts     # no DS plan is single-shard
