"""Pandas oracle implementations of the 22 TPC-H queries (validation
parameters), used to check the engine's results on generated data."""

from __future__ import annotations

import datetime

import numpy as np
import pandas as pd

D = datetime.date


def _rev(df):
    return df.l_extendedprice * (1 - df.l_discount)


def q1(t):
    li = t["lineitem"]
    li = li[li.l_shipdate <= pd.Timestamp("1998-12-01") - pd.Timedelta(days=90)]
    g = li.assign(disc_price=_rev(li),
                  charge=_rev(li) * (1 + li.l_tax)).groupby(
        ["l_returnflag", "l_linestatus"], as_index=False).agg(
        sum_qty=("l_quantity", "sum"),
        sum_base_price=("l_extendedprice", "sum"),
        sum_disc_price=("disc_price", "sum"),
        sum_charge=("charge", "sum"),
        avg_qty=("l_quantity", "mean"),
        avg_price=("l_extendedprice", "mean"),
        avg_disc=("l_discount", "mean"),
        count_order=("l_quantity", "size"))
    return g.sort_values(["l_returnflag", "l_linestatus"])


def q2(t):
    p, s, ps, n, r = t["part"], t["supplier"], t["partsupp"], t["nation"], t["region"]
    eu = n.merge(r[r.r_name == "EUROPE"], left_on="n_regionkey",
                 right_on="r_regionkey")
    sup = s.merge(eu, left_on="s_nationkey", right_on="n_nationkey")
    j = ps.merge(sup, left_on="ps_suppkey", right_on="s_suppkey")
    pp = p[(p.p_size == 15) & p.p_type.str.endswith("BRASS")]
    j = j.merge(pp, left_on="ps_partkey", right_on="p_partkey")
    mins = j.groupby("p_partkey")["ps_supplycost"].transform("min")
    j = j[j.ps_supplycost == mins]
    out = j[["s_acctbal", "s_name", "n_name", "p_partkey", "p_mfgr",
             "s_address", "s_phone", "s_comment"]]
    return out.sort_values(["s_acctbal", "n_name", "s_name", "p_partkey"],
                           ascending=[False, True, True, True]).head(100)


def q3(t):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    c = c[c.c_mktsegment == "BUILDING"]
    o = o[o.o_orderdate < pd.Timestamp("1995-03-15")]
    li = li[li.l_shipdate > pd.Timestamp("1995-03-15")]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey")
    g = j.assign(rev=_rev(j)).groupby(
        ["l_orderkey", "o_orderdate", "o_shippriority"], as_index=False) \
        .agg(revenue=("rev", "sum"))
    g = g[["l_orderkey", "revenue", "o_orderdate", "o_shippriority"]]
    return g.sort_values(["revenue", "o_orderdate"],
                         ascending=[False, True]).head(10)


def q4(t):
    o, li = t["orders"], t["lineitem"]
    o = o[(o.o_orderdate >= pd.Timestamp("1993-07-01"))
          & (o.o_orderdate < pd.Timestamp("1993-10-01"))]
    late = li[li.l_commitdate < li.l_receiptdate].l_orderkey.unique()
    o = o[o.o_orderkey.isin(late)]
    return o.groupby("o_orderpriority", as_index=False).agg(
        order_count=("o_orderkey", "size")).sort_values("o_orderpriority")


def q5(t):
    c, o, li, s, n, r = (t["customer"], t["orders"], t["lineitem"],
                         t["supplier"], t["nation"], t["region"])
    o = o[(o.o_orderdate >= pd.Timestamp("1994-01-01"))
          & (o.o_orderdate < pd.Timestamp("1995-01-01"))]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey")
    j = j[j.c_nationkey == j.s_nationkey]
    j = j.merge(n, left_on="s_nationkey", right_on="n_nationkey") \
        .merge(r[r.r_name == "ASIA"], left_on="n_regionkey", right_on="r_regionkey")
    g = j.assign(rev=_rev(j)).groupby("n_name", as_index=False).agg(
        revenue=("rev", "sum"))
    return g.sort_values("revenue", ascending=False)


def q6(t):
    li = t["lineitem"]
    li = li[(li.l_shipdate >= pd.Timestamp("1994-01-01"))
            & (li.l_shipdate < pd.Timestamp("1995-01-01"))
            & (li.l_discount >= 0.05 - 1e-9) & (li.l_discount <= 0.07 + 1e-9)
            & (li.l_quantity < 24)]
    return pd.DataFrame({"revenue": [(li.l_extendedprice * li.l_discount).sum()]})


def q7(t):
    s, li, o, c, n = (t["supplier"], t["lineitem"], t["orders"], t["customer"],
                      t["nation"])
    j = li.merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey") \
        .merge(n.rename(columns=lambda x: x + "_1"), left_on="s_nationkey",
               right_on="n_nationkey_1") \
        .merge(n.rename(columns=lambda x: x + "_2"), left_on="c_nationkey",
               right_on="n_nationkey_2")
    j = j[(((j.n_name_1 == "FRANCE") & (j.n_name_2 == "GERMANY"))
           | ((j.n_name_1 == "GERMANY") & (j.n_name_2 == "FRANCE")))
          & (j.l_shipdate >= pd.Timestamp("1995-01-01"))
          & (j.l_shipdate <= pd.Timestamp("1996-12-31"))]
    j = j.assign(l_year=j.l_shipdate.dt.year, volume=_rev(j))
    g = j.groupby(["n_name_1", "n_name_2", "l_year"], as_index=False).agg(
        revenue=("volume", "sum"))
    g.columns = ["supp_nation", "cust_nation", "l_year", "revenue"]
    return g.sort_values(["supp_nation", "cust_nation", "l_year"])


def q8(t):
    p, s, li, o, c, n, r = (t["part"], t["supplier"], t["lineitem"], t["orders"],
                            t["customer"], t["nation"], t["region"])
    j = li.merge(p[p.p_type == "ECONOMY ANODIZED STEEL"],
                 left_on="l_partkey", right_on="p_partkey") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey") \
        .merge(n.rename(columns=lambda x: x + "_1"), left_on="c_nationkey",
               right_on="n_nationkey_1") \
        .merge(r[r.r_name == "AMERICA"], left_on="n_regionkey_1",
               right_on="r_regionkey") \
        .merge(n.rename(columns=lambda x: x + "_2"), left_on="s_nationkey",
               right_on="n_nationkey_2")
    j = j[(j.o_orderdate >= pd.Timestamp("1995-01-01"))
          & (j.o_orderdate <= pd.Timestamp("1996-12-31"))]
    j = j.assign(o_year=j.o_orderdate.dt.year, volume=_rev(j))
    j["brazil"] = np.where(j.n_name_2 == "BRAZIL", j.volume, 0.0)
    g = j.groupby("o_year", as_index=False).agg(num=("brazil", "sum"),
                                                den=("volume", "sum"))
    g["mkt_share"] = g.num / g.den
    return g[["o_year", "mkt_share"]].sort_values("o_year")


def q9(t):
    p, s, li, ps, o, n = (t["part"], t["supplier"], t["lineitem"],
                          t["partsupp"], t["orders"], t["nation"])
    j = li.merge(p[p.p_name.str.contains("green")], left_on="l_partkey",
                 right_on="p_partkey") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(ps, left_on=["l_partkey", "l_suppkey"],
               right_on=["ps_partkey", "ps_suppkey"]) \
        .merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(n, left_on="s_nationkey", right_on="n_nationkey")
    j = j.assign(o_year=j.o_orderdate.dt.year,
                 amount=_rev(j) - j.ps_supplycost * j.l_quantity)
    g = j.groupby(["n_name", "o_year"], as_index=False).agg(
        sum_profit=("amount", "sum"))
    g.columns = ["nation", "o_year", "sum_profit"]
    return g.sort_values(["nation", "o_year"], ascending=[True, False])


def q10(t):
    c, o, li, n = t["customer"], t["orders"], t["lineitem"], t["nation"]
    o = o[(o.o_orderdate >= pd.Timestamp("1993-10-01"))
          & (o.o_orderdate < pd.Timestamp("1994-01-01"))]
    li = li[li.l_returnflag == "R"]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey") \
        .merge(n, left_on="c_nationkey", right_on="n_nationkey")
    g = j.assign(rev=_rev(j)).groupby(
        ["c_custkey", "c_name", "c_acctbal", "c_phone", "n_name", "c_address",
         "c_comment"], as_index=False).agg(revenue=("rev", "sum"))
    g = g[["c_custkey", "c_name", "revenue", "c_acctbal", "n_name",
           "c_address", "c_phone", "c_comment"]]
    return g.sort_values("revenue", ascending=False).head(20)


def q11(t):
    ps, s, n = t["partsupp"], t["supplier"], t["nation"]
    j = ps.merge(s, left_on="ps_suppkey", right_on="s_suppkey") \
        .merge(n[n.n_name == "GERMANY"], left_on="s_nationkey",
               right_on="n_nationkey")
    j = j.assign(v=j.ps_supplycost * j.ps_availqty)
    total = j.v.sum() * 0.0001
    g = j.groupby("ps_partkey", as_index=False).agg(value=("v", "sum"))
    g = g[g.value > total]
    return g.sort_values("value", ascending=False)


def q12(t):
    o, li = t["orders"], t["lineitem"]
    li = li[li.l_shipmode.isin(["MAIL", "SHIP"])
            & (li.l_commitdate < li.l_receiptdate)
            & (li.l_shipdate < li.l_commitdate)
            & (li.l_receiptdate >= pd.Timestamp("1994-01-01"))
            & (li.l_receiptdate < pd.Timestamp("1995-01-01"))]
    j = li.merge(o, left_on="l_orderkey", right_on="o_orderkey")
    hi = j.o_orderpriority.isin(["1-URGENT", "2-HIGH"])
    g = j.assign(high=hi.astype(np.int64), low=(~hi).astype(np.int64)) \
        .groupby("l_shipmode", as_index=False).agg(
        high_line_count=("high", "sum"), low_line_count=("low", "sum"))
    return g.sort_values("l_shipmode")


def q13(t):
    c, o = t["customer"], t["orders"]
    o = o[~o.o_comment.str.contains("special.*requests", regex=True)]
    j = c.merge(o, left_on="c_custkey", right_on="o_custkey", how="left")
    g = j.groupby("c_custkey", as_index=False).agg(
        c_count=("o_orderkey", "count"))
    g2 = g.groupby("c_count", as_index=False).agg(custdist=("c_count", "size"))
    return g2.sort_values(["custdist", "c_count"], ascending=[False, False])


def q14(t):
    li, p = t["lineitem"], t["part"]
    li = li[(li.l_shipdate >= pd.Timestamp("1995-09-01"))
            & (li.l_shipdate < pd.Timestamp("1995-10-01"))]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    promo = np.where(j.p_type.str.startswith("PROMO"), _rev(j), 0.0)
    return pd.DataFrame({"promo_revenue":
                         [100.0 * promo.sum() / _rev(j).sum()]})


def q15(t):
    li, s = t["lineitem"], t["supplier"]
    li = li[(li.l_shipdate >= pd.Timestamp("1996-01-01"))
            & (li.l_shipdate < pd.Timestamp("1996-04-01"))]
    rev = li.assign(r=_rev(li)).groupby("l_suppkey", as_index=False).agg(
        total_revenue=("r", "sum"))
    mx = rev.total_revenue.max()
    j = s.merge(rev[np.isclose(rev.total_revenue, mx)], left_on="s_suppkey",
                right_on="l_suppkey")
    return j[["s_suppkey", "s_name", "s_address", "s_phone",
              "total_revenue"]].sort_values("s_suppkey")


def q16(t):
    ps, p, s = t["partsupp"], t["part"], t["supplier"]
    bad = s[s.s_comment.str.contains("Customer.*Complaints", regex=True)].s_suppkey
    p = p[(p.p_brand != "Brand#45")
          & ~p.p_type.str.startswith("MEDIUM POLISHED")
          & p.p_size.isin([49, 14, 23, 45, 19, 3, 36, 9])]
    j = ps.merge(p, left_on="ps_partkey", right_on="p_partkey")
    j = j[~j.ps_suppkey.isin(bad)]
    g = j.groupby(["p_brand", "p_type", "p_size"], as_index=False).agg(
        supplier_cnt=("ps_suppkey", "nunique"))
    return g[["p_brand", "p_type", "p_size", "supplier_cnt"]].sort_values(
        ["supplier_cnt", "p_brand", "p_type", "p_size"],
        ascending=[False, True, True, True])


def q17(t):
    li, p = t["lineitem"], t["part"]
    pp = p[(p.p_brand == "Brand#23") & (p.p_container == "MED BOX")]
    j = li.merge(pp, left_on="l_partkey", right_on="p_partkey")
    avg_qty = li.groupby("l_partkey")["l_quantity"].mean()
    j = j[j.l_quantity < 0.2 * j.l_partkey.map(avg_qty)]
    return pd.DataFrame({"avg_yearly": [j.l_extendedprice.sum() / 7.0]})


def q18(t):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    big = li.groupby("l_orderkey")["l_quantity"].sum()
    big = big[big > 300].index
    j = li[li.l_orderkey.isin(big)] \
        .merge(o, left_on="l_orderkey", right_on="o_orderkey") \
        .merge(c, left_on="o_custkey", right_on="c_custkey")
    g = j.groupby(["c_name", "c_custkey", "o_orderkey", "o_orderdate",
                   "o_totalprice"], as_index=False).agg(sq=("l_quantity", "sum"))
    return g.sort_values(["o_totalprice", "o_orderdate"],
                         ascending=[False, True]).head(100)


def q19(t):
    li, p = t["lineitem"], t["part"]
    j = li.merge(p, left_on="l_partkey", right_on="p_partkey")
    j = j[j.l_shipmode.isin(["AIR", "AIR REG"])
          & (j.l_shipinstruct == "DELIVER IN PERSON")]
    b1 = ((j.p_brand == "Brand#12")
          & j.p_container.isin(["SM CASE", "SM BOX", "SM PACK", "SM PKG"])
          & (j.l_quantity >= 1) & (j.l_quantity <= 11)
          & (j.p_size >= 1) & (j.p_size <= 5))
    b2 = ((j.p_brand == "Brand#23")
          & j.p_container.isin(["MED BAG", "MED BOX", "MED PKG", "MED PACK"])
          & (j.l_quantity >= 10) & (j.l_quantity <= 20)
          & (j.p_size >= 1) & (j.p_size <= 10))
    b3 = ((j.p_brand == "Brand#34")
          & j.p_container.isin(["LG CASE", "LG BOX", "LG PACK", "LG PKG"])
          & (j.l_quantity >= 20) & (j.l_quantity <= 30)
          & (j.p_size >= 1) & (j.p_size <= 15))
    sel = j[b1 | b2 | b3]
    # SQL SUM over zero rows is NULL, not 0
    return pd.DataFrame({"revenue": [_rev(sel).sum() if len(sel) else np.nan]})


def q20(t):
    s, n, ps, p, li = (t["supplier"], t["nation"], t["partsupp"], t["part"],
                       t["lineitem"])
    forest = p[p.p_name.str.startswith("forest")].p_partkey
    li4 = li[(li.l_shipdate >= pd.Timestamp("1994-01-01"))
             & (li.l_shipdate < pd.Timestamp("1995-01-01"))]
    half = li4.groupby(["l_partkey", "l_suppkey"])["l_quantity"].sum() * 0.5
    psf = ps[ps.ps_partkey.isin(forest)].copy()
    key = list(zip(psf.ps_partkey, psf.ps_suppkey))
    psf["threshold"] = [half.get(k, np.nan) for k in key]
    psf = psf[psf.ps_availqty > psf.threshold]
    sup = s[s.s_suppkey.isin(psf.ps_suppkey)] \
        .merge(n[n.n_name == "CANADA"], left_on="s_nationkey",
               right_on="n_nationkey")
    return sup[["s_name", "s_address"]].sort_values("s_name")


def q21(t):
    s, li, o, n = t["supplier"], t["lineitem"], t["orders"], t["nation"]
    l1 = li[li.l_receiptdate > li.l_commitdate]
    j = l1.merge(o[o.o_orderstatus == "F"], left_on="l_orderkey",
                 right_on="o_orderkey") \
        .merge(s, left_on="l_suppkey", right_on="s_suppkey") \
        .merge(n[n.n_name == "SAUDI ARABIA"], left_on="s_nationkey",
               right_on="n_nationkey")
    # exists: another supplier on the same order
    multi = li.groupby("l_orderkey")["l_suppkey"].nunique()
    j = j[j.l_orderkey.map(multi) > 1]
    # not exists: another supplier late on the same order
    late_multi = l1.groupby("l_orderkey")["l_suppkey"].nunique()
    j = j[j.l_orderkey.map(late_multi).fillna(0) == 1]
    g = j.groupby("s_name", as_index=False).agg(numwait=("l_orderkey", "size"))
    return g.sort_values(["numwait", "s_name"], ascending=[False, True]).head(100)


def q22(t):
    c, o = t["customer"], t["orders"]
    codes = ["13", "31", "23", "29", "30", "18", "17"]
    cc = c[c.c_phone.str[:2].isin(codes)]
    avg_bal = cc[cc.c_acctbal > 0.0].c_acctbal.mean()
    cc = cc[(cc.c_acctbal > avg_bal) & ~cc.c_custkey.isin(o.o_custkey)]
    g = cc.assign(cntrycode=cc.c_phone.str[:2]).groupby(
        "cntrycode", as_index=False).agg(numcust=("cntrycode", "size"),
                                         totacctbal=("c_acctbal", "sum"))
    return g.sort_values("cntrycode")


ORACLES = {i: globals()[f"q{i}"] for i in range(1, 23)}
