"""Plain pandas references of the TPC-H templates the benchmark runs,
parametrised by the template's substitution parameters.

Decimal sums are computed in exact int64 arithmetic and returned as
integers in units of 10**-scale (the scale each template's .json
declares), so "to the cent" means exactly that. ``accum`` is what
the sums are accumulated in: int64 for the reference, float32 or
bfloat16 for the controls that must come out as not correct.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

from benchmark.reference.common import as_units, cents, lower


def q6(t, year, discount, quantity, accum="int64"):
    li = t["lineitem"]
    disc = cents(li.l_discount)
    d0 = int(round(float(discount) * 100))
    m = ((li.l_shipdate >= np.datetime64(f"{int(year)}-01-01")).to_numpy()
         & (li.l_shipdate < np.datetime64(f"{int(year) + 1}-01-01")).to_numpy()
         & (disc >= d0 - 1) & (disc <= d0 + 1)
         & (cents(li.l_quantity) < int(quantity) * 100))
    revenue = lower(cents(li.l_extendedprice)[m] * disc[m], accum).sum()
    return pd.DataFrame({"revenue": [int(as_units(revenue, accum))]})


def q3(t, segment, date, accum="int64"):
    c, o, li = t["customer"], t["orders"], t["lineitem"]
    day = np.datetime64(date)
    c = c[c.c_mktsegment == segment]
    o = o[o.o_orderdate < day]
    li = li[li.l_shipdate > day]
    li = pd.DataFrame({
        "l_orderkey": li.l_orderkey.to_numpy(),
        "revenue": lower(cents(li.l_extendedprice)
                         * (100 - cents(li.l_discount)), accum)})
    j = li.merge(o.merge(c, left_on="o_custkey", right_on="c_custkey"),
                 left_on="l_orderkey", right_on="o_orderkey")
    g = j.groupby(["l_orderkey", "o_orderdate", "o_shippriority"],
                  as_index=False)["revenue"].sum()
    g["revenue"] = as_units(g.revenue, accum)
    g = g.sort_values(["revenue", "o_orderdate"], ascending=[False, True],
                      kind="stable").head(10)
    return g[["l_orderkey", "revenue", "o_orderdate",
              "o_shippriority"]].reset_index(drop=True)


def q13(t, word1, word2, accum="int64"):
    c, o = t["customer"], t["orders"]
    oo = o[~o.o_comment.str.contains(f"{word1}.*{word2}", regex=True)]
    cnt = (c[["c_custkey"]]
           .merge(oo[["o_custkey", "o_orderkey"]], left_on="c_custkey",
                  right_on="o_custkey", how="left")
           .groupby("c_custkey")["o_orderkey"].count()
           .reset_index(name="c_count"))
    g = cnt.groupby("c_count", as_index=False).size()
    g.columns = ["c_count", "custdist"]
    return g.sort_values(["custdist", "c_count"],
                         ascending=[False, False]).reset_index(drop=True)


REFERENCES = {"q6": q6, "q3": q3, "q13": q13}
