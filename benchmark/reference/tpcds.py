"""Plain pandas references of the TPC-DS templates the benchmark runs:
q67 and q70, ``GROUP BY ROLLUP`` under ``rank() OVER``, parametrised by
the template's month sequence (DMS .. DMS + 11).

A ROLLUP is one ``groupby`` a prefix of its keys; the keys a level
leaves out are ``None`` (object dtype: a NaN would not compare as the
served page's null does). Decimal sums in exact int64 units of 10**-2;
ranks are taken over those integers, so a tie is a tie. ``accum`` as in
``reference/tpch.py``.
"""

from __future__ import annotations

import numpy as np
import pandas as pd

from benchmark.reference.common import as_units, cents, lower


def _months(t, dms, dms_last, cols):
    d = t["date_dim"]
    return d[(d.d_month_seq >= int(dms)) & (d.d_month_seq <= int(dms_last))][
        ["d_date_sk", *cols]]


def _rollup(j, keys, name, accum):
    """One grouped sum of ``name`` per prefix of ``keys``, longest
    first, concatenated: the absent keys None, ``level`` the number of
    keys left out (the statement's sum of ``grouping()`` bits)."""
    parts = []
    for k in range(len(keys), -1, -1):
        if k:
            g = j.groupby(keys[:k], as_index=False, dropna=False)[name].sum()
        else:
            g = pd.DataFrame({name: [j[name].sum()]})
        g[name] = as_units(g[name], accum)
        g = g.astype({c: object for c in keys[:k]})
        for c in keys[k:]:
            g[c] = pd.Series([None] * len(g), dtype=object)
        g["level"] = len(keys) - k
        parts.append(g[[*keys, name, "level"]])
    u = pd.concat(parts, ignore_index=True)
    for c in keys:      # a NULL in the data is the page's null too
        u[c] = u[c].where(u[c].notna(), None)
    return u


def _rank_desc(u, by, name):
    return u.groupby(by, dropna=False)[name].rank(
        ascending=False, method="min").astype(np.int64)


def _first(u, n, keys):
    """The statement's ORDER BY (every key ascending, NULLS LAST where
    a key can be NULL) and LIMIT: stable sorts, last key first."""
    for c, ascending in reversed(keys):
        u = u.sort_values(c, ascending=ascending, na_position="last",
                          kind="stable")
    return u.head(n)


def q67(t, dms, dms_last, accum="int64"):
    ss = t["store_sales"]
    # coalesce(ss_sales_price * ss_quantity, 0): a NULL factor adds 0
    known = (ss.ss_sales_price.notna() & ss.ss_quantity.notna()).to_numpy()
    sales = np.where(
        known, cents(ss.ss_sales_price.fillna(0))
        * ss.ss_quantity.fillna(0).to_numpy(dtype=np.int64), 0)
    j = ss[["ss_sold_date_sk", "ss_item_sk", "ss_store_sk"]].assign(
        sumsales=lower(sales, accum))
    j = j.merge(_months(t, dms, dms_last, ["d_year", "d_qoy", "d_moy"]),
                left_on="ss_sold_date_sk", right_on="d_date_sk")
    j = j.merge(t["store"][["s_store_sk", "s_store_id"]],
                left_on="ss_store_sk", right_on="s_store_sk")
    j = j.merge(t["item"][["i_item_sk", "i_category", "i_class", "i_brand",
                           "i_product_name"]],
                left_on="ss_item_sk", right_on="i_item_sk")
    keys = ["i_category", "i_class", "i_brand", "i_product_name", "d_year",
            "d_qoy", "d_moy", "s_store_id"]
    u = _rollup(j, keys, "sumsales", accum)
    u["rk"] = _rank_desc(u, "i_category", "sumsales")
    u = _first(u[u.rk <= 100], 100,
               [(c, True) for c in (*keys, "sumsales", "rk")])
    return u[[*keys, "sumsales", "rk"]].reset_index(drop=True)


def q70(t, dms, dms_last, accum="int64"):
    ss = t["store_sales"]
    j = ss[["ss_sold_date_sk", "ss_store_sk"]].assign(
        total_sum=lower(cents(ss.ss_net_profit.fillna(0)), accum))
    j = j.merge(_months(t, dms, dms_last, []), left_on="ss_sold_date_sk",
                right_on="d_date_sk")
    j = j.merge(t["store"][["s_store_sk", "s_state", "s_county"]],
                left_on="ss_store_sk", right_on="s_store_sk")
    # the subquery ranks PARTITION BY s_state over GROUP BY s_state: one
    # row a partition, every ranking 1, so `ranking <= 5` keeps every
    # state that sold in the months — the statement as written
    by_state = j.groupby("s_state", as_index=False)["total_sum"].sum()
    by_state["ranking"] = _rank_desc(by_state, "s_state", "total_sum")
    j = j[j.s_state.isin(by_state.s_state[by_state.ranking <= 5])]
    u = _rollup(j, ["s_state", "s_county"], "total_sum", accum)
    u = u.rename(columns={"level": "lochierarchy"})
    # case when grouping(s_county) = 0 then s_state end
    u["parent"] = u.s_state.where(u.lochierarchy == 0, None)
    u["rank_within_parent"] = _rank_desc(u, ["lochierarchy", "parent"],
                                         "total_sum")
    u = _first(u, 100, [("lochierarchy", False), ("parent", True),
                        ("rank_within_parent", True), ("s_state", True),
                        ("s_county", True)])
    return u[["total_sum", "s_state", "s_county", "lochierarchy",
              "rank_within_parent"]].reset_index(drop=True)


REFERENCES = {"q67": q67, "q70": q70}
