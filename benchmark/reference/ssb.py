"""Plain pandas references of the SSB templates the benchmark runs (the
query text as ``connectors/ssb/queries.py`` has it: SSB's own constants,
no substitution parameters). Decimal sums in exact int64 units of
10**-scale; ``accum`` as in ``reference/tpch.py``."""

from __future__ import annotations

import numpy as np
import pandas as pd

from benchmark.reference.common import as_units, cents, lower


def _lo_date(t):
    return t["lineorder"].merge(t["date"], left_on="lo_orderdate",
                                right_on="d_datekey")


def _grouped(j, keys, value, name, accum):
    j = j.assign(**{name: lower(value, accum)})
    g = j.groupby(keys, as_index=False)[name].sum()
    g[name] = as_units(g[name], accum)
    return g


def q2_1(t, accum="int64"):
    j = _lo_date(t)
    p, s = t["part"], t["supplier"]
    j = j.merge(p[p.p_category == "MFGR#12"], left_on="lo_partkey",
                right_on="p_partkey")
    j = j.merge(s[s.s_region == "AMERICA"], left_on="lo_suppkey",
                right_on="s_suppkey")
    g = _grouped(j, ["d_year", "p_brand1"], cents(j.lo_revenue), "revenue",
                 accum)
    return g[["revenue", "d_year", "p_brand1"]]


REFERENCES = {"q2_1": q2_1}
