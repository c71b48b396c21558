"""The frame-vs-oracle comparison rule shared by the differential SQL
tests and ``chip_smoke.py``: columns positional, rows order-free,
floats by tolerance (reduction order differs between engines),
everything else exact. ``TO_THE_CENT`` is the benchmark's decimal rule
(a sum within a tenth of a cent of the oracle's) as ``compare``
keywords, for decimal sums the default tolerance would wave through."""

from __future__ import annotations

import numpy as np
import pandas as pd


#: ``compare(got, want, query, **TO_THE_CENT)``
TO_THE_CENT = {"rtol": 0.0, "atol": 1e-3, "decimals": 4}


def normalize(df: pd.DataFrame, decimals: int = 2) -> pd.DataFrame:
    df = df.copy()
    df.columns = [f"c{i}" for i in range(len(df.columns))]
    for c in df.columns:
        if pd.api.types.is_float_dtype(df[c]):
            df[c] = df[c].astype(np.float64).round(decimals)
        elif pd.api.types.is_datetime64_any_dtype(df[c]):
            df[c] = df[c].astype("datetime64[s]")
        elif df[c].dtype == object or pd.api.types.is_string_dtype(df[c]):
            # engine NULL doubles ride object columns as Python None
            # beside real floats (stddev of a 1-row sample, NULL lag
            # windows); astype(str) would freeze those None values into
            # the literal string 'None' and poison the float compare
            # below. A numeric-or-null object column aligns with the
            # oracle's NaN floats instead.
            vals = df[c].dropna()
            if len(vals) == 0 or vals.map(
                lambda v: isinstance(v, (int, float, np.number))
                and not isinstance(v, bool)
            ).all():
                df[c] = df[c].astype(np.float64).round(decimals)
            else:
                df[c] = df[c].astype(str).str.rstrip()
        else:
            df[c] = pd.to_numeric(df[c]).astype(np.int64)
    return df.sort_values(list(df.columns), kind="stable").reset_index(drop=True)


def compare(got: pd.DataFrame, want: pd.DataFrame, query: str, *,
            rtol: float = 1e-3, atol: float = 0.02, decimals: int = 2):
    assert got.shape == want.shape, (
        f"{query}: shape {got.shape} != oracle {want.shape}"
    )
    if len(got) == 0:
        return
    g = normalize(got, decimals)
    w = normalize(want, decimals)
    for c in g.columns:
        if pd.api.types.is_float_dtype(w[c]):
            if not pd.api.types.is_float_dtype(g[c]):
                # engine NULL doubles surface as None (object column);
                # the oracle has NaN floats — align for allclose
                g[c] = g[c].astype(np.float64)
            np.testing.assert_allclose(
                g[c].to_numpy(), w[c].to_numpy(), rtol=rtol, atol=atol,
                err_msg=f"{query}: column {c}",
            )
        else:
            assert g[c].tolist() == w[c].tolist(), f"{query}: column {c}"
