"""The comparison that decides ``correct``: a served result page against
the plain reference's frame.

Rows are compared order-free (sorted by their exact columns), columns by
position and by the kind the template declares:

- ``exact``   integers, strings, dates: equal or a mismatch;
- ``decimal`` the reference gives the exact value as an integer in
              units of 10**-scale; the gap is taken in cents.

Another kind is an error: a number that no limit judges is not compared.

The numbers come back unjudged; the runner holds each against the limit
of its own (``limits.json``).
"""

from __future__ import annotations

KINDS = ("exact", "decimal")


def _norm_exact(v):
    if v is None:
        return ""
    if isinstance(v, float) and v == int(v):
        return int(v)
    if isinstance(v, str):
        # dates arrive as ISO timestamps; trailing pad of fixed-width text
        if len(v) >= 10 and v[4:5] == "-" and v[7:8] == "-" and "T" in v:
            return v[:10]
        return v.rstrip()
    return v


def _number(v) -> float:
    return float(v) if isinstance(v, (int, float)) else float("inf")


def _sort_key(kinds):
    """Rows in their natural units, ordered by the exact columns first
    and by the numbers, rounded, only to break a tie."""
    exact = [i for i, k in enumerate(kinds) if k[0] == "exact"]
    other = [i for i, k in enumerate(kinds) if k[0] != "exact"]

    def key(row):
        return (tuple(str(_norm_exact(row[i])) for i in exact),
                tuple(round(_number(row[i]), 2) for i in other))

    return key


def reference_rows(frame, kinds) -> list:
    """The reference frame as plain rows: decimal columns stay the exact
    integers (units of 10**-scale) the reference computed."""
    cols = list(frame.columns)
    unknown = [k[0] for k in kinds if k[0] not in KINDS]
    if unknown:
        raise ValueError(f"unknown column kind(s) {unknown}; known: {KINDS}")
    if len(cols) != len(kinds):
        raise ValueError(f"reference has {len(cols)} columns, the template "
                         f"declares {len(kinds)}")
    out = []
    for rec in frame.itertuples(index=False, name=None):
        row = []
        for v, k in zip(rec, kinds):
            if k[0] == "decimal":
                row.append(int(v))
            else:
                row.append(v.isoformat()[:10] if hasattr(v, "isoformat")
                           else (int(v) if hasattr(v, "__index__") else v))
        out.append(row)
    return out


def zero() -> dict:
    return {"exact_mismatches": 0, "max_cent_gap": 0.0}


def natural_rows(rows, kinds) -> list:
    """Reference rows with their decimals in natural units (as a result
    page carries them) instead of exact integers of 10**-scale."""
    return [[(v / 10 ** k[1]) if k[0] == "decimal" else v
             for v, k in zip(row, kinds)] for row in rows]


def compare_page(data, want_rows, kinds) -> dict:
    """-> {"exact_mismatches", "max_cent_gap"}."""
    res = zero()
    if data is None or len(data) != len(want_rows) or any(
            len(r) != len(kinds) for r in data):
        res["exact_mismatches"] = max(1, len(want_rows))
        return res
    key = _sort_key(kinds)
    natural = natural_rows(want_rows, kinds)    # for the sort key only
    order_w = sorted(range(len(want_rows)), key=lambda i: key(natural[i]))
    got_sorted = sorted(data, key=key)
    for g, wi in zip(got_sorted, order_w):
        w = want_rows[wi]
        for col, (gv, wv, k) in enumerate(zip(g, w, kinds)):
            if k[0] == "exact":
                if _norm_exact(gv) != _norm_exact(wv):
                    res["exact_mismatches"] += 1
            elif gv is None or not isinstance(gv, (int, float)):
                res["exact_mismatches"] += 1
            else:
                # cents: got * 100 against exact units / 10**(scale-2)
                gap = abs(float(gv) * 100.0 - wv / 10 ** (k[1] - 2))
                if gap != gap:                  # a NaN is no number
                    res["exact_mismatches"] += 1
                elif gap > res["max_cent_gap"]:
                    res["max_cent_gap"] = gap
                    res["worst_cent_column"] = col
    return res


def merge(into: dict, other: dict) -> dict:
    into["exact_mismatches"] += other["exact_mismatches"]
    if other["max_cent_gap"] > into["max_cent_gap"]:
        into["worst_cent_column"] = other.get("worst_cent_column")
    into["max_cent_gap"] = max(into["max_cent_gap"], other["max_cent_gap"])
    return into
