"""The comparison that decides ``correct``."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness import compare  # noqa: E402

KINDS = [["exact"], ["decimal", 4], ["decimal", 2], ["exact"]]
WANT = [["A", 1234567, 250, "1995-03-13"], ["B", 50, 100, "1996-01-01"]]


def page(rows):
    return [list(r) for r in rows]


def test_equal_page_in_another_row_order():
    got = page([["B", 0.005, 1.0, "1996-01-01T00:00:00.000"],
                ["A", 123.4567, 2.5, "1995-03-13T00:00:00.000"]])
    assert compare.compare_page(got, WANT, KINDS) == {
        "exact_mismatches": 0, "max_cent_gap": pytest.approx(0, abs=1e-9)}


@pytest.mark.parametrize("row, key, least", [
    (["A", 123.4667, 2.5, "1995-03-13"], "max_cent_gap", 0.99),
    (["A", 123.4567, 2.53, "1995-03-13"], "max_cent_gap", 2.99),
    (["A", float("nan"), 2.5, "1995-03-13"], "exact_mismatches", 1),
    (["A", 123.4567, 2.5, "1995-03-14"], "exact_mismatches", 1),
    (["A", None, 2.5, "1995-03-13"], "exact_mismatches", 1),
])
def test_each_kind_of_gap_is_seen(row, key, least):
    got = page([row, ["B", 0.005, 1.0, "1996-01-01"]])
    assert compare.compare_page(got, WANT, KINDS)[key] >= least


@pytest.mark.parametrize("got", [None, [], [["A", 1.0, 2.5]],
                                 [["A", 1.0, 2.5, "x"]] * 3])
def test_a_page_of_another_shape_is_a_mismatch(got):
    assert compare.compare_page(got, WANT, KINDS)["exact_mismatches"] > 0


def test_reference_rows_and_merge():
    import numpy as np
    import pandas as pd

    f = pd.DataFrame({"k": ["A"], "d": np.array([1234567], np.int64),
                      "f": np.array([250], np.int64), "day": pd.to_datetime(["1995-03-13"])})
    assert compare.reference_rows(f, KINDS) == [
        ["A", 1234567, 250, "1995-03-13"]]
    with pytest.raises(ValueError):
        compare.reference_rows(f, KINDS[:2])
    # a kind that no limit judges is refused, not passed over
    with pytest.raises(ValueError, match="unknown column kind"):
        compare.reference_rows(f, [["exact"], ["decimal", 4], ["float"],
                                   ["exact"]])
    total = {"exact_mismatches": 1, "max_cent_gap": 0.1}
    compare.merge(total, {"exact_mismatches": 2, "max_cent_gap": 0.25,
                          "worst_cent_column": 1})
    assert total == {"exact_mismatches": 3, "max_cent_gap": 0.25,
                     "worst_cent_column": 1}
