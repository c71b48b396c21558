"""The TPC-DS suite of the benchmark (q67, q70: GROUP BY ROLLUP under
rank() OVER): its plain references against the repo's oracle at SF 0.01,
the float32 control of its cell, and the comparison's handling of a
ROLLUP's NULL keys. No device is touched: every side is pandas over the
connector's decoded frames."""

import importlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import compare as BC  # noqa: E402

CELL = "tpcds_sf1_rollup_rank_1s"
TEMPLATES = ("tpcds/q67", "tpcds/q70")


@pytest.fixture(scope="module")
def spec():
    return C.load_cell(CELL)


@pytest.fixture(scope="module")
def tables():
    from presto_tpu.connectors.tpcds import TpcdsConnector

    conn = TpcdsConnector(sf=0.01, seed=424242)
    return {t: conn.table_pandas(t)
            for t in ("store_sales", "date_dim", "store", "item")}


def _reference(spec, tables, name, **kw):
    t = spec["templates"][name]
    # the reference sees only the columns its template declares
    narrow = {tb: tables[tb][cols] for tb, cols in t["reads"].items()}
    fn = importlib.import_module(
        f"benchmark.reference.{t['suite']}").REFERENCES[t["reference"]]
    return fn(narrow, **C.binding(spec["traffic"], name, 0), **kw)


@pytest.mark.parametrize("name", TEMPLATES)
def test_the_template_is_the_programs_statement(name, spec):
    """The SQL is connectors/tpcds/queries.py's, letter for letter, with
    the month sequence bound from the traffic file."""
    from presto_tpu.connectors.tpcds.queries import QUERIES

    t = spec["templates"][name]
    assert "{dms}" in t["sql"] and "{dms_last}" in t["sql"]
    sql = C.render_sql(t, C.binding(spec["traffic"], name, 0))
    assert sql.strip() == QUERIES[name.split("/")[1]].strip()


@pytest.mark.parametrize("name", TEMPLATES)
def test_reference_agrees_with_the_oracle(name, spec, tables):
    from presto_tpu.oracle.compare import compare
    from presto_tpu.oracle.tpcds_oracle import ORACLES

    kinds = spec["templates"][name]["columns"]
    got = _reference(spec, tables, name)
    assert len(got) > 0
    # a ROLLUP's absent keys are None, never NaN
    assert any(v is None for v in got.iloc[:, 1])
    assert not any(isinstance(v, float) and v != v
                   for col in got.columns for v in got[col])
    rows = BC.reference_rows(got, kinds)
    frame = got.copy()
    for col, kind in zip(list(frame.columns), kinds):
        if kind[0] == "decimal":
            frame[col] = frame[col].astype(np.float64) / 10 ** kind[1]
    oracle = ORACLES[name.split("/")[1]](tables)
    compare(frame, oracle, name)      # in the statement's ORDER BY
    # and to the row and the cent, as a served page is held to it
    page = json.loads(oracle.to_json(orient="values"))
    gap = BC.compare_page(page, rows, kinds)
    assert gap["exact_mismatches"] == 0 and gap["max_cent_gap"] < 0.01, gap
    # the float32 control is told apart on the cents (the grand total
    # alone is 3.7e8 cents at SF 0.01), bfloat16 everywhere
    for accum in ("float32", "bfloat16"):
        ctl = BC.reference_rows(_reference(spec, tables, name, accum=accum),
                                kinds)
        gap = BC.compare_page(BC.natural_rows(ctl, kinds), rows, kinds)
        assert gap["max_cent_gap"] > 0.5 or gap["exact_mismatches"], gap


def test_the_cells_float32_control_is_not_correct(spec, tables):
    """runner.py's control at SF 0.01: the references with float32 sums
    put in the program's place come out as not correct."""
    from benchmark.harness import runner

    frames = {tb: tables[tb] for t in spec["templates"].values()
              for tb in t["reads"]}
    want = runner.reference_rows(spec, frames)
    assert spec["traffic"]["control"] == "float32"

    def judged(accum):
        fake = [{"ok": True, "template": t, "binding": i,
                 "data": BC.natural_rows(
                     rows, spec["templates"][t]["columns"])}
                for (t, i), rows in runner.reference_rows(
                    spec, frames, accum=accum).items()]
        numbers = runner.compare_all(spec, fake, want)
        numbers.update(failed_queries=0, approximate_pages=0,
                       interpret_kernels=0, fallback_counters=0)
        return runner.judge(numbers)

    ok, table = judged(None)
    assert ok and table["max_cent_gap"]["value"] < 1e-6
    ok, table = judged("float32")
    assert not ok
    assert not (table["max_cent_gap"]["ok"]
                and table["exact_mismatches"]["ok"])


KINDS = [["exact"], ["exact"], ["decimal", 2], ["exact"]]
WANT = [["Books", None, 1234567, 1], ["Books", "accent", 250, 2],
        [None, None, 9999999, 1]]


def test_a_page_with_null_keys_compares_with_none_keys():
    # a served page carries a subtotal row's absent keys as JSON null
    got = [[None, None, 99999.99, 1], ["Books", "accent", 2.5, 2],
           ["Books", None, 12345.67, 1]]
    res = BC.compare_page(got, WANT, KINDS)
    assert res["exact_mismatches"] == 0 and res["max_cent_gap"] < 1e-6
    # a key where a NULL is wanted, and a NULL where a key is
    for bad in ([got[0], got[1], ["Books", "accent", 12345.67, 1]],
                [got[0], [None, None, 2.5, 2], got[2]]):
        assert BC.compare_page(bad, WANT, KINDS)["exact_mismatches"] >= 1


def test_a_nan_key_in_a_reference_is_an_error_not_a_null():
    """A reference that hands NaN for an absent key (what pandas makes
    of a missing value in a numeric column) does not compare: it has to
    hand None."""
    with pytest.raises(ValueError):
        BC.compare_page([[None, None, 99999.99, 1]],
                        [[None, float("nan"), 9999999, 1]], KINDS)
