"""The benchmark's parametrised references against the repo's oracle at
the validation parameters, SF 0.01 (no device is touched: both sides are
pandas over the connector's decoded frames)."""

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import cell as C  # noqa: E402
from benchmark.harness import compare as BC  # noqa: E402

VALIDATION = {
    "tpch/q6": {"year": 1994, "discount": "0.06", "quantity": 24},
    "tpch/q3": {"segment": "BUILDING", "date": "1995-03-15"},
    "tpch/q13": {"word1": "special", "word2": "requests"},
    "ssb/q2_1": {},
}


@pytest.fixture(scope="module")
def frames():
    from presto_tpu.connectors.ssb import SsbConnector
    from presto_tpu.connectors.tpch import TpchConnector

    conns = {"tpch": TpchConnector(sf=0.01, seed=424242),
             "ssb": SsbConnector(sf=0.01, seed=424242)}
    cache: dict = {}

    def get(suite, table):
        if (suite, table) not in cache:
            cache[(suite, table)] = conns[suite].table_pandas(table)
        return cache[(suite, table)]

    return get


@pytest.mark.parametrize("name", sorted(VALIDATION))
def test_reference_agrees_with_the_oracle(name, frames):
    import importlib

    from presto_tpu.oracle.compare import compare
    from presto_tpu.oracle.ssb_oracle import ORACLES as SSB
    from presto_tpu.oracle.tpch_oracle import ORACLES as TPCH

    t = C.load_template(name)
    suite, short = name.split("/")
    tables = {tb: frames(suite, tb) for tb in (
        ("lineitem", "orders", "customer") if suite == "tpch" else
        ("lineorder", "date", "part", "supplier", "customer"))}
    # the reference sees only the columns its template declares
    narrow = {tb: tables[tb][cols] for tb, cols in t["reads"].items()}
    fn = importlib.import_module(
        f"benchmark.reference.{suite}").REFERENCES[t["reference"]]
    got = fn(narrow, **VALIDATION[name]).copy()
    for col, kind in zip(list(got.columns), t["columns"]):
        if kind[0] == "decimal":
            got[col] = got[col].astype(np.float64) / 10 ** kind[1]
    want = {"tpch": TPCH, "ssb": SSB}[suite][short](tables)
    compare(got, want, name)
    # and the control, the same reference with float32 sums, is told
    # apart from it wherever the template sums decimals
    rows = BC.reference_rows(fn(narrow, **VALIDATION[name]), t["columns"])
    ctl = BC.reference_rows(
        fn(narrow, accum="float32", **VALIDATION[name]), t["columns"])
    gap = BC.compare_page(BC.natural_rows(ctl, t["columns"]), rows,
                          t["columns"])
    assert gap["exact_mismatches"] == 0
    # (at SF 0.01 only the ungrouped and coarsely grouped sums outgrow
    # float32's 24 bits; at the cells' SF1 every decimal sum does)
    if name == "tpch/q6":
        assert gap["max_cent_gap"] > 0.5, gap
    # bfloat16 (8 bits) is told apart everywhere a decimal is summed
    if any(k[0] == "decimal" for k in t["columns"]):
        low = BC.reference_rows(
            fn(narrow, accum="bfloat16", **VALIDATION[name]), t["columns"])
        assert BC.compare_page(BC.natural_rows(low, t["columns"]), rows,
                               t["columns"])["max_cent_gap"] > 0.5
