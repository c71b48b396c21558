"""Engine-vs-oracle differential tests for all 22 TPC-H queries
(reference parity: AbstractTestQueries + H2QueryRunner diffing
MaterializedResults [SURVEY §4])."""

import pytest

pytestmark = pytest.mark.slow

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.connectors.tpch.queries import QUERIES
from presto_tpu.oracle.compare import compare, normalize  # noqa: F401
from presto_tpu.oracle.tpch_oracle import ORACLES
from presto_tpu.runtime.session import Session

SF = 0.005


@pytest.fixture(scope="module")
def env():
    conn = TpchConnector(sf=SF, units_per_split=1 << 14)
    session = Session({"tpch": conn})
    tables = {name: conn.table_pandas(name) for name in conn.tables()}
    return session, tables


@pytest.mark.parametrize("name", sorted(QUERIES, key=lambda x: int(x[1:])))
def test_tpch_query_matches_oracle(env, name):
    session, tables = env
    got = session.sql(QUERIES[name])
    want = ORACLES[name](tables)
    compare(got, want, name)
