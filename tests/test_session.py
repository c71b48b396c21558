"""Session runtime behaviors (query lifecycle, stats isolation,
session properties, the CLI statement loop).

Reference parity: per-query execution objects (SqlQueryExecution) —
per-query state like the stats recorder must not live on shared
machinery [SURVEY §3.1; round-1 advisor finding]; SystemSessionProperties
typed/validated per-session knobs [SURVEY §5.6]; presto-cli console
[SURVEY §2.1]."""

import pytest

from presto_tpu.connectors.tpch import TpchConnector
from presto_tpu.runtime.session import Session


def test_each_query_gets_a_fresh_executor(monkeypatch):
    s = Session({"tpch": TpchConnector(sf=0.01)})
    seen = []
    orig = Session._make_executor

    def spy(self):
        ex = orig(self)
        seen.append(ex)
        return ex

    monkeypatch.setattr(Session, "_make_executor", spy)
    s.sql("select count(*) c from nation")
    out = s.explain_analyze("select count(*) c from region")
    assert "rows" in out or "Output" in out
    assert len(seen) == 2
    assert seen[0] is not seen[1]
    # the session's template executor never carries a recorder
    assert s.executor.recorder is None


def test_nested_query_from_event_listener_keeps_outer_stats():
    """A listener that issues its own query mid-lifecycle must not
    clobber the outer query's recorded node stats."""
    s = Session({"tpch": TpchConnector(sf=0.01)})
    nested_df = []

    running = []

    class Listener:
        def query_created(self, info):
            pass

        def query_completed(self, info):
            if not running:  # re-entrancy guard
                running.append(True)
                nested_df.append(s.sql("select count(*) c from region"))

    s.add_event_listener(Listener())
    df, info = s.execute("select count(*) c from nation")
    assert int(df["c"][0]) == 25
    assert info.node_stats, "outer query lost its recorded stats"
    assert len(nested_df) == 1


# ---------------------------------------------------------------------------
# session properties (SURVEY §5.6)
# ---------------------------------------------------------------------------


# pallas_join / approx_join: removed with the fused join probe;
# health_ring / batch_max_size: two of the twelve that nothing set and
# that became the constructor defaults they equalled (PR 47) — a
# session that still names one fails at the door, no alias
@pytest.mark.parametrize("name", ["nope", "pallas_join", "approx_join",
                                  "health_ring", "batch_max_size"])
def test_unknown_session_property_rejected(name):
    with pytest.raises(ValueError, match="unknown session property"):
        Session({"tpch": TpchConnector(sf=0.01)}, properties={name: True})


def test_property_type_coercion_and_validation():
    s = Session(
        {"tpch": TpchConnector(sf=0.01)},
        properties={"gather_row_limit": "4096", "collect_node_stats": "true"},
    )
    assert s.prop("gather_row_limit") == 4096
    assert s.prop("collect_node_stats") is True
    with pytest.raises(ValueError, match="must be positive"):
        s.set_property("gather_row_limit", 0)
    with pytest.raises(ValueError, match="cannot interpret"):
        s.set_property("gather_row_limit", "abc")
    # 0 is legal where it means "disabled" (never broadcast)
    s.set_property("broadcast_join_row_limit", 0)
    assert s.prop("broadcast_join_row_limit") == 0


def test_show_session_lists_every_registered_property():
    from presto_tpu.runtime.properties import SESSION_PROPERTIES

    s = Session({"tpch": TpchConnector(sf=0.01)})
    rows = s.show_session()
    assert {r[0] for r in rows} == set(SESSION_PROPERTIES)
    assert all(r[2] for r in rows)  # every property is documented


def test_direct_group_limit_reaches_executor():
    s = Session(
        {"tpch": TpchConnector(sf=0.01)},
        properties={"direct_group_limit": 7},
    )
    assert s.executor.direct_group_limit == 7
    df = s.sql(
        "select l_returnflag, l_linestatus, count(*) c "
        "from lineitem group by l_returnflag, l_linestatus order by 1, 2"
    )
    assert df["c"].sum() > 0


def test_query_retries_rerun_failed_queries():
    s = Session(
        {"tpch": TpchConnector(sf=0.01)},
        properties={"query_retries": 2},
    )
    calls = []
    orig = Session._run_tracked

    def flaky(self, sql, plan, recorder, **kw):
        calls.append(1)
        if len(calls) < 3:
            raise RuntimeError("transient device loss")
        return orig(self, sql, plan, recorder, **kw)

    Session._run_tracked = flaky
    try:
        df = s.sql("select count(*) c from nation")
    finally:
        Session._run_tracked = orig
    assert len(calls) == 3
    assert int(df["c"][0]) == 25


# ---------------------------------------------------------------------------
# CLI statement loop (presto-cli analog)
# ---------------------------------------------------------------------------


def test_cli_statements(capsys):
    from presto_tpu.__main__ import run_statement

    s = Session({"tpch": TpchConnector(sf=0.01)})
    assert run_statement(s, "select count(*) as c from nation;")
    out = capsys.readouterr().out
    assert "25" in out and "1 row" in out

    assert run_statement(s, "show tables;")
    assert "tpch.lineitem" in capsys.readouterr().out

    assert run_statement(s, "set session gather_row_limit = 1234;")
    assert s.prop("gather_row_limit") == 1234
    assert run_statement(s, "show session;")
    assert "gather_row_limit = 1234" in capsys.readouterr().out

    assert run_statement(s, "explain select * from nation;")
    assert "TableScan" in capsys.readouterr().out

    assert run_statement(s, "select no_such_column from nation;")
    assert "error:" in capsys.readouterr().err  # REPL survives bad SQL

    assert not run_statement(s, "quit;")


def test_cli_file_split_respects_quoted_semicolons():
    from presto_tpu.__main__ import split_statements

    stmts = split_statements(
        "select r_name from region where r_name like '%;%';\n"
        "select 1 ; select ';' from region"
    )
    assert stmts[0].strip() == "select r_name from region where r_name like '%;%'"
    assert stmts[1].strip() == "select 1"
    assert stmts[2].strip() == "select ';' from region"


# ---------------------------------------------------------------------------
# mesh_devices: the deployment's worker count, stated as a property
# ---------------------------------------------------------------------------

Q_KEYED = ("select l_returnflag, count(*) c from lineitem "
           "group by l_returnflag")


def test_mesh_devices_unset_or_one_is_the_local_executor():
    from presto_tpu.exec.local_planner import LocalExecutor

    conn = TpchConnector(sf=0.01)
    for props in ({}, {"mesh_devices": 1}):
        s = Session({"tpch": conn}, properties=props)
        assert s.mesh is None
        assert isinstance(s.executor, LocalExecutor)


def test_mesh_devices_keys_as_an_explicit_mesh_does():
    from presto_tpu.cache.fingerprint import plan_fingerprint
    from presto_tpu.exec.distributed import DistributedExecutor
    from presto_tpu.parallel.mesh import make_mesh

    conn = TpchConnector(sf=0.01)
    stated = Session({"tpch": conn}, properties={"mesh_devices": "4"})
    passed = Session({"tpch": conn}, mesh=make_mesh(4))
    assert stated.mesh.devices.shape == (4,)
    assert list(stated.mesh.devices.flat) == list(passed.mesh.devices.flat)
    ex = stated.executor
    assert isinstance(ex, DistributedExecutor) and ex.nworkers == 4
    # the exec cache keys a distributed step by the mesh's fingerprint,
    # the result cache and the templates by the plan's
    assert ex._mesh_fp == passed.executor._mesh_fp
    fps = [plan_fingerprint(s.plan(Q_KEYED), s.catalog, s.properties, s.mesh)
           for s in (stated, passed)]
    assert fps[0] is not None and fps[0] == fps[1]
    local = Session({"tpch": conn})
    assert plan_fingerprint(local.plan(Q_KEYED), local.catalog,
                            local.properties, local.mesh) != fps[0]
    a, b = stated.sql(Q_KEYED), passed.sql(Q_KEYED)
    assert sorted(map(tuple, a.values)) == sorted(map(tuple, b.values))


def test_mesh_devices_beyond_the_backend_is_a_user_error():
    import jax

    from presto_tpu.runtime.errors import UserError

    with pytest.raises(UserError, match="devices, have"):
        Session({"tpch": TpchConnector(sf=0.01)},
                properties={"mesh_devices": len(jax.devices()) + 1})
    with pytest.raises(UserError, match="must be positive"):
        Session({"tpch": TpchConnector(sf=0.01)},
                properties={"mesh_devices": 0})


def test_an_explicit_mesh_wins_over_mesh_devices():
    from presto_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(2)
    s = Session({"tpch": TpchConnector(sf=0.01)}, mesh=mesh,
                properties={"mesh_devices": 4})
    assert s.mesh is mesh and s.executor.nworkers == 2


def test_cli_mesh_goes_through_the_property(monkeypatch, capsys):
    import presto_tpu.__main__ as cli

    built = []
    orig = Session.__init__

    def spy(self, connectors, properties=None, mesh=None, **kw):
        built.append((dict(properties or {}), mesh))
        orig(self, connectors, properties=properties, mesh=mesh, **kw)

    monkeypatch.setattr(Session, "__init__", spy)
    cli.main(["--mesh", "4", "-e", "select count(*) c from nation"])
    assert "25" in capsys.readouterr().out
    assert built == [({"mesh_devices": 4}, None)]
