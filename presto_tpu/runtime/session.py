"""Session: the client-facing query surface.

Reference parity: ``Session`` + the statement execution path
(``SqlQueryExecution``: parse -> analyze -> plan -> execute), the
``QueryTracker``/``QueryStateMachine`` lifecycle (QUEUED -> RUNNING ->
FINISHED/FAILED), ``QueryMonitor`` events, and EXPLAIN / EXPLAIN
ANALYZE [SURVEY §2.1, §3.1, §5.1, §5.5; reference tree unavailable,
paths reconstructed]. Single-controller: there is no dispatch/queueing
tier; ``sql()`` drives the full pipeline synchronously and returns a
DataFrame.

Every session auto-registers the ``system`` catalog
(system.runtime_queries / runtime_metrics / runtime_nodes) backed by
its own query history and the process metrics registry.
"""

from __future__ import annotations

import itertools
import threading
import time
import uuid
from typing import Mapping, Optional

from presto_tpu.exec.local_planner import LocalExecutor
from presto_tpu.plan.catalog import Catalog
from presto_tpu.plan.nodes import PlanNode, plan_tree_str
from presto_tpu.plan.prune import prune
from presto_tpu.runtime import trace
from presto_tpu.runtime.errors import UserError, error_code, is_retryable
from presto_tpu.runtime.events import EventDispatcher, QueryHistoryBuffer
from presto_tpu.runtime.lifecycle import QueryManager, on_roomy_stack
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.stats import (
    QueryInfo,
    StatsRecorder,
    render_analyzed_plan,
)
from presto_tpu.runtime.trace import TraceRecorder, TraceStore
from presto_tpu.sql.analyzer import Analyzer
from presto_tpu.sql.parser import parse

_query_seq = itertools.count(1)

#: request-scoped tenant identity, set by the serving front-end
#: (presto_tpu/server/frontend.py) around each tenant's execution so
#: QueryInfo attribution works through one shared session without
#: threading a parameter into every sql()/execute() signature. Falls
#: back to the ``tenant`` session property, then "".
from contextvars import ContextVar

CURRENT_TENANT: ContextVar[Optional[str]] = ContextVar(
    "presto_tpu_current_tenant", default=None
)

#: request-scoped trace context, set by the serving front-end around a
#: submitted query's execution and by the subscription manager around
#: each refresh fire. A mutable dict: {"token": trace token for the
#: query's TraceRecorder (client-supplied X-Presto-Trace / W3C
#: traceparent trace-id, or a subscription-scoped token),
#: "subscription_id": continuous-query id ("" for ad-hoc),
#: "force_trace": record spans even when the session-level
#: trace_enabled property is off (a client that sent a traceparent
#: asked to be traced), "query_id": written BACK by _run_tracked so
#: the front-end can stitch its submit/poll spans onto the query's
#: recorder after the fact}.
REQUEST_TRACE: ContextVar[Optional[dict]] = ContextVar(
    "presto_tpu_request_trace", default=None
)


def _ast_literal_value(node):
    """EXECUTE ... USING argument -> logical Python value (literals
    only — parameters are values, not expressions)."""
    from presto_tpu.sql import ast as A

    if isinstance(node, A.NumberLit):
        return float(node.text) if "." in node.text else int(node.text)
    if isinstance(node, A.StringLit):
        return node.value
    if isinstance(node, (A.DateLit, A.TimestampLit)):
        return node.value  # ISO strings; DataType.to_physical parses
    if isinstance(node, A.UnaryOp) and node.op == "-":
        return -_ast_literal_value(node.operand)
    raise UserError(
        "EXECUTE ... USING arguments must be literals"
    )


class Session:
    def __init__(self, connectors: Mapping[str, object], properties=None,
                 mesh=None, trace_token: Optional[str] = None,
                 memory_pool=None):
        """``mesh=None`` runs single-device (the LocalQueryRunner shape);
        passing a ``jax.sharding.Mesh`` runs every query distributed
        over its ``workers`` axis (the DistributedQueryRunner shape), as
        does the ``mesh_devices`` property where no mesh is passed.
        Session properties override engine defaults per query, the
        reference's SystemSessionProperties rule [SURVEY §5.6].
        ``memory_pool`` shares an explicit ``runtime.memory.MemoryPool``
        across sessions (default: the process-wide pool, or a private
        one when ``memory_pool_bytes`` is set)."""
        from presto_tpu.connectors.memory import MemoryConnector
        from presto_tpu.connectors.system import SystemConnector
        from presto_tpu.runtime.properties import validate_properties

        conns = dict(connectors)
        conns.setdefault("system", SystemConnector(self))
        # the writable catalog: CREATE TABLE AS / INSERT INTO land here
        # (reference: presto-memory as the default test/CTAS target)
        conns.setdefault("memory", MemoryConnector())
        self.catalog = Catalog(conns)
        self.analyzer = Analyzer(self.catalog)
        self.properties = validate_properties(dict(properties or {}))
        if "scan_resident_budget_bytes" in self.properties:
            # (a session that does not set it leaves the stores as they
            # are: the server's approximate sibling shares connectors)
            self._set_scan_resident_budget()
        workers = self.prop("mesh_devices")
        if mesh is None and workers is not None and workers > 1:
            from presto_tpu.parallel.mesh import make_mesh

            mesh = make_mesh(workers)
        self.mesh = mesh
        self.trace_token = trace_token
        self.events = EventDispatcher()
        self.query_history: list[QueryInfo] = []
        #: ring of recent completed QueryInfos behind system.query_history
        #: (a built-in EventListener — the reference's history-store
        #: EventListener plugin shape)
        self.history = QueryHistoryBuffer(self.prop("query_history_limit"))
        self.events.add(self.history)
        #: ring of recent span traces (Session.export_trace /
        #: system.trace_spans); populated when trace_enabled
        self.traces = TraceStore()
        #: flight recorder: bounded ring of failure post-mortems
        #: (runtime/flight.py), auto-captured at run_plan's choke point
        #: whenever a query fails/degrades/retries/overruns; queryable
        #: as system.flight_recorder, exportable via
        #: export_flight_record / `python -m presto_tpu flightrec`
        from presto_tpu.runtime.flight import FlightRecorder

        self.flight = FlightRecorder(self.prop("flight_recorder_limit"))
        #: lifecycle mechanics: admission control, deadlines, fragment
        #: retry, distributed->local degradation (runtime/lifecycle.py)
        self.query_manager = QueryManager(self)
        #: explicit shared memory pool (None: ``pool()`` resolves to
        #: the private pool below or the process-wide one). The private
        #: pool is built EAGERLY — lazy creation would race concurrent
        #: first queries into two pools, doubling the admission bound
        self._memory_pool = memory_pool
        self._private_pool = None
        cap = self.prop("memory_pool_bytes")
        if cap is not None:
            from presto_tpu.runtime.memory import MemoryPool

            self._private_pool = MemoryPool(cap, name="session")
        #: versioned result cache (cache/result_cache.py) — per session:
        #: sessions own private memory catalogs, so equal fingerprints
        #: across sessions do not imply equal data. DDL drops entries
        #: eagerly through the catalog's invalidation listener.
        from presto_tpu.cache.result_cache import ResultCache

        self.result_cache = ResultCache(self.prop("result_cache_max_bytes"))
        self.catalog.add_invalidation_listener(
            self.result_cache.invalidate_table
        )
        #: estimate-vs-actual history keyed by plan fingerprint
        #: (cache/plan_stats.py; system.plan_stats) — invalidated
        #: through the same catalog DDL listeners as the result cache,
        #: so stale history never survives a version bump
        from presto_tpu.cache.plan_stats import PlanStatsStore

        self.plan_stats = PlanStatsStore(self.prop("plan_stats_limit"))
        #: adaptive-execution feedback controller (plan/adaptive.py):
        #: turns plan-stats history into sticky per-(fingerprint, node)
        #: plan decisions, budget-gated against the exec-cache ledger;
        #: its decision ring is queryable as ``system.adaptive``
        from presto_tpu.plan.adaptive import AdaptiveController

        self.adaptive = AdaptiveController()
        self.catalog.add_invalidation_listener(
            self.plan_stats.invalidate_table
        )
        #: serving-layer tenant registry (server/scheduler.FairScheduler
        #: when a QueryServer fronts this session) — the backing store
        #: of system.tenants; None outside the serving layer
        self.tenants = None
        #: tenant SLO tracker (runtime/health.SloTracker, attached by
        #: the serving layer) — the backing store of system.slo; None
        #: outside the serving layer
        self.slo = None
        #: anomaly watchdog (runtime/health.HealthMonitor, armed by the
        #: serving layer) — the backing store of system.health; None
        #: outside the serving layer
        self.health = None
        #: prepared statements (PREPARE name FROM ... / Session.prepare)
        self._prepared: dict[str, object] = {}
        #: plan templates this session has executed at least once —
        #: the query_history ``template_hit`` column's ground truth.
        #: LRU-bounded: a long-lived serving session over unbounded
        #: distinct statements must not grow it forever (evicting a
        #: template only re-marks its NEXT run a miss — observability,
        #: never correctness)
        from collections import OrderedDict

        self._seen_templates: "OrderedDict[str, None]" = OrderedDict()
        self._seen_templates_limit = 4096
        self._tmpl_lock = threading.Lock()
        # every memory-connector write (CTAS store / INSERT commit /
        # DROP) bumps the catalog version even when issued through the
        # Python API rather than SQL DDL — stale metadata or cached
        # results after a direct write are structurally impossible
        mem = conns["memory"]
        self._mem_ddl_hooked = hasattr(mem, "add_ddl_listener")
        if self._mem_ddl_hooked:
            mem.add_ddl_listener(self.catalog.invalidate)

    # ------------------------------------------------------------------
    def prop(self, name: str):
        """Effective value of a session property (override or default)."""
        from presto_tpu.runtime.properties import effective

        return effective(self.properties, name)

    def set_property(self, name: str, value):
        """SET SESSION name = value (typed + validated; unknown names
        rejected, the reference's config-binding rule [SURVEY §5.6])."""
        from presto_tpu.runtime.properties import validate_properties

        self.properties.update(validate_properties({name: value}))
        if name == "query_history_limit":
            # the history ring is sized at construction; a changed
            # limit must take effect, not silently keep the old bound
            self.history.resize(self.prop(name))
        if name == "plan_stats_limit":
            # like the history ring above: a lowered bound must evict
            # immediately, not silently keep the old size until the
            # next recorded query
            self.plan_stats.resize(self.prop(name))
        if name == "flight_recorder_limit":
            # same take-effect rule as the rings above
            self.flight.resize(self.prop(name))
        if name == "scan_resident_budget_bytes":
            self._set_scan_resident_budget()
        if name == "memory_pool_bytes":
            # rebuild the private pool here — not lazily in pool() —
            # so concurrent queries always see exactly one pool
            from presto_tpu.runtime.memory import MemoryPool

            cap = self.prop(name)
            self._private_pool = (
                None if cap is None else MemoryPool(cap, name="session")
            )

    def _set_scan_resident_budget(self) -> None:
        """Hand ``scan_resident_budget_bytes`` to every catalog
        connector that keeps its splits (a connector's ``scan`` takes
        no session, and the mesh's scan reads the same store): bytes a
        device, before the first query sizes a step, since the budget
        comes out of ``device_budget_bytes``."""
        budget = self.prop("scan_resident_budget_bytes") or 0
        for conn in self.catalog.connectors.values():
            store = getattr(conn, "scan_store", None)
            if store is not None:
                store.set_device_budget(budget)

    def show_session(self) -> "list[tuple[str, object, str]]":
        """(name, effective value, description) rows, SHOW SESSION."""
        from presto_tpu.runtime.properties import SESSION_PROPERTIES

        return [
            (d.name, self.prop(d.name), d.description)
            for d in SESSION_PROPERTIES.values()
        ]
    def pool(self):
        """The memory pool this session's queries reserve from: an
        explicit shared pool if one was passed, else the private pool
        built from ``memory_pool_bytes``, else the process-wide pool
        (``runtime.memory.global_pool``). Read-only — pools are built
        in ``__init__``/``set_property``, never here, so concurrent
        queries can race this accessor safely."""
        from presto_tpu.runtime.memory import global_pool

        if self._memory_pool is not None:
            return self._memory_pool
        if self._private_pool is not None:
            return self._private_pool
        return global_pool()

    @property
    def executor(self):
        """A freshly-configured executor reflecting current session
        properties. Queries never share one: ``_run_tracked`` builds its
        own per query (this accessor exists for introspection)."""
        return self._make_executor()

    def _make_executor(self):
        """A fresh executor per query: per-query state (the stats
        recorder) must never live on a shared object, or concurrent /
        nested queries cross-contaminate each other's stats
        (reference parity: per-query SqlQueryExecution objects)."""
        import os

        from presto_tpu.cache.exec_cache import EXEC_CACHE

        # the executable cache is PROCESS-wide: only an explicit
        # per-session override mutates its bound — a session that never
        # touched the knob must not evict other sessions' compiled steps
        if "exec_cache_max_entries" in self.properties:
            EXEC_CACHE.set_max_entries(self.prop("exec_cache_max_entries"))
        pallas = self.prop("pallas_strings")
        if pallas is not None:
            # the string-kernel probe reads the env at trace time;
            # mirror the property there (documented as process-wide)
            # presto-lint: ignore[PT401] -- deliberate documented mirror: the property IS the process-wide env switch (properties.py documents it); tests restore via the conftest guard
            os.environ["PRESTO_TPU_PALLAS"] = "1" if pallas else "0"
        narrow = self.prop("narrow_storage")
        if narrow is not None:
            # connectors read the switch at scan time (spi.narrow_enabled);
            # mirror the property there (documented as process-wide)
            # presto-lint: ignore[PT401] -- deliberate documented mirror: the property IS the process-wide env switch (properties.py documents it); tests restore via the conftest guard
            os.environ["PRESTO_TPU_NARROW"] = "1" if narrow else "0"
        if self.mesh is None:
            budget = self.prop("join_build_budget_bytes")
            return LocalExecutor(
                self.catalog,
                join_build_budget=budget,
                direct_group_limit=self.prop("direct_group_limit"),
                runtime_join_filters=self.prop("runtime_join_filters"),
                scan_sample_fraction=self.prop("approx_scan_fraction"),
                spill_host_budget=self.prop("spill_host_budget_bytes"),
            )
        from presto_tpu.exec.distributed import DistributedExecutor

        return DistributedExecutor(
            self.catalog,
            self.mesh,
            broadcast_limit=self.prop("broadcast_join_row_limit"),
            gather_limit=self.prop("gather_row_limit"),
            direct_group_limit=self.prop("direct_group_limit"),
            join_build_budget=self.prop("join_build_budget_bytes"),
            spill_host_budget=self.prop("spill_host_budget_bytes"),
        )

    def _profiled(self):
        """XLA op-level profiling per query when ``profile_dir`` is set
        (jax.profiler trace -> TensorBoard/xprof), the device-side
        complement to the host-level EXPLAIN ANALYZE node stats
        [SURVEY §5.1 TPU-mapping row]."""
        import contextlib

        d = self.prop("profile_dir")
        if not d:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.trace(d)

    # ------------------------------------------------------------------
    def add_event_listener(self, listener):
        """Register an EventListener (reference: EventListener SPI)."""
        self.events.add(listener)

    def plan(self, sql: str) -> PlanNode:
        from presto_tpu.sql import ast as A

        ast = parse(sql)
        if isinstance(ast, (A.CreateTableAs, A.InsertInto, A.DropTable)):
            raise UserError(
                "DDL statements execute via Session.sql(), not plan()/explain()"
            )
        logical = self.analyzer.analyze(ast)
        if self.analyzer.param_types:
            # catch the unbindable plan at PLAN time: executing it would
            # surface as a KeyError deep inside a traced step (and then
            # be pointlessly retried)
            raise UserError(
                "query contains ? parameters; PREPARE it and EXECUTE "
                "... USING (or Session.prepare/execute)"
            )
        return prune(logical)

    def explain(self, sql: str) -> str:
        """EXPLAIN rendering. With ``plan_templates`` on, the plan is
        rendered as its TEMPLATE — exprs show ``?N`` slots — followed by
        a ``params=[...]`` line binding each slot to this statement's
        literal (the prepared-statement view of the query)."""
        from presto_tpu.sql import ast as A

        stmt = parse(sql)
        if isinstance(stmt, (A.CreateTableAs, A.InsertInto, A.DropTable,
                             A.Prepare, A.ExecuteStmt, A.Deallocate)):
            raise UserError(
                "DDL statements execute via Session.sql(), not plan()/explain()"
            )
        plan, bound = self._plan_binding(stmt)
        hints = self._plan_hints(plan)
        out = plan_tree_str(plan, catalog=self.catalog,
                            plan_hints=hints,
                            agg_bypass=bool(self.prop("partial_agg_bypass")),
                            join_build_budget=self.prop(
                                "join_build_budget_bytes"),
                            adaptive=self._explain_adaptive(plan, hints))
        if bound:
            rendered = ", ".join(
                f"?{i}={dt}:{v!r}" for i, (dt, v) in enumerate(bound)
            )
            out += f"params=[{rendered}]\n"
        return out

    def explain_distributed(self, sql: str) -> str:
        """Fragment/exchange rendering (reference: EXPLAIN (TYPE
        DISTRIBUTED) via PlanFragmenter + PlanPrinter). Fragment
        headers carry observed exchange-partition skew from plan-stats
        history when this plan's fingerprint has recurred — a hot
        partition seen in past runs is plan-visible, not buried in a
        finished query's trace."""
        from presto_tpu.plan.fragmenter import fragment_plan

        ex = self.executor
        plan = self.plan(sql)
        # local sessions render with the same session-property defaults
        # a distributed executor would be built with — no duplicated
        # literals that could drift from execution
        fp = fragment_plan(
            plan, self.catalog,
            getattr(ex, "broadcast_limit",
                    self.prop("broadcast_join_row_limit")),
            getattr(ex, "join_build_budget",
                    self.prop("join_build_budget_bytes")),
        )
        skew = {
            nid: rec.get("skew", 0.0)
            for nid, rec in self._plan_hints(plan).items()
            if rec.get("skew", 0.0) > 1.0
        }
        return fp.render(skew_history=skew or None)

    def explain_analyze(self, sql: str) -> str:
        """Execute and render the plan annotated with actuals
        (reference: EXPLAIN ANALYZE), plus the exchange/cache span
        rollups from the query's trace. A result-cache hit is reported
        in a header line — no execution happened, so node actuals
        render as not-executed."""
        recorder = StatsRecorder()
        t0 = time.perf_counter()
        plan = self.plan(sql)
        _df, info = self._run_tracked(
            sql, plan, recorder, planning=(t0, time.perf_counter() - t0))
        rendered = render_analyzed_plan(
            plan, recorder, tracer=self.traces.for_query(info.query_id)
        )
        if info.cache_hit:
            return "result cache: HIT (no execution)\n" + rendered
        return rendered

    def sql(self, sql: str):
        """Execute and return a pandas DataFrame. DDL/DML statements
        (CREATE TABLE AS / INSERT INTO / DROP TABLE) return a one-row
        summary frame; PREPARE / EXECUTE ... USING / DEALLOCATE PREPARE
        drive the prepared-statement surface."""
        import pandas as pd

        from presto_tpu.sql import ast as A

        # the ``plan`` interval (parse, analyse, template binding) ends
        # before the query's recorder exists: it is annotated here, where
        # it happens, and _run_tracked puts ``planning`` = (start,
        # seconds) on the recorder as the ``plan`` span
        rctx = REQUEST_TRACE.get()
        want = bool(self.prop("collect_node_stats"))
        t0 = time.perf_counter()
        with trace.annotation(
                "plan", (rctx.get("token") if rctx else None)
                or self.trace_token,
                on=bool(self.prop("profile_annotations"))):
            stmt = parse(sql)
            if not isinstance(stmt, (A.Prepare, A.ExecuteStmt, A.Deallocate,
                                     A.CreateTableAs, A.InsertInto,
                                     A.DropTable)):
                plan, bound = self._plan_binding(stmt,
                                                 parameterize=not want)
        planning = (t0, time.perf_counter() - t0)
        if isinstance(stmt, A.Prepare):
            self._prepared[stmt.name] = self._prepare_ast(
                stmt.name, sql, stmt.statement)
            return pd.DataFrame({"prepared": [stmt.name]})
        if isinstance(stmt, A.ExecuteStmt):
            df, _info = self.execute_prepared(
                stmt.name, [_ast_literal_value(a) for a in stmt.args],
                planning=planning,
            )
            return df
        if isinstance(stmt, A.Deallocate):
            if self._prepared.pop(stmt.name, None) is None:
                raise UserError(f"prepared statement not found: {stmt.name}")
            return pd.DataFrame({"deallocated": [stmt.name]})
        if isinstance(stmt, (A.CreateTableAs, A.InsertInto, A.DropTable)):
            return self._run_ddl(sql, stmt)
        df, _info = self._run_with_retries(
            sql, plan, (lambda: StatsRecorder()) if want else (lambda: None),
            planning=planning, bound=bound,
        )
        return df

    def cancel(self, query_id: str, reason: str = "cancelled") -> bool:
        """Cooperatively cancel a live query by ENGINE query id
        (``QueryInfo.query_id``): flips its CancelScope so the next
        checkpoint — fragment entry, morsel push, spill transfer slot,
        batch-gate wake — raises the typed ``QueryCancelled`` and the
        ordinary ``finally`` paths release its pool and host-spill
        reservations. Returns False for unknown/terminal/already-
        cancelled ids; there is nothing to interrupt preemptively — a
        compiled XLA step runs to completion, like every other
        lifecycle control here."""
        return self.query_manager.cancel(query_id, reason)

    # ---- prepared statements / plan templates ------------------------
    def _plan_binding(self, stmt, parameterize: bool = True):
        """Analyze + prune + (when ``plan_templates`` is on)
        auto-parameterize one statement: returns ``(plan, bound)``
        where ``bound`` is the slot-ordered (dtype, logical value)
        binding the statement's own literals supply. A raw statement
        containing explicit ``?`` placeholders has no values to bind —
        PREPARE it instead."""
        plan = prune(self.analyzer.analyze(stmt))
        if self.analyzer.param_types:
            raise UserError(
                "query contains ? parameters; PREPARE it and EXECUTE "
                "... USING (or Session.prepare/execute)"
            )
        if not (parameterize and self.prop("plan_templates")):
            return plan, ()
        from presto_tpu.plan.templates import parameterize_plan

        plan, slots = parameterize_plan(plan, self.catalog)
        return plan, tuple((s.dtype, s.value) for s in slots)

    def _prepare_ast(self, name: str, sql: str, stmt):
        from presto_tpu.plan.templates import (
            PreparedStatement,
            parameterize_plan,
        )
        from presto_tpu.sql import ast as A

        if not isinstance(stmt, (A.Query, A.SetQuery)):
            raise UserError("only queries can be prepared")
        plan = prune(self.analyzer.analyze(stmt))
        user = tuple(sorted(self.analyzer.param_types.items()))
        auto = ()
        if self.prop("plan_templates"):
            plan, auto = parameterize_plan(plan, self.catalog,
                                           start_slot=len(user))
        return PreparedStatement(name, sql, plan, user, auto)

    def prepare(self, sql: str, name: Optional[str] = None):
        """Prepare a query into a plan-template handle: eligible
        literals (and explicit ``?`` placeholders) become typed slots,
        and every ``execute(handle, params)`` binding reuses ONE
        compiled executable — zero re-traces across bindings."""
        stmt = parse(sql)
        handle = self._prepare_ast(name or f"stmt_{len(self._prepared)}",
                                   sql, stmt)
        self._prepared[handle.name] = handle
        return handle

    def execute_prepared(self, handle, params=(), planning=(0.0, 0.0)):
        """Execute a prepared handle (or its registered name) with
        positional ``?`` bindings; returns (DataFrame, QueryInfo)."""
        from presto_tpu.plan.templates import PreparedStatement

        if not isinstance(handle, PreparedStatement):
            h = self._prepared.get(handle)
            if h is None:
                raise UserError(f"prepared statement not found: {handle}")
            handle = h
        bound = handle.bind(list(params))
        return self._run_with_retries(
            handle.sql, handle.plan, lambda: None,
            planning=planning, bound=bound,
        )

    def _owning_catalog(self, table: str):
        for cname, conn in self.catalog.connectors.items():
            if table in conn.tables():
                return cname
        return None

    def _run_ddl(self, sql: str, stmt):
        """Write-path statements against the memory catalog
        (reference: ConnectorPageSink + the coordinator's
        finishInsert — all-or-nothing visibility [SURVEY §5.4]).
        Target names must not shadow tables in read-only catalogs:
        name resolution prefers user connectors, so a shadowed memory
        table would be unreachable."""
        import pandas as pd

        from presto_tpu.sql import ast as A

        mem = self.catalog.connector("memory")
        owner = self._owning_catalog(stmt.name)
        if isinstance(stmt, A.DropTable):
            if owner == "memory":
                mem.drop_table(stmt.name)
            elif owner is not None:
                raise UserError(
                    f"cannot drop {stmt.name}: it belongs to the read-only "
                    f"{owner!r} catalog"
                )
            elif not stmt.if_exists:
                raise UserError(f"table not found in memory catalog: {stmt.name}")
            if not self._mem_ddl_hooked:
                # connectors with the DDL-listener API already bumped
                # the version from inside drop_table — invalidating
                # again would double-count versions and listener fires
                self.catalog.invalidate(stmt.name)
            return pd.DataFrame({"dropped": [stmt.name]})
        # existence checks BEFORE running the (possibly expensive) query
        if isinstance(stmt, A.CreateTableAs) and owner is not None:
            raise UserError(
                f"table already exists in catalog {owner!r}: {stmt.name}"
            )
        if isinstance(stmt, A.InsertInto):
            if owner is None:
                raise UserError(f"table not found: {stmt.name}")
            if owner != "memory":
                raise UserError(
                    f"cannot insert into {stmt.name}: the {owner!r} catalog "
                    "is read-only"
                )
        t0 = time.perf_counter()
        plan, bound = self._plan_binding(stmt.query)
        df, _info = self._run_with_retries(
            sql, plan, lambda: None,
            planning=(t0, time.perf_counter() - t0), bound=bound)
        if isinstance(stmt, A.CreateTableAs):
            rows = mem.create_table(stmt.name, df)
        else:
            rows = mem.insert(stmt.name, df)
        if not self._mem_ddl_hooked:
            self.catalog.invalidate(stmt.name)  # see the drop path
        return pd.DataFrame({"rows": [rows]})

    def execute(self, sql, params=None):
        """Execute returning (DataFrame, QueryInfo). With a
        ``PreparedStatement`` handle (or a registered name) plus
        ``params``, runs the prepared template with those bindings."""
        from presto_tpu.plan.templates import PreparedStatement

        if isinstance(sql, PreparedStatement) or params is not None:
            return self.execute_prepared(sql, params or ())
        t0 = time.perf_counter()
        plan = self.plan(sql)
        return self._run_with_retries(
            sql, plan, StatsRecorder,
            planning=(t0, time.perf_counter() - t0))

    def _run_with_retries(self, sql: str, plan, make_recorder,
                          planning=(0.0, 0.0), bound=()):
        """The engine's whole failure-recovery posture, like the
        reference's: no mid-query recovery — a failed attempt fails the
        query, and recovery is re-running it from the top
        (``query_retries`` session property). Each attempt is tracked
        as its own query with its own fresh recorder — stats from a
        failed attempt must not leak into the retry's QueryInfo."""
        retries = self.prop("query_retries")
        for attempt in range(retries + 1):
            try:
                return self._run_tracked(sql, plan, make_recorder(),
                                         planning=planning, bound=bound)
            except Exception:
                if attempt == retries:
                    raise
                REGISTRY.counter("query.retried").add()

    # ------------------------------------------------------------------
    def _run_tracked(self, sql: str, plan: PlanNode, recorder,
                     planning=(0.0, 0.0), bound=()):
        """Track one execution attempt: QueryInfo lifecycle, span trace
        (when ``trace_enabled``), result-cache lookup, events.
        ``bound`` is the plan template's slot-ordered (dtype, value)
        literal binding (empty for unparameterized plans).
        ``planning`` is the (perf_counter start, seconds) of the
        caller's parse + analyse + bind, where it timed one: the seconds
        are ``QueryInfo.planning_s``, and the interval goes on the
        recorder as the ``plan`` span, beside the ``query`` span whose
        extent it does not change."""
        # request-scoped trace context (serving front-end / subscription
        # manager): the client's trace token overrides the session's,
        # the subscription id rides into history attribution, and the
        # query id flows BACK so the caller can stitch frontend spans
        # onto this query's recorder post-hoc
        rctx = REQUEST_TRACE.get()
        info = QueryInfo(
            query_id=f"q_{next(_query_seq)}_{uuid.uuid4().hex[:8]}",
            sql=sql,
            state="QUEUED",
            created_at=time.time(),
            created_mono=time.monotonic(),
            planning_s=planning[1],
            trace_token=(rctx.get("token") if rctx else None)
            or self.trace_token,
            # serving-layer attribution: request-scoped tenant first
            # (the front-end sets it around each client's execution),
            # then the session-level default property
            tenant=(CURRENT_TENANT.get() or self.prop("tenant") or ""),
            subscription_id=(rctx.get("subscription_id", "")
                             if rctx else ""),
        )
        if rctx is not None:
            rctx["query_id"] = info.query_id
        tracer = None
        token = None
        if self.prop("trace_enabled") or (rctx is not None
                                          and rctx.get("force_trace")):
            tracer = TraceRecorder(
                info.query_id, info.trace_token,
                max_spans=self.prop("trace_max_spans"),
                annotate=bool(self.prop("profile_annotations")),
            )
            token = trace.install(tracer)
        # the cancel scope covers the WHOLE tracked execution — cache
        # lookup, coalescer and batch-gate waits included — so
        # Session.cancel reaches a query before run_plan installs it
        # in the in-flight registry
        self.query_manager.open_scope(info.query_id)
        # this thread's CPU time across the root span: the span minus
        # this minus its sync:* waits is the time the query's thread was
        # runnable and not running (another stream held the interpreter)
        cpu0 = time.thread_time()
        try:
            with trace.span("query", "query", {"query_id": info.query_id}):
                # every frame of the execution in ONE mapped chunk of
                # the interpreter's frame stack (lifecycle.py)
                return on_roomy_stack(lambda: self._run_tracked_inner(
                    sql, plan, recorder, info, bound=bound))
        finally:
            REGISTRY.counter("query.thread_cpu_s").add(
                time.thread_time() - cpu0)
            self.query_manager.close_scope(info.query_id)
            if tracer is not None:
                trace.uninstall(token)
                if planning[1] > 0.0:
                    tracer.add_complete("plan", "planner", *planning)
                self.traces.add(tracer)

    def _run_tracked_inner(self, sql: str, plan: PlanNode, recorder, info,
                           bound=()):
        self.query_history.append(info)
        REGISTRY.counter("query.started").add()
        self.events.query_created(info)
        info.state = "RUNNING"
        info.started_at = time.time()
        info.started_mono = time.monotonic()
        if recorder is not None:
            # deterministic pre-order plan-node ids (trace spans and
            # NodeStats correlate on them)
            recorder.attach_plan(plan)
        from presto_tpu.cache.fingerprint import (
            plan_fingerprint,
            table_versions,
            try_fingerprint,
        )
        from presto_tpu.cache.result_cache import ResultCache
        from presto_tpu.plan.templates import device_params, logical_values

        # ---- binding identity (plan/templates.py) --------------------
        # Two fingerprints with distinct jobs: the plan TEMPLATE's
        # fingerprint (Param slots hash by id + type, never value) is
        # the trace/compile identity — template-hit tracking and the
        # in-flight coalescer's serialization key; the full BINDING
        # fingerprint (template + this query's literal values) keys the
        # result cache and plan stats. Compile work is shared across
        # bindings; results never are.
        # (a content hash of the whole plan: host work of the query's
        # own, named so that it is not the root span's)
        with trace.span("plan:fingerprint", "planner"):
            values = logical_values(bound) if bound else ()
            admissible = ResultCache.admissible(plan, self.catalog)
            cache_ok = (bool(self.prop("result_cache_enabled"))
                        and admissible)
            templates_on = (bool(self.prop("plan_templates"))
                            and recorder is None)
            base_fp = None
            if cache_ok or templates_on:
                base_fp = plan_fingerprint(plan, self.catalog,
                                           self.properties, self.mesh)
            fp = None
            if base_fp is not None:
                fp = (try_fingerprint(("binding", base_fp, values))
                      if bound else base_fp)
        if templates_on and base_fp is not None:
            with self._tmpl_lock:
                info.template_hit = base_fp in self._seen_templates
                self._seen_templates[base_fp] = None
                self._seen_templates.move_to_end(base_fp)
                while len(self._seen_templates) > self._seen_templates_limit:
                    self._seen_templates.popitem(last=False)
            REGISTRY.counter(
                "prepare.template_hit" if info.template_hit
                else "prepare.template_miss").add()
        # ---- versioned result cache (cache/result_cache.py) ----------
        # the binding fingerprint folds in plan-template content,
        # referenced-table catalog versions, mesh shape, codegen
        # session properties, AND the full literal values; admission
        # excludes volatile plans and fault-injected runs. Failed
        # queries never populate: the put sits on the FINISHED path.
        if cache_ok and fp is not None:
            with trace.span("result_cache:lookup", "cache") as sp, \
                    REGISTRY.histogram("cache.result_lookup_s").time():
                hit = self.result_cache.get_entry(fp, self.catalog)
                cached = None if hit is None else hit[0]
                if sp is not None:
                    sp.args["hit"] = cached is not None
            if cached is not None:
                info.state = "FINISHED"
                info.cache_hit = True
                # restore the flag the POPULATING run recorded — an
                # approx-tier session still produces exact results
                # when no scan was sampled, and the hit must not
                # re-label them (the fingerprint folds
                # approx_scan_fraction, so exact and sampled sessions
                # can never share entries)
                info.approximate = hit[1].approximate
                info.output_rows = len(cached)
                info.finished_at = time.time()
                info.finished_mono = time.monotonic()
                REGISTRY.counter("query.completed").add()
                self.events.query_cached(info)
                self.events.query_completed(info)
                return cached, info
        # plan-stats history hints for recurring fingerprints (runs>=2):
        # the adaptive aggregation-strategy inputs, shared by the
        # estimate snapshot, EXPLAIN, and the executors
        hints = self._plan_hints(plan, fp)
        if recorder is not None:
            # snapshot the planner's per-node predictions BEFORE
            # execution (estimate-vs-actual telemetry: estimated rows,
            # sound upper bound + exactness, chosen join/agg strategy,
            # physical widths), keyed by the same stable node ids.
            # AFTER the cache lookup deliberately: a hit skips
            # execution entirely, so paying the per-node estimate walk
            # there would slow exactly the path the cache speeds up
            with trace.span("plan_estimates", "stats"):
                recorder.attach_estimates(
                    plan, self.catalog,
                    join_build_budget=self.prop("join_build_budget_bytes"),
                    plan_hints=hints,
                    agg_bypass=bool(self.prop("partial_agg_bypass")),
                )
        # ---- in-flight coalescing (lifecycle.InflightCoalescer) ------
        # identical concurrent queries (same binding fp) dedupe onto
        # one execution; same-template different-literal queries queue
        # behind the single warm executable via the template slot.
        # Gated by the result-cache admission rules (deterministic
        # plans, no fault injector): a follower's answer is always what
        # its own execution would have produced.
        entry = None
        if templates_on and admissible and fp is not None:
            wait_s = (self.prop("query_max_run_time")
                      or self.prop("admission_queue_timeout_s"))
            lead, payload = self.query_manager.coalescer.lead_or_wait(
                fp, wait_s)
            if lead:
                entry = payload
            elif payload is not None:
                info.state = "FINISHED"
                info.coalesced = True
                info.output_rows = len(payload)
                info.finished_at = time.time()
                info.finished_mono = time.monotonic()
                REGISTRY.counter("prepare.coalesced").add()
                REGISTRY.counter("query.completed").add()
                self.events.query_completed(info)
                return payload, info
            # else: the leader failed or the wait timed out — fall
            # through and execute this query ourselves (uncoalesced)
        try:
            executor = self._make_executor()
            executor.recorder = recorder
            executor.plan_hints = hints
            executor.agg_bypass = bool(self.prop("partial_agg_bypass"))
            # adaptive-execution decisions for THIS query (guarded:
            # property, runs>=2 via hints, fault injector, success
            # recorder, compile budget — plan/adaptive.py)
            executor.adaptive = self._adaptive_decisions(
                plan, fp, hints, executor)
            #: the literal binding as device scalars, threaded through
            #: every jitted step (plan/templates.py; expr.param_scope)
            executor.params = device_params(bound) if bound else ()
            # counters bumped AFTER run_plan returns (query.completed,
            # result-cache populate, plan-stats record, completion
            # events) land in an explicit ``post_run.`` metric bucket —
            # closing the attribution gap run_plan's delta scope cannot
            # see
            import contextlib

            from presto_tpu.runtime.metrics import (
                QueryMetricsDelta,
                install_delta,
                uninstall_delta,
            )

            post = QueryMetricsDelta()
        except BaseException:
            # a failure BEFORE the publishing try/finally below (e.g.
            # executor construction) must still retire the in-flight
            # entry, or every later identical query blocks the full
            # coalesce wait on a key nobody will ever publish
            if entry is not None:
                self.query_manager.coalescer.publish(fp, entry, None)
            raise
        published = None  # the leader's successful result, for waiters
        try:
            # cross-query BATCHED dispatch (server/batcher.py): the
            # bindings queued on this template fuse into one vmapped
            # device dispatch when the template is batchable; falls
            # back to (and interoperates with) the serialized template
            # slot below via the same per-template executor lock
            gate_on = (
                entry is not None and bound and base_fp is not None
                and bool(self.prop("batched_dispatch"))
            )
            if gate_on and not getattr(executor,
                                       "supports_batched_dispatch", False):
                # mesh sessions can't stack a binding axis onto
                # shard_map fragments — loud, then the classic path
                REGISTRY.counter("batch.fallback").add()
                REGISTRY.counter("batch.fallback.distributed").add()
                gate_on = False
            if gate_on:
                with self._profiled():
                    df = self._run_template_batched(
                        executor, plan, info, recorder, base_fp, bound)
                published = df
            else:
                # same-template serialization: first binding compiles,
                # the rest run warm back to back (leaders only;
                # identical-fp followers wait on the entry event, not
                # this lock)
                slot_cm = (
                    self.query_manager.coalescer.template_slot(base_fp)
                    if entry is not None and bound and base_fp is not None
                    else contextlib.nullcontext()
                )
                # the query.execution_s histogram is timed inside
                # run_plan AFTER admission, so pool queue wait lands in
                # queued_s / memory.queued_s, never in execution
                # percentiles
                with self._profiled(), slot_cm:
                    df = self.query_manager.run_plan(executor, plan, info,
                                                     recorder)
                published = df
            token = install_delta(post)
            try:
                info.state = "FINISHED"
                info.output_rows = len(df)
                REGISTRY.counter("query.completed").add()
                if cache_ok and fp is not None:
                    with trace.span("result_cache:populate", "cache"):
                        self.result_cache.put(
                            fp, df, table_versions(plan, self.catalog),
                            max_bytes=self.prop("result_cache_max_bytes"),
                            approximate=info.approximate,
                        )
            finally:
                uninstall_delta(token)
        except Exception as e:
            info.state = "FAILED"
            info.error = f"{type(e).__name__}: {e}"
            info.error_code = error_code(e)
            info.retryable = is_retryable(e)
            token = install_delta(post)
            try:
                REGISTRY.counter("query.failed").add()
                self.events.query_failed(info)
            finally:
                uninstall_delta(token)
            raise
        finally:
            if entry is not None:
                # wake identical-query followers with the result (or,
                # on failure, with nothing — each then runs itself:
                # coalescing batches work, never failures)
                self.query_manager.coalescer.publish(fp, entry, published)
            info.finished_at = time.time()
            info.finished_mono = time.monotonic()
            token = install_delta(post)
            try:
                if recorder is not None:
                    recorder.finalize(plan)
                    info.node_stats = [
                        s.to_dict() for s in recorder.nodes.values()
                    ]
                    if info.state == "FINISHED":
                        self._record_plan_stats(plan, info, recorder, fp)
                # stitch applied adaptive decisions into the session
                # decision log (system.adaptive) — failed runs too: a
                # post-mortem needs to know what adaptivity changed
                ev = getattr(executor, "adaptive_events", None)
                if ev:
                    self.adaptive.note_applied(
                        getattr(executor, "adaptive_fp", None) or fp or "",
                        info.query_id, ev)
                self.events.query_completed(info)
            finally:
                uninstall_delta(token)
            for k, v in post.snapshot().items():
                if v:
                    info.metrics["post_run." + k] = v
        return df, info

    def _run_template_batched(self, executor, plan, info, recorder,
                              base_fp, bound):
        """Run one bound template through the batch gate
        (server/batcher.TemplateBatchGate): enqueue the binding, then
        either get SERVED by a concurrent leader's fused dispatch, or
        LEAD — draining the queued bindings into one vmapped dispatch
        when the template is batchable (``batch.dispatched``), else
        running serially under the template executor lock (the PR 9
        serialization, with the unbatchable reason counted). Patience
        is bounded like the coalescer's wait; on timeout the query
        executes itself unserialized (correct, just unbatched)."""
        gate = self.query_manager.batch_gate
        wait_s = (self.prop("query_max_run_time")
                  or self.prop("admission_queue_timeout_s"))
        member = gate.enqueue(base_fp, bound)
        # lane provenance: the leader's fused dispatch stamps one
        # batch:lane span per member, carrying this origin — linking
        # every vmapped lane back to the submission that enqueued it
        member.origin = info.trace_token or info.query_id
        deadline = (None if wait_s is None
                    else time.monotonic() + float(wait_s))
        gate_t0 = time.perf_counter()
        scope = self.query_manager.scope_of(info.query_id)
        while True:
            if scope is not None:
                # batch-gate cancel checkpoint: a cancelled waiter must
                # abandon its lane (dequeue + deref) on the way out, or
                # a later leader would burn a lane on a departed thread
                try:
                    scope.check("batch-gate-wait")
                except BaseException:
                    gate.abandon(base_fp, member)
                    raise
            remaining = (None if deadline is None
                         else max(0.0, deadline - time.monotonic()))
            # annotated where it happens: the span below is recorded
            # post-hoc, once the verdict is known
            with trace.annotation(
                    "batch:gate_wait", info.trace_token,
                    on=bool(self.prop("profile_annotations"))):
                role, payload = gate.lead_or_wait(base_fp, member, remaining)
            if role != "retry":
                # the batch-gate wait, visible in the trace between
                # submit and dispatch (the serving-tier span chain)
                trace.add_complete(
                    "batch:gate_wait", "driver", gate_t0,
                    time.perf_counter() - gate_t0, {"verdict": role})
            if role == "serve":
                # a leader's batched dispatch computed this binding —
                # same skip-the-lifecycle shape as a coalesced follower
                # (the caller's FINISHED path still populates the
                # result cache under THIS binding's fingerprint)
                info.batched = True
                info.batch_size = int(getattr(member, "batch_size", 0))
                REGISTRY.counter("batch.served").add()
                return payload
            if role == "timeout":
                REGISTRY.counter("batch.gate_timeout").add()
                return self.query_manager.run_plan(executor, plan, info,
                                                   recorder)
            if role == "retry":
                if deadline is not None and time.monotonic() >= deadline:
                    # leaving the gate without a verdict: abandon the
                    # member first, or a later leader would burn a
                    # lane on (and pin a ref for) a departed thread
                    gate.abandon(base_fp, member)
                    REGISTRY.counter("batch.gate_timeout").add()
                    return self.query_manager.run_plan(executor, plan,
                                                       info, recorder)
                continue
            # lead: this thread holds the template executor lock
            members = payload
            try:
                runner = executor
                if len(members) > 1:
                    reason = gate.template_reason(base_fp, plan,
                                                  self.catalog)
                    if reason is None:
                        from presto_tpu.server.batcher import BatchRunner

                        runner = BatchRunner(executor, gate, members,
                                             member, template_key=base_fp)
                    else:
                        REGISTRY.counter("batch.fallback").add()
                        REGISTRY.counter(f"batch.fallback.{reason}").add()
                df = self.query_manager.run_plan(runner, plan, info,
                                                 recorder)
                if runner is not executor:
                    info.batched = bool(
                        getattr(runner, "dispatched_batch", False))
                    if info.batched:
                        info.batch_size = int(
                            getattr(runner, "batch_size", 0))
                return df
            finally:
                gate.finish_lead(base_fp, member, members)

    def _plan_hints(self, plan, fp=None) -> dict:
        """Plan-stats history for this plan, keyed by the LIVE plan
        nodes: ``{id(node): estimate-vs-actual record}`` when the
        plan's fingerprint has recurred (``runs >= 2``), else empty.
        Record node_ids are the recorder's pre-order ids
        (``NodeIds.assign``), so a fresh pre-order walk of the
        shape-identical plan maps them back onto nodes. Best-effort:
        hints are advisory inputs to the adaptive aggregation strategy
        — a failure here must never fail (or even slow) a query."""
        try:
            if len(self.plan_stats) == 0:
                return {}
            from presto_tpu.cache.fingerprint import (
                plan_fingerprint,
                plan_is_deterministic,
            )

            if fp is None:
                if not plan_is_deterministic(plan, self.catalog):
                    return {}
                fp = plan_fingerprint(plan, self.catalog, self.properties,
                                      self.mesh)
            entry = self.plan_stats.get(fp, self.catalog)
            if entry is None or entry.runs < 2:
                return {}
            from presto_tpu.runtime.stats import NodeIds

            ids = NodeIds()
            ids.assign(plan)
            by_id = {}

            def walk(n):
                by_id[ids.of(n)] = n
                for c in n.children:
                    walk(c)

            walk(plan)
            # fresh copies, with the entry's recurrence count attached:
            # consumers (adaptive controller, EXPLAIN) must never
            # mutate — or observe mutation of — the store's records
            return {
                id(by_id[r["node_id"]]): {**r, "runs": entry.runs}
                for r in entry.records if r["node_id"] in by_id
            }
        except Exception:  # noqa: BLE001 — advisory only
            return {}

    def _adaptive_decisions(self, plan, fp, hints, executor,
                            for_render: bool = False) -> dict:
        """Adaptive-execution decision pass for one query (or for an
        EXPLAIN render): plan/adaptive.AdaptiveController over the
        plan-hints history. Best-effort and guarded — the
        ``adaptive_execution`` property, a missing fingerprint, or any
        internal failure yields the baseline (empty) decision map."""
        try:
            if not hints:
                return {}
            if not bool(self.prop("adaptive_execution")):
                return {}
            if not fp:
                # the caller ran without a binding fingerprint (result
                # cache off / stats run): decisions still need the
                # history key, so derive it the way _plan_hints does
                from presto_tpu.cache.fingerprint import (
                    plan_fingerprint,
                    plan_is_deterministic,
                )

                if not plan_is_deterministic(plan, self.catalog):
                    return {}
                fp = plan_fingerprint(plan, self.catalog, self.properties,
                                      self.mesh)
            if not for_render:
                # the stitch in _run_tracked_inner logs applied events
                # under the same history key the decisions used
                executor.adaptive_fp = fp
            return self.adaptive.decide(
                plan, hints, self.catalog, fingerprint=fp,
                nworkers=getattr(executor, "nworkers", 1),
                for_render=for_render,
                recording=bool(self.prop("flight_record_successes")),
            )
        except Exception:  # noqa: BLE001 — adaptivity never fails a query
            return {}

    def _explain_adaptive(self, plan, hints) -> dict:
        """WOULD-BE adaptive decisions for EXPLAIN rendering (no
        logging, no stickiness, no runtime stand-down guards — the
        steady-state plan a recurring query will get)."""
        try:
            if not hints:
                return {}
            from presto_tpu.cache.fingerprint import (
                plan_fingerprint,
                plan_is_deterministic,
            )

            if not plan_is_deterministic(plan, self.catalog):
                return {}
            fp = plan_fingerprint(plan, self.catalog, self.properties,
                                  self.mesh)
            return self._adaptive_decisions(plan, fp, hints, self.executor,
                                            for_render=True)
        except Exception:  # noqa: BLE001 — EXPLAIN renders partial plans
            return {}

    def _record_plan_stats(self, plan, info, recorder, fp) -> None:
        """Persist the run's estimate-vs-actual records into the
        fingerprint-keyed history store (system.plan_stats). Reuses the
        result-cache lookup's fingerprint when one was computed;
        volatile plans (system-table scans) are never recorded — their
        cardinalities describe engine state, not data. Best-effort: a
        recording failure must never fail a FINISHED query."""
        from presto_tpu.cache.fingerprint import (
            plan_fingerprint,
            plan_is_deterministic,
            table_versions,
        )

        try:
            if not recorder.estimates:
                return
            if fp is None:
                if not plan_is_deterministic(plan, self.catalog):
                    return
                fp = plan_fingerprint(plan, self.catalog, self.properties,
                                      self.mesh)
            with trace.span("plan_stats:record", "stats"):
                self.plan_stats.put(
                    fp, info.query_id, table_versions(plan, self.catalog),
                    recorder.estimate_vs_actual(),
                )
        except Exception:  # noqa: BLE001 — observability never fails a query
            REGISTRY.counter("plan_stats.record_errors").add()

    # ------------------------------------------------------------------
    def export_metrics(self, path: Optional[str] = None) -> str:
        """The process metrics registry as OpenMetrics/Prometheus text
        exposition (counters, timers, histogram quantiles — see
        ``runtime.metrics.to_openmetrics``), plus live state gauges the
        counter registry cannot carry: memory-pool occupancy, compiled-
        executable cache entries, and this session's flight-recorder
        ring depth. Returns the text; with ``path``, also writes it
        there (the scrape-file shape; ``python -m presto_tpu metrics``
        is the CLI surface)."""
        from presto_tpu.cache.exec_cache import EXEC_CACHE
        from presto_tpu.runtime.metrics import to_openmetrics

        snap = self.pool().snapshot()
        gauges = {
            "memory_pool_capacity_bytes": snap["capacity_bytes"],
            "memory_pool_reserved_bytes": snap["reserved_bytes"],
            "memory_pool_occupancy": (
                snap["reserved_bytes"] / snap["capacity_bytes"]
                if snap["capacity_bytes"] else 0.0),
            "exec_cache_entries": len(EXEC_CACHE),
            "flight_recorder_depth": len(self.flight),
        }
        # serving-tier health gauges (ISSUE 18): per-device allocator
        # state, tenant SLO burn rates, and the watchdog's latest
        # sample — each best-effort, none may fail the scrape
        if self.prop("device_telemetry"):
            try:
                from presto_tpu.runtime import devices

                gauges.update(devices.gauges())
            except Exception:  # noqa: BLE001
                pass
        for layer in (self.slo, self.health):
            if layer is not None:
                try:
                    gauges.update(layer.gauges())
                except Exception:  # noqa: BLE001
                    pass
        text = to_openmetrics(gauges=gauges)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def export_flight_record(self, path: Optional[str] = None,
                             query_id: Optional[str] = None) -> str:
        """Flight-recorder post-mortems as JSON (runtime/flight.py):
        one record with ``query_id``, else the whole ring (newest
        last). Returns the JSON text; with ``path``, also writes it
        there (``python -m presto_tpu flightrec`` is the CLI surface —
        the dump-on-failure workflow)."""
        text = self.flight.to_json(query_id)
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def export_plan_stats(self, path: Optional[str] = None) -> str:
        """The plan-stats history (system.plan_stats) as JSON — the
        warm-restart half of adaptive execution. A server about to
        restart exports; its successor imports
        (:meth:`import_plan_stats`) and history-driven decisions
        resume at full recurrence counts instead of starting cold.
        Returns the JSON text; with ``path``, also writes it there."""
        text = self.plan_stats.to_json()
        if path is not None:
            with open(path, "w") as f:
                f.write(text)
        return text

    def import_plan_stats(self, path: str) -> int:
        """Merge a previously exported plan-stats history from
        ``path``, returning the number of entries imported. Entries
        are version-checked against the CURRENT catalog's table epochs
        — history recorded against data that has since changed is
        skipped (``plan_stats.import_stale``), and a document in an
        unknown format is refused (UserError)."""
        with open(path) as f:
            text = f.read()
        try:
            return self.plan_stats.load_json(text, catalog=self.catalog)
        except ValueError as e:
            raise UserError(str(e)) from e

    def export_trace(self, path: str, query_id: Optional[str] = None) -> str:
        """Write retained span traces as Chrome ``trace_event`` JSON
        (load in Perfetto / chrome://tracing). ``query_id`` narrows the
        export to one query; default exports every retained trace, one
        pid per query. Returns ``path``."""
        from presto_tpu.runtime.trace import export_chrome_trace

        if query_id is None:
            recorders = self.traces.recorders()
        else:
            rec = self.traces.for_query(query_id)
            if rec is None:
                raise UserError(f"no retained trace for query {query_id!r} "
                                "(trace_enabled off, or evicted)")
            recorders = [rec]
        if not recorders:
            raise UserError(
                "no traces retained (is trace_enabled set to false?)"
            )
        return export_chrome_trace(path, recorders)
