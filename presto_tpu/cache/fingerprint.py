"""Canonical content-based fingerprints for plans, exprs, and configs.

Reference parity: the canonicalization half of plan/expression caching
(Presto keys compiled page functions on canonical ``RowExpression``
equality, and RaptorX keys fragment results on plan subtree + table
version) [SURVEY §2.1; reference tree unavailable].

Everything here hashes by VALUE, never by identity:

- plan nodes / exprs / operator configs are frozen dataclasses — they
  serialize field-by-field with a class tag;
- ``Dictionary`` columns hash by their *content* (the sorted value
  tuple), not the object — the identity-hash convention that keeps
  ``jax.jit`` signature caches stable (batch.py) is exactly wrong for
  cross-query keys, where two scans of the same table build distinct
  but equal dictionary objects;
- tables contribute (connector, name, catalog version), so any DDL
  that bumps the version changes every fingerprint that read the
  table — result-cache invalidation falls out of the key itself.

The serialization is tag-length-value into one sha256, so nested
structures cannot collide by concatenation ambiguity.
"""

from __future__ import annotations

import dataclasses
import enum
import hashlib
from typing import Iterable, Optional

import numpy as np

from presto_tpu.batch import Dictionary

#: scalar functions whose value is not a pure function of their inputs
#: (none are registered today — the engine has no now()/random() yet —
#: but the result cache checks the plan against this set so the first
#: volatile function added cannot silently serve stale results).
NONDETERMINISTIC_FNS = frozenset({"now", "random", "rand", "uuid",
                                  "current_timestamp", "current_date"})

#: session properties that change the traced/compiled computation or
#: its results — these feed the plan fingerprint. Observability knobs
#: (collect_node_stats, profile_dir) and retry policy deliberately do
#: not: they do not change what a query computes.
CODEGEN_PROPERTIES = (
    "broadcast_join_row_limit",
    "gather_row_limit",
    "join_build_budget_bytes",
    "direct_group_limit",
    "pallas_strings",
    # runtime_join_filters is deliberately NOT here — it is bit-identical
    # to its fallback.
    # approx_scan_fraction < 1 drops splits (sampled scans): sampled and
    # exact runs must never share cached results
    "approx_scan_fraction",
    # narrow_storage is deliberately NOT here: the fingerprint folds the
    # RESOLVED physical scan schemas (physical_scan_schemas below), which
    # capture the switch through the types it resolves to — keying on the
    # raw property would make an explicit narrow_storage=true session
    # miss caches shared with a default-on session of identical plans.
)


class Unfingerprintable(TypeError):
    """An object with no canonical content serialization reached the
    fingerprinter (e.g. an open file, a raw callable). Callers treat
    the enclosing plan/config as uncacheable rather than guessing."""


def dictionary_fingerprint(d: Dictionary) -> str:
    """Content hash of an ordered dictionary, cached on the object
    (dictionaries are immutable after construction; ``_bytes_mats`` is
    its materialization cache)."""
    fp = d._bytes_mats.get("content_fp")
    if fp is None:
        h = hashlib.sha256()
        for v in d.values.tolist():
            b = v.encode("utf-8", "surrogatepass")
            h.update(len(b).to_bytes(4, "little"))
            h.update(b)
        fp = h.hexdigest()
        d._bytes_mats["content_fp"] = fp
    return fp


def _canon(obj, h) -> None:
    """Feed ``obj``'s canonical tag-length-value serialization to ``h``."""
    if obj is None:
        h.update(b"N")
    elif obj is True:
        h.update(b"T")
    elif obj is False:
        h.update(b"F")
    elif isinstance(obj, (int, np.integer)):
        b = str(int(obj)).encode()
        h.update(b"i" + len(b).to_bytes(4, "little") + b)
    elif isinstance(obj, (float, np.floating)):
        b = float(obj).hex().encode()
        h.update(b"f" + len(b).to_bytes(4, "little") + b)
    elif isinstance(obj, str):
        b = obj.encode("utf-8", "surrogatepass")
        h.update(b"s" + len(b).to_bytes(4, "little") + b)
    elif isinstance(obj, bytes):
        h.update(b"b" + len(obj).to_bytes(4, "little") + obj)
    elif isinstance(obj, enum.Enum):
        _canon(type(obj).__name__, h)
        _canon(obj.name, h)
    elif isinstance(obj, Dictionary):
        h.update(b"D")
        _canon(dictionary_fingerprint(obj), h)
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        h.update(b"C")
        _canon(type(obj).__name__, h)
        for f in dataclasses.fields(obj):
            _canon(f.name, h)
            _canon(getattr(obj, f.name), h)
        h.update(b".")
    elif isinstance(obj, (tuple, list)):
        h.update(b"(")
        for x in obj:
            _canon(x, h)
        h.update(b")")
    elif isinstance(obj, (set, frozenset)):
        h.update(b"{")
        for x in sorted(fingerprint(x) for x in obj):
            _canon(x, h)
        h.update(b"}")
    elif isinstance(obj, dict):
        h.update(b"[")
        for k in sorted(obj, key=repr):
            _canon(k, h)
            _canon(obj[k], h)
        h.update(b"]")
    elif isinstance(obj, np.generic):
        # remaining numpy scalar kinds (datetime64 literals etc.):
        # repr is canonical for a given dtype+value
        _canon(str(obj.dtype), h)
        _canon(repr(obj), h)
    elif isinstance(obj, np.ndarray):
        if obj.dtype.hasobject:
            # tobytes() on object arrays serializes element POINTERS —
            # identity, not content. Uncacheable, never mis-keyed.
            raise Unfingerprintable("object-dtype ndarray")
        _canon(str(obj.dtype), h)
        _canon(obj.shape, h)
        h.update(np.ascontiguousarray(obj).tobytes())
    elif isinstance(obj, type):
        _canon(f"{obj.__module__}.{obj.__qualname__}", h)
    else:
        raise Unfingerprintable(
            f"no canonical serialization for {type(obj).__name__}"
        )


def fingerprint(*parts) -> str:
    """sha256 hex digest of the parts' canonical serialization."""
    h = hashlib.sha256()
    for p in parts:
        _canon(p, h)
    return h.hexdigest()


def try_fingerprint(*parts) -> Optional[str]:
    """``fingerprint`` that answers None for uncacheable content."""
    try:
        return fingerprint(*parts)
    except Unfingerprintable:
        return None


def expr_fingerprint(expr) -> str:
    """Content hash of one expression tree (frozen Expr dataclasses)."""
    return fingerprint(expr)


# ---------------------------------------------------------------------------
# plan-level fingerprints
# ---------------------------------------------------------------------------


def referenced_tables(plan) -> "tuple[tuple[str, str], ...]":
    """All (connector, table) pairs scanned anywhere under ``plan``,
    deduped, in deterministic order."""
    from presto_tpu.plan import nodes as N

    out: dict[tuple[str, str], None] = {}

    def walk(node):
        if isinstance(node, N.TableScan):
            out[(node.connector, node.table)] = None
        for c in node.children:
            walk(c)

    walk(plan)
    return tuple(sorted(out))


def _walk_exprs(obj, found: set) -> None:
    from presto_tpu.expr import Call

    if isinstance(obj, Call):
        found.add(obj.fn)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        for f in dataclasses.fields(obj):
            _walk_exprs(getattr(obj, f.name), found)
    elif isinstance(obj, (tuple, list)):
        for x in obj:
            _walk_exprs(x, found)


def plan_functions(plan) -> frozenset:
    """Every scalar-function name appearing anywhere in the plan tree
    (predicates, projections, keys, agg inputs)."""
    found: set = set()
    _walk_exprs(plan, found)
    return frozenset(found)


def plan_is_deterministic(plan, catalog) -> bool:
    """True when re-running the plan against unchanged tables must
    produce the same rows: no volatile scalar functions, and no scans
    of volatile connectors (system tables change between calls by
    definition). Result-cache admission rule #1."""
    if plan_functions(plan) & NONDETERMINISTIC_FNS:
        return False
    for cname, _table in referenced_tables(plan):
        conn = catalog.connectors.get(cname)
        if conn is None or getattr(conn, "volatile", False):
            return False
    return True


def table_versions(plan, catalog) -> "tuple[tuple[str, int], ...]":
    """(table, catalog version) for every referenced table — the
    result cache stores these at populate time and re-checks them at
    lookup (a DDL bump anywhere forces a miss)."""
    return tuple(
        (t, catalog.version(t)) for _c, t in referenced_tables(plan)
    )


def physical_scan_schemas(plan, catalog) -> tuple:
    """The RESOLVED physical storage of every scanned column:
    (connector, table, ((col, 'bigint:int16'), ...)) per TableScan.
    Folded into the plan fingerprint so the chosen physical dtypes ARE
    part of a query's identity — toggling ``narrow_storage`` (a
    process-wide env-mirrored switch whose session-property value can
    be unset) changes the fingerprint through the types it resolves to,
    never silently reusing a cached plan compiled for other widths."""
    from presto_tpu.plan import nodes as N

    out = []

    def walk(node):
        if isinstance(node, N.TableScan):
            conn = catalog.connectors.get(node.connector)
            cols = [s for _n, s in node.columns]
            if conn is not None and hasattr(conn, "physical_schema"):
                try:
                    sch = conn.physical_schema(node.table, cols)
                    out.append((node.connector, node.table,
                                tuple((c, sch[c].physical_str())
                                      for c in cols)))
                except KeyError:
                    pass  # dropped table mid-plan: versions catch it
        for c in node.children:
            walk(c)

    walk(plan)
    return tuple(sorted(out))


def _mesh_shape(mesh) -> tuple:
    if mesh is None:
        return ()
    return (
        tuple(mesh.axis_names),
        tuple(int(s) for s in mesh.devices.shape),
        tuple(str(d) for d in mesh.devices.flat),
    )


def plan_fingerprint(plan, catalog, properties: dict | None = None,
                     mesh=None) -> Optional[str]:
    """The canonical identity of one executable query: plan structure
    and expressions, referenced tables WITH their catalog versions,
    the mesh shape (local vs each distributed layout compile
    differently), and every codegen-affecting session property.

    None when the plan contains uncacheable content.
    """
    from presto_tpu.runtime.properties import effective

    props = {
        name: effective(properties or {}, name) for name in CODEGEN_PROPERTIES
    }
    return try_fingerprint(
        plan,
        table_versions(plan, catalog),
        referenced_tables(plan),
        physical_scan_schemas(plan, catalog),
        _mesh_shape(mesh),
        props,
    )
