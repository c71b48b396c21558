#!/usr/bin/env python3
"""Find every place one statement reads a device value outside a
``sync:*`` span.

``trace.sync`` is the program's one hook for "the host reads a device
value here and waits for the device" — ``exec.sync.reads`` and the
``host_sync_ms`` entry count those spans, so an implicit read (a
``bool()``, ``int()``, ``np.asarray`` of a device array) outside one is
a wait no metric sees. This script runs a statement (warm: once before,
unaudited, so that compiles are out of the way) with two detectors on:

- ``jax.transfer_guard_device_to_host("disallow")`` around the audited
  run, with every ``sync:*`` span patched to allow: on a TPU a read
  outside a span raises. The guard does not fire on the CPU backend
  (device memory is host memory there).
- the funnel every Python-side read passes, ``ArrayImpl._value``,
  patched to note the stack of each first read of an array made outside
  a ``sync:*`` span, and then to let it through — so one run lists all
  of them. On the CPU backend it sees the scalar reads (``bool()``,
  ``int()``, ``float()``, ``.item()``, ``.tolist()``) but not
  ``np.asarray``, which there takes the buffer protocol and copies
  nothing: the whole answer comes from a run on the chip.

    python3 scripts/audit_device_reads.py --workload ssb_sf1_star_1s [--sf 0.01]
    python3 scripts/audit_device_reads.py --catalog tpch --sf 0.01 --sql "select ..."

Prints, per statement, the reads inside spans and the stack (the
program's frames) of every read outside one; exits 1 if there was one.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import threading
import traceback

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

_state = threading.local()


class _AllowedSync:
    """A ``sync:*`` span that also lifts the transfer guard."""

    def __init__(self, ctx):
        self.ctx = ctx
        self.guard = None

    def __enter__(self):
        import jax

        _state.depth = getattr(_state, "depth", 0) + 1
        self.guard = jax.transfer_guard_device_to_host("allow")
        self.guard.__enter__()
        return self.ctx.__enter__()

    def __exit__(self, *exc):
        out = self.ctx.__exit__(*exc)
        self.guard.__exit__(*exc)
        _state.depth -= 1
        return out


@contextlib.contextmanager
def auditing():
    """Patch the recorder's ``span`` and the arrays' ``_value`` for the
    calling thread's next statements; yields the list the stacks of the
    reads outside a ``sync:*`` span are appended to."""
    import jax
    from jax._src import array as jax_array

    from presto_tpu.runtime.trace import TraceRecorder

    found: list = []
    span = TraceRecorder.span
    value = jax_array.ArrayImpl._value

    def audited_span(self, name, cat="step", args=None):
        ctx = span(self, name, cat, args)
        return _AllowedSync(ctx) if cat == "sync" else ctx

    def audited_value(self):
        if (getattr(_state, "on", False) and self._npy_value is None
                and not getattr(_state, "depth", 0)):
            found.append(traceback.extract_stack()[:-1])
            with jax.transfer_guard_device_to_host("allow"):
                return value.fget(self)
        return value.fget(self)

    TraceRecorder.span = audited_span
    jax_array.ArrayImpl._value = property(audited_value)
    _state.on = True
    try:
        with jax.transfer_guard_device_to_host("disallow"):
            yield found
    finally:
        _state.on = False
        TraceRecorder.span = span
        jax_array.ArrayImpl._value = value


def audit(session, sql: str) -> dict:
    """Run ``sql`` once unaudited, once audited: ``{"inside": reads made
    in ``sync:*`` spans, "outside": [stack, ...], "error": str | None}``."""
    from presto_tpu.runtime.metrics import REGISTRY

    session.sql(sql)
    reads = REGISTRY.counter("exec.sync.reads")
    before = reads.total
    error = None
    with auditing() as found:
        try:
            session.sql(sql)
        except Exception as e:  # noqa: BLE001 — the guard's refusal
            error = f"{type(e).__name__}: {e}"
            found.append(traceback.extract_tb(e.__traceback__))
    return {"inside": int(reads.total - before), "outside": list(found),
            "error": error}


def program_frames(stack) -> list:
    """The stack's frames inside this repository, outermost first."""
    return [f for f in stack if f.filename.startswith(ROOT)
            and os.sep + "scripts" + os.sep not in f.filename]


def statements(args):
    """[(label, catalog, connector, properties, sql)] to audit."""
    if args.sql:
        mod = importlib.import_module(f"presto_tpu.connectors.{args.catalog}")
        cls = next(getattr(mod, n) for n in dir(mod)
                   if n.lower() == f"{args.catalog}connector")
        return [("sql", args.catalog, cls(sf=args.sf, seed=args.seed),
                 {"result_cache_enabled": False}, args.sql)]
    from benchmark.harness import cell as C
    from benchmark.harness.runner import _load_object

    spec = C.load_cell(args.workload)
    cfg = spec["config"]
    sf = cfg["sf"] if args.sf is None else args.sf
    conn = _load_object(cfg["connector"])(sf=sf, seed=args.seed)
    return [(f"{t}[{i}]", cfg["catalog"], conn, dict(cfg["properties"]),
             C.render_sql(spec["templates"][t], C.binding(
                 spec["traffic"], t, i)))
            for t, i in C.pairs(spec["traffic"])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json: its "
                    "configuration, properties and templates")
    ap.add_argument("--catalog", default="tpch")
    ap.add_argument("--sql")
    ap.add_argument("--sf", type=float, default=None)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    if not (args.sql or args.workload):
        ap.error("give --workload or --sql")
    if args.sql and args.sf is None:
        args.sf = 0.01
    import jax

    from presto_tpu.runtime.session import Session

    print(json.dumps({"device": jax.devices()[0].platform,
                      "count": len(jax.devices())}), flush=True)
    outside = 0
    sessions: dict = {}
    for label, catalog, conn, props, sql in statements(args):
        s = sessions.setdefault(id(conn), Session({catalog: conn},
                                                  properties=props))
        got = audit(s, sql)
        outside += len(got["outside"])
        print(f"== {label}: {got['inside']} reads inside sync:* spans, "
              f"{len(got['outside'])} outside"
              + (f"; FAILED {got['error']}" if got["error"] else ""),
              flush=True)
        for stack in got["outside"]:
            print("  -- read outside a sync:* span:")
            for f in program_frames(stack):
                print(f"     {os.path.relpath(f.filename, ROOT)}:{f.lineno} "
                      f"in {f.name}: {f.line}")
    return 1 if outside else 0


if __name__ == "__main__":
    raise SystemExit(main())
