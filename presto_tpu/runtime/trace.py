"""Structured query tracing: nested spans, exportable as Chrome trace JSON.

Reference parity: the reference's observability stack is three-tiered —
``OperatorStats`` rollups (host timings), the EventListener SPI (query
history), and external tracing hooks; this module is the tracing tier
[SURVEY §5.1, §5.5]. A :class:`TraceRecorder` collects one query's span
tree — query -> fragment dispatch -> plan node -> jitted-step dispatch,
plus cache / retry / exchange / degradation spans — and the session's
ring of recent recorders backs ``Session.export_trace`` (Chrome
``trace_event`` JSON, loadable in Perfetto / chrome://tracing) and the
``system.trace_spans`` table.

Design constraints:

- **Cheap when off, cheap when on.** The recorder rides a ContextVar;
  with none installed, :func:`span` costs one ContextVar read and
  returns a shared no-op context manager. With one installed, a span is
  two ``perf_counter`` reads and one list append — recording is
  per-query and single-writer (the driver thread), so there are no
  locks on the hot path. The acceptance bound (<5% overhead on the
  warm-cache Q1 path) is asserted in tests/test_trace.py.
- **Host-observed times.** A span around a jitted-step call measures
  the host-side dispatch latency including the device work the host
  waited on; XLA owns the intra-step schedule (SURVEY §5.1). The
  optional ``profile_annotations`` hook wraps each span in a
  ``jax.profiler.TraceAnnotation`` named ``<span>#<trace_token>`` so
  xprof device timelines correlate with engine spans by trace token.
- **Bounded.** Spans per query cap at ``max_spans`` (overflow counts
  into the ``trace.spans_dropped`` metric, never errors); the
  per-session :class:`TraceStore` is a fixed-size ring.
- **A span the benchmark reads is a live interval, never a post-hoc
  sum.** Every span is one real interval on the query's thread, so
  with ``profile_annotations`` on it is also a ``TraceAnnotation`` on
  the profiler's clock and the benchmark's gap attribution can put an
  idle gap of the device under it. Two categories exist for that
  reading: ``scan`` parts the host's work inside a connector scan
  (``scan:generate`` making the split's host arrays, ``batch:pad`` the
  capacity-sized copies, ``batch:upload`` the hand-over to the device),
  and ``sync`` marks every place the served local path blocks on a
  device value (:func:`sync`). Intervals that begin before the query's
  recorder exists or end after it closed (``plan``,
  ``frontend:submit``, ``frontend:encode``) are timed where they
  happen under :func:`annotation` and put on the recorder with
  ``add_complete`` from the same two clock reads.
- **Self time from the spans' own parent links.** Every span carries
  ``span_id`` / ``parent_id``; :meth:`TraceRecorder.self_times` gives
  each span its duration minus what its children cover (their union,
  clipped to the span: an ``add_complete`` child may reach outside its
  parent), so a container's inclusive time parts into the named
  activities under it and the remainder no span names. A query's self
  times sum to its ``query`` span. ``system.trace_spans.self_s`` is the
  operator's view; the benchmark's ``span_self_time`` reader does the
  same arithmetic on its own.
- **The interpreter beside the spans.** One ``gc.callbacks`` hook,
  installed at import: every collection counts into
  ``exec.gc.collections.gen<g>`` and ``exec.gc.pause_s``; a collection
  of generation 1 or 2 is also a ``gc:gen<g>`` span (category
  ``runtime``) on the recorder of the thread it ran on. Generation 0
  gets the counters only (span volume). ``query.thread_cpu_s`` is the
  query thread's CPU time across the root span (``runtime/session``).
"""

from __future__ import annotations

import gc
import json
import time
from collections import deque
from contextlib import nullcontext
from contextvars import ContextVar
from typing import Any, Optional

from presto_tpu.runtime.metrics import REGISTRY

#: span categories (the ``cat`` field of exported events)
CATEGORIES = (
    "query",      # the root span of one tracked query
    "fragment",   # a lifecycle fragment dispatch (run_fragment attempt)
    "node",       # one plan node's execution (inclusive of children)
    "step",       # one jitted-step / operator dispatch
    "exchange",   # a collective exchange (bytes/partitions/rounds in args)
    "cache",      # exec/result/stats cache lookups
    "retry",      # a fragment-retry backoff window
    "lifecycle",  # admission / degradation
    "driver",     # the local driver push loop
    "stats",      # estimate snapshot / plan-stats history recording
    "frontend",   # HTTP serving-tier spans (submit / poll round-trips)
    "subscription",  # a continuous-query refresh fire (child of its sub)
    "scan",       # host work inside a connector scan: generate / pad / upload
    "sync",       # the host blocked on a device value (see sync())
    "planner",    # parse + analyse + template binding, before the query span
    "runtime",    # the interpreter itself: a gc:gen<g> collector pause
)

_TRACE: ContextVar[Optional["TraceRecorder"]] = ContextVar(
    "presto_tpu_trace", default=None
)

#: shared reusable no-op context manager (``nullcontext`` keeps no
#: per-use state); its ``__enter__`` returns None, so callers that
#: annotate span args must guard ``if sp is not None``
_NOOP = nullcontext()


class Span:
    """One recorded span. ``args`` is live-mutable until export —
    callers may attach results (bytes moved, hit/miss) after the
    timed region closes."""

    __slots__ = ("span_id", "parent_id", "name", "cat", "t0", "t1", "args")

    def __init__(self, span_id: int, parent_id: int, name: str, cat: str):
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.cat = cat
        self.t0 = 0.0
        self.t1 = 0.0
        self.args: dict[str, Any] = {}


class _SpanCtx:
    __slots__ = ("rec", "span", "_ann")

    def __init__(self, rec: "TraceRecorder", span: Span):
        self.rec = rec
        self.span = span
        self._ann = None

    def __enter__(self) -> Span:
        rec = self.rec
        rec._stack.append(self.span)
        if rec.annotate:
            self._ann = _annotation(self.span.name, rec.trace_token)
            if self._ann is not None:
                self._ann.__enter__()
        self.span.t0 = time.perf_counter()
        return self.span

    def __exit__(self, *exc):
        self.span.t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.rec._stack.pop()
        return False


#: ``jax.profiler.TraceAnnotation`` once the first annotated span has
#: asked for it (False: the profiler is unavailable). Kept here so that
#: the gc hook, which must import nothing, finds it ready.
_TRACE_ANNOTATION: Any = None


def _annotation(name: str, token: Optional[str]):
    """A jax.profiler.TraceAnnotation carrying the trace token, or None
    when the profiler is unavailable (annotation is best-effort)."""
    global _TRACE_ANNOTATION
    if _TRACE_ANNOTATION is None:
        try:
            from jax.profiler import TraceAnnotation
        except Exception:  # pragma: no cover - ancient jax
            TraceAnnotation = False
        _TRACE_ANNOTATION = TraceAnnotation
    if not _TRACE_ANNOTATION:
        return None
    return _TRACE_ANNOTATION(f"{name}#{token}" if token else name)


class TraceRecorder:
    """One query's span tree. Single-writer (the driver thread owns the
    query synchronously); reads happen after the query finishes."""

    __slots__ = (
        "query_id", "trace_token", "max_spans", "annotate",
        "spans", "dropped", "created_clock", "_stack", "_seq",
    )

    def __init__(self, query_id: str, trace_token: Optional[str] = None,
                 max_spans: int = 8192, annotate: bool = False):
        self.query_id = query_id
        self.trace_token = trace_token
        self.max_spans = max_spans
        self.annotate = annotate
        self.spans: list[Span] = []
        self.dropped = 0
        #: (perf_counter, time_ns) read together: span times are on
        #: the first clock, a profiler trace on the second
        self.created_clock = (time.perf_counter(), time.time_ns())
        self._stack: list[Span] = []  # open spans (parents)
        self._seq = 0

    # -- recording ---------------------------------------------------------
    def span(self, name: str, cat: str = "step",
             args: Optional[dict] = None):
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            REGISTRY.counter("trace.spans_dropped").add()
            return _NOOP
        s = self._new_span(name, cat)
        if args:
            s.args.update(args)
        return _SpanCtx(self, s)

    def _new_span(self, name: str, cat: str,
                  parent: Optional[int] = None) -> Span:
        """A recorded span under ``parent`` (default: the innermost open
        span). The id is taken before the Span is made: the gc hook may
        add a span of its own from inside any allocation."""
        sid = self._seq
        self._seq = sid + 1
        if parent is None:
            parent = self._stack[-1].span_id if self._stack else -1
        s = Span(sid, parent, name, cat)
        self.spans.append(s)
        return s

    def add_complete(self, name: str, cat: str, t0: float, dur_s: float,
                     args: Optional[dict] = None) -> Optional[Span]:
        """Record an already-timed span (explicit perf_counter start +
        duration) under the currently open span."""
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            REGISTRY.counter("trace.spans_dropped").add()
            return None
        s = self._new_span(name, cat)
        s.t0 = t0
        s.t1 = t0 + dur_s
        if args:
            s.args.update(args)
        return s

    # -- introspection -----------------------------------------------------
    @property
    def t0(self) -> float:
        return self.spans[0].t0 if self.spans else 0.0

    def self_times(self, spans: Optional[list] = None) -> dict[int, float]:
        """``{span_id: seconds}``: each span's duration minus the union
        of its children's intervals clipped to it — what the span spent
        under no child's name. Children of one span follow one another
        on the one writer thread, but an ``add_complete`` span (``plan``,
        ``frontend:*``, a ``gc:*`` pause) is put under whatever span was
        open when it was recorded and may lie partly or wholly outside
        it: it takes away only what it covers of its parent. Over ONE
        snapshot of the spans (``spans``, or taken here): on a recorder
        that is still installed, a collection tripped by this very
        loop's allocations appends a ``gc:*`` span meanwhile."""
        if spans is None:
            spans = list(self.spans)
        kids: dict[int, list[tuple[float, float]]] = {}
        for s in spans:
            kids.setdefault(s.parent_id, []).append((s.t0, s.t1))
        out = {}
        for s in spans:
            covered, edge = 0.0, s.t0
            for a, b in sorted(kids.get(s.span_id, ())):
                a, b = max(a, edge), min(b, s.t1)
                if b > a:
                    covered += b - a
                    edge = b
            out[s.span_id] = max(s.t1 - s.t0, 0.0) - covered
        return out

    def to_span_dicts(self) -> list[dict]:
        """The span tree as plain dicts with query-relative timestamps
        (args shared by reference — callers that persist them, like
        the flight recorder, must deep-copy/coerce). The flattening
        the ``system.trace_spans`` scan and post-mortem capture share."""
        t0 = self.t0
        spans = list(self.spans)
        self_s = self.self_times(spans)
        return [
            {
                "span_id": s.span_id,
                "parent_id": s.parent_id,
                "name": s.name,
                "cat": s.cat,
                "start_s": round(max(s.t0 - t0, 0.0), 6),
                "duration_s": round(max(s.t1 - s.t0, 0.0), 6),
                "self_s": round(self_s[s.span_id], 6),
                "args": s.args,
            }
            for s in spans
        ]

    def spans_by_cat(self, cat: str) -> list[Span]:
        return [s for s in self.spans if s.cat == cat]

    # -- export ------------------------------------------------------------
    def to_events(self, pid: int) -> list[dict]:
        """Chrome trace_event entries for this query (one pid per
        query; ts in microseconds on the process perf_counter epoch)."""
        events: list[dict] = [
            {
                "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
                "args": {"name": f"query {self.query_id}"},
            },
            {
                "name": "process_labels", "ph": "M", "pid": pid, "tid": 0,
                "args": {"labels": f"trace_token={self.trace_token}"},
            },
        ]
        for s in self.spans:
            args = {"span_id": s.span_id, "parent_id": s.parent_id}
            args.update(s.args)
            if self.trace_token is not None:
                args["trace_token"] = self.trace_token
            events.append({
                "name": s.name,
                "cat": s.cat,
                "ph": "X",
                "pid": pid,
                "tid": 0,
                "ts": round(s.t0 * 1e6, 3),
                "dur": round(max(s.t1 - s.t0, 0.0) * 1e6, 3),
                "args": args,
            })
        return events


# ---------------------------------------------------------------------------
# Module-level recording surface (the instrumentation points' API)
# ---------------------------------------------------------------------------


def install(rec: Optional[TraceRecorder]):
    """Install ``rec`` as the active recorder; returns the reset token
    (nested queries from event listeners get their own recorder and
    restore the outer one on exit)."""
    return _TRACE.set(rec)


def uninstall(token) -> None:
    _TRACE.reset(token)


def current() -> Optional[TraceRecorder]:
    return _TRACE.get()


def span(name: str, cat: str = "step", args: Optional[dict] = None):
    """The one instrumentation hook: a context manager timing a span
    under the active recorder, or a shared no-op when tracing is off.
    ``with span(...) as sp:`` — ``sp`` is the live Span (mutate
    ``sp.args`` freely) or None on the no-op path."""
    rec = _TRACE.get()
    if rec is None:
        return _NOOP
    return rec.span(name, cat, args)


def add_complete(name: str, cat: str, t0: float, dur_s: float,
                 args: Optional[dict] = None) -> None:
    rec = _TRACE.get()
    if rec is not None:
        rec.add_complete(name, cat, t0, dur_s, args)


def annotation(name: str, token: Optional[str], on: bool):
    """The profiler annotation ``<name>#<token>`` alone, for an interval
    that reaches the recorder through ``add_complete`` (it starts before
    the recorder exists, ends after it closed, or is recorded only once
    its outcome is known): entered where the interval really happens, so
    that the interval is on the profiler's clock like a live span.
    ``on`` is the ``profile_annotations`` property; a no-op when off."""
    ann = _annotation(name, token) if on else None
    return _NOOP if ann is None else ann


def sync(what: str):
    """The span for one place where the host reads a device value and so
    waits for every step dispatched before it: ``with sync("leaf_state"):
    overflow = bool(state["value_overflow"])`` records ``sync:leaf_state``
    (category ``sync``) and counts ``exec.sync.reads``."""
    REGISTRY.counter("exec.sync.reads").add()
    return span(f"sync:{what}", "sync")


# ---------------------------------------------------------------------------
# The interpreter: collector pauses
# ---------------------------------------------------------------------------


_GC_COLLECTIONS = tuple(f"exec.gc.collections.gen{g}" for g in range(3))


class _GcHook:
    """The ``gc.callbacks`` entry (one a process, installed below).

    A collection interrupts whatever thread asked for the allocation
    that tripped it, between two bytecodes of ANY code — also code that
    holds the registry's lock, a counter's or a per-query delta's. So
    the hook takes no lock and imports nothing: it reads the registry's
    dict, adds with :meth:`CounterStat.add_unlocked` (collections never
    overlap, so it is the one writer of its counters) and appends to
    the recorder of the thread it runs on, which is the thread that
    writes that recorder."""

    __slots__ = ("t0", "ann")

    def __init__(self):
        self.t0 = 0.0
        self.ann = None

    def __call__(self, phase: str, info: dict) -> None:
        gen = info["generation"]
        rec = _TRACE.get() if gen else None
        if phase == "start":
            if rec is not None and rec.annotate and _TRACE_ANNOTATION:
                self.ann = _annotation(f"gc:gen{gen}", rec.trace_token)
                self.ann.__enter__()
            self.t0 = time.perf_counter()
            return
        dur = time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
            self.ann = None
        for name, v in ((_GC_COLLECTIONS[gen], 1.0),
                        ("exec.gc.pause_s", dur)):
            c = REGISTRY.counter_nowait(name)
            if c is not None:
                c.add_unlocked(v)
        if rec is None:
            return
        if len(rec.spans) >= rec.max_spans:
            rec.dropped += 1
            c = REGISTRY.counter_nowait("trace.spans_dropped")
            if c is not None:
                c.add_unlocked(1.0)
            return
        # under the innermost span that is really open: the collection
        # may have run inside a span's own enter or exit, when the top
        # of the stack has not read its first clock or has read its last
        parent = next((p.span_id for p in reversed(rec._stack)
                       if p.t0 and not p.t1), -1)
        s = rec._new_span(f"gc:gen{gen}", "runtime", parent)
        s.t0, s.t1 = self.t0, self.t0 + dur
        s.args["collected"] = info.get("collected", 0)


if not any(type(cb).__name__ == "_GcHook" for cb in gc.callbacks):
    gc.callbacks.append(_GcHook())


# ---------------------------------------------------------------------------
# Byte accounting helpers (observability-side batch sizing; capacity
# arithmetic only — never a device sync)
# ---------------------------------------------------------------------------


def batch_row_bytes(batch) -> int:
    """Per-row device bytes of a Batch as it is HELD and as the
    column-by-column collectives move it (an all_gather, a device_put):
    column payload widths + the validity and live masks, 1 byte each —
    bools ride as uint8. NOT what a hash exchange sends: that moves a
    packed row of 32-bit words — the data of the 4- and 8-byte columns
    and BYTES matrices word for word, the narrower columns sharing
    words, every mask ONE BIT (``ops/partition.pack_rows``) — counted
    by ``parallel/exchange.exchange_row_bytes``."""
    total = 1  # live mask
    for c in batch.columns.values():
        width = 1
        for d in c.data.shape[1:]:
            width *= int(d)
        total += width * c.data.dtype.itemsize + 1  # + valid mask
    return total


def batch_device_bytes(batch) -> int:
    """Capacity-based device residency of a Batch (live rows and
    padding both occupy HBM)."""
    return batch_row_bytes(batch) * int(batch.capacity)


# ---------------------------------------------------------------------------
# Per-session trace retention + Chrome export
# ---------------------------------------------------------------------------

#: recorders retained per session (spans are memory-heavy relative to
#: QueryInfo, so this ring is deliberately smaller than query history)
TRACE_RING = 64


class TraceStore:
    """Ring buffer of the session's most recent TraceRecorders."""

    def __init__(self, maxlen: int = TRACE_RING):
        self._ring: deque[TraceRecorder] = deque(maxlen=maxlen)

    def add(self, rec: TraceRecorder) -> None:
        self._ring.append(rec)

    def recorders(self) -> list[TraceRecorder]:
        return list(self._ring)

    def latest(self) -> Optional[TraceRecorder]:
        return self._ring[-1] if self._ring else None

    def for_query(self, query_id: str) -> Optional[TraceRecorder]:
        for rec in reversed(self._ring):
            if rec.query_id == query_id:
                return rec
        return None

    def __len__(self) -> int:
        return len(self._ring)


def to_chrome_trace(recorders: list[TraceRecorder]) -> dict:
    """The Chrome ``trace_event`` JSON object for a set of recorders
    (one pid per query, ts on the shared perf_counter epoch)."""
    events: list[dict] = []
    tokens = []
    for pid, rec in enumerate(recorders, start=1):
        events.extend(rec.to_events(pid))
        if rec.trace_token is not None:
            tokens.append(rec.trace_token)
    return {
        "traceEvents": events,
        "displayTimeUnit": "ms",
        "otherData": {
            "engine": "presto_tpu",
            "trace_tokens": sorted(set(tokens)),
            "queries": [rec.query_id for rec in recorders],
            # per query, the two clocks read together at recorder
            # creation: ts is on the first, a profiler trace on the second
            "clocks": [{"query": rec.query_id,
                        "perf_counter_s": rec.created_clock[0],
                        "time_ns": rec.created_clock[1]}
                       for rec in recorders],
        },
    }


def export_chrome_trace(path: str, recorders: list[TraceRecorder]) -> str:
    with open(path, "w") as f:
        json.dump(to_chrome_trace(recorders), f)
    return path
