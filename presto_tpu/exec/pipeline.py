"""Pipelines and the driver loop.

Reference parity: ``operator.Driver.processFor`` — the inner loop moving
Pages between adjacent operators — and ``DriverFactory``/pipeline
structure from ``LocalExecutionPlanner`` [SURVEY §2.1, §3.2; reference
tree unavailable, paths reconstructed].

TPU-first: the driver is a *push* loop on the host; batches are device
arrays, so each ``process`` call is an async XLA dispatch and the loop
runs ahead of the device (the cooperative time-slicing machinery of
``TaskExecutor`` collapses into Python + the XLA stream). A pipeline is
``source -> transforms... -> sink``; pipeline-breaking operators
(aggregations, sorts, joins' build side) buffer device-side and emit on
``finish()``.
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Callable, Iterable, Iterator, Sequence

from presto_tpu.batch import Batch
from presto_tpu.exec.operators import Operator
from presto_tpu.runtime.metrics import REGISTRY
from presto_tpu.runtime.trace import span as trace_span
from presto_tpu.spi import Connector, Split, batch_capacity


@dataclass
class OperatorStats:
    """Per-operator runtime stats (reference: OperatorStats rollup into
    QueryStats [SURVEY §5.1])."""

    name: str
    input_batches: int = 0
    output_batches: int = 0
    wall_s: float = 0.0


class ScanSource:
    """Pulls splits from a connector and yields device batches
    (reference: ScanFilterAndProjectOperator's page source half +
    SourcePartitionedScheduler's split feed)."""

    def __init__(
        self,
        connector: Connector,
        table: str,
        columns: Sequence[str] | None,
        splits: Sequence[Split] | None = None,
        capacity: int | None = None,
    ):
        self.connector = connector
        self.table = table
        self.columns = list(columns) if columns is not None else None
        self.splits = list(splits) if splits is not None else list(connector.splits(table))
        # one shared capacity bucket across splits keeps a single
        # compiled program per chain
        self.capacity = capacity or batch_capacity(
            max(s.row_hint for s in self.splits)
        )

    def __iter__(self) -> Iterator[Batch]:
        def load(split):
            from presto_tpu.runtime.faults import fault_point

            fault_point("scan")
            return self.connector.scan(split, self.columns, self.capacity)

        return prefetch_iter(load, self.splits)


def prefetch_enabled() -> bool:
    """Default: on when the host has CPU to spare, off on a 1-core
    host — measured in round 5, on another runtime, through a hand-fed
    Q1 stream and not re-measured on the served path: with one host
    core the worker thread only contends with generation under the
    GIL (SF1 streamed: 439k rows/s prefetched vs 518k serial).
    ``PRESTO_TPU_PREFETCH=1/0`` overrides either way."""
    import os

    v = os.environ.get("PRESTO_TPU_PREFETCH", "").strip().lower()
    if v:
        return v not in ("0", "false", "off", "no")
    try:
        ncpu = len(os.sched_getaffinity(0))  # cgroup/taskset-aware
    except AttributeError:  # non-Linux
        ncpu = os.cpu_count() or 1
    return ncpu > 1


def prefetch_iter(load, items):
    """One-slot prefetch (SURVEY §2.4 PP row, §7.1 double-buffered H2D):
    item k+1 loads (generate + transfer) on a worker thread while the
    consumer holds item k — XLA dispatches are async, so the consumer
    returns to this loop immediately and host-side generation overlaps
    device compute. Exactly one item is in flight (bounded host
    memory). ``PRESTO_TPU_PREFETCH=0`` reverts to a serial loop."""
    if len(items) <= 1 or not prefetch_enabled():
        for it in items:
            yield load(it)
        return
    from concurrent.futures import ThreadPoolExecutor

    with ThreadPoolExecutor(max_workers=1) as ex:
        fut = ex.submit(load, items[0])
        for nxt in items[1:]:
            out = fut.result()
            fut = ex.submit(load, nxt)
            yield out
        yield fut.result()


class BatchSource:
    """A source over in-memory batches (exchange inputs, tests)."""

    def __init__(self, batches: Iterable[Batch]):
        self._batches = batches

    def __iter__(self) -> Iterator[Batch]:
        return iter(self._batches)


class BatchStream:
    """A REPLAYABLE lazy batch stream — the executor's unit of data flow.

    ``make_iter`` returns a fresh iterator on every call, so retry loops
    (capacity-overflow doubling) can re-drain the stream; a plain
    generator would come back empty on the second attempt and silently
    drop rows. Replaying a scan-rooted stream re-generates the data —
    the deliberate trade that keeps memory bounded (SURVEY §7.4 #1:
    overflow retries are rare, whole-table materialization is not).

    Streams rooted at materialized results wrap a list (replay is free).
    """

    def __init__(self, make_iter: Callable[[], Iterator[Batch]]):
        self._make = make_iter

    @classmethod
    def of(cls, batches: Sequence[Batch]) -> "BatchStream":
        return cls(lambda: iter(batches))

    def __iter__(self) -> Iterator[Batch]:
        return self._make()

    def map(self, fn: Callable[[Batch], Batch]) -> "BatchStream":
        return BatchStream(lambda: (fn(b) for b in self))

    def peek(self) -> "Batch | None":
        """First batch, or None when empty (costs one replayed scan of
        the first split — used for trace-time decisions like dictionary
        domains). The replay is a hidden re-scan: it is the span
        ``stream:peek`` and counts ``exec.stream.peeks``."""
        REGISTRY.counter("exec.stream.peeks").add()
        with trace_span("stream:peek", "step"):
            return next(iter(self), None)

    def materialize(self) -> list[Batch]:
        return list(self)


class Pipeline:
    """source -> op chain; run() returns the terminal output batches."""

    def __init__(self, source: Iterable[Batch], operators: Sequence[Operator]):
        self.source = source
        self.operators = list(operators)
        self.stats = [OperatorStats(type(op).__name__) for op in self.operators]

    def run(self) -> list[Batch]:
        from presto_tpu.runtime.lifecycle import check_deadline

        outputs: list[Batch] = []

        def push(i: int, batch: Batch):
            if i == len(self.operators):
                outputs.append(batch)
                return
            st = self.stats[i]
            st.input_batches += 1
            t0 = time.perf_counter()
            with trace_span(f"step:{st.name}", "step"):
                produced = self.operators[i].process(batch)
            st.wall_s += time.perf_counter() - t0
            for b in produced:
                st.output_batches += 1
                push(i + 1, b)

        # the driver-loop deadline boundary: one check per morsel (a
        # compiled step in flight runs to completion; the NEXT push is
        # what an expired query_max_run_time stops)
        with trace_span("driver:push", "driver"):
            for batch in self.source:
                check_deadline("driver-loop")
                push(0, batch)
        # finish cascade — checked per finish() step, not once: for
        # sort/window/topN plans the heavy work happens HERE, so an
        # expired deadline must stop the remaining collecting operators
        for i, op in enumerate(self.operators):
            check_deadline("driver-finish")
            t0 = time.perf_counter()
            with trace_span(f"finish:{self.stats[i].name}", "step"):
                tail = op.finish()
            self.stats[i].wall_s += time.perf_counter() - t0
            for b in tail:
                self.stats[i].output_batches += 1
                push(i + 1, b)
        return outputs
