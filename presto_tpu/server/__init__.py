"""Serving layer: multi-tenant front-end, fairness scheduler, and
cross-query batched dispatch.

Reference parity: the coordinator tier presto-main wraps around query
execution — ``NodeScheduler`` / resource groups multiplexing many
clients onto shared workers, and the HTTP ``/v1/statement`` protocol
[SURVEY §2.1 protocol + resource-group rows]. Single-controller
mapping: the "cluster" is one process, so the serving layer is three
cooperating pieces over the existing ``Session``/``QueryManager``
substrate:

- :mod:`presto_tpu.server.scheduler` — weighted-fair admission with
  per-tenant quotas between the front-end and the memory pool's strict
  FIFO.
- :mod:`presto_tpu.server.batcher` — the throughput multiplier that
  comes from *load shape*: concurrent same-template different-literal
  queries stack their param bindings into ONE vmapped device dispatch.
- :mod:`presto_tpu.server.frontend` — the HTTP/JSON surface
  (``/v1/statement``, ``/v1/prepared``, ``/metrics``) plus the
  in-process ``ServerClient`` tests drive without sockets.

Imports are lazy (PEP 562): the runtime imports
``presto_tpu.server.batcher`` from ``QueryManager`` without dragging
the HTTP front-end (and its ``Session`` import) into every query.
"""

from __future__ import annotations

_EXPORTS = {
    "TenantSpec": "presto_tpu.server.scheduler",
    "FairScheduler": "presto_tpu.server.scheduler",
    "TemplateBatchGate": "presto_tpu.server.batcher",
    "run_batched": "presto_tpu.server.batcher",
    "QueryServer": "presto_tpu.server.frontend",
    "ServerClient": "presto_tpu.server.frontend",
    "HttpFrontend": "presto_tpu.server.frontend",
}

__all__ = list(_EXPORTS)


def __getattr__(name: str):
    mod = _EXPORTS.get(name)
    if mod is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    import importlib

    return getattr(importlib.import_module(mod), name)
