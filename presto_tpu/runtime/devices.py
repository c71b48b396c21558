"""Device telemetry: the accelerator-side half of serving-tier health.

Reference parity: the coordinator's continuously observable workers —
``NodeScheduler`` consumes live per-node memory/CPU state before
placing work [SURVEY §2.1 node-state rows]. Single-controller JAX has
no remote workers to poll, but it does have local devices whose HBM
occupancy and dispatch wall are exactly the signals the hybrid-spill
tier and the admission ladder guess at today. This module makes them
queryable:

- ``sample_devices()`` — one row per ``jax.local_devices()`` entry
  with ``memory_stats()`` bytes-in-use / peak watermark / limit
  (CPU-safe: backends without allocator stats report zeros, rows still
  appear so ``system.device_stats`` is never empty), plus
  ``dispatches`` and ``dispatch_wall_s``: the process's jitted-step
  calls and the host seconds inside them, read from the counters
  ``exec.dispatch.calls`` / ``exec.dispatch.seconds`` that
  ``cache/exec_cache._TimedStep`` keeps — the one place every cached
  step is called. Every local device takes part in every
  single-controller dispatch, so each row carries the call count and
  an even share of the seconds.
- ``headroom_bytes()`` — min over devices of ``limit - in_use``; the
  number hybrid-spill residency decisions should be judged against
  (``None`` when no backend reports a limit, e.g. CPU meshes).
- ``gauges()`` — OpenMetrics gauge rows merged into
  ``Session.export_metrics``.
- ``peak_bytes()`` — max device watermark, stamped per query as
  ``QueryInfo.device_peak_bytes`` by the lifecycle.

Sampling cost is one ``memory_stats()`` call per device (a dict read
on TPU, ``None`` on CPU) — cheap enough to run per query; the
watchdog overhead bound in ``tests/test_health.py`` holds it to <5%.
"""

from __future__ import annotations

from typing import Optional

import jax

from presto_tpu.runtime.metrics import REGISTRY


def _memory_stats(device) -> dict:
    """``device.memory_stats()`` with every backend quirk absorbed:
    CPU returns ``None``, some backends raise ``NotImplementedError``
    (or anything else mid-teardown) — telemetry degrades to zeros, it
    never degrades a query."""
    try:
        return device.memory_stats() or {}
    except Exception:  # noqa: BLE001 — telemetry must not fail queries
        return {}


def sample_devices() -> "list[dict]":
    """One telemetry row per local device (the ``system.device_stats``
    backing store). Rows appear even when the backend reports no
    allocator stats so the table is populated on CPU meshes too."""
    devs = jax.local_devices()
    dispatches = int(REGISTRY.counter("exec.dispatch.calls").total)
    total_s = REGISTRY.counter("exec.dispatch.seconds").total
    per_device_s = total_s / len(devs) if devs else 0.0
    rows = []
    for d in devs:
        ms = _memory_stats(d)
        rows.append({
            "device_id": str(d.id),
            "platform": str(getattr(d, "platform", "unknown")),
            "bytes_in_use": int(ms.get("bytes_in_use", 0)),
            "peak_bytes": int(ms.get("peak_bytes_in_use", 0)),
            "bytes_limit": int(ms.get("bytes_limit", 0)),
            "dispatch_wall_s": per_device_s,
            "dispatches": dispatches,
        })
    return rows


def peak_bytes() -> int:
    """Max device HBM watermark right now — stamped on each finished
    query as ``QueryInfo.device_peak_bytes`` (0 on backends without
    allocator stats)."""
    peak = 0
    for d in jax.local_devices():
        peak = max(peak, int(_memory_stats(d).get("peak_bytes_in_use", 0)))
    return peak


def headroom_bytes() -> Optional[int]:
    """Min over devices of ``bytes_limit - bytes_in_use`` — the real
    HBM headroom the hybrid-spill residency planner should be judged
    against. ``None`` when no device reports a limit (CPU meshes):
    absent telemetry must read as "unknown", not "infinite"."""
    headroom = None
    for d in jax.local_devices():
        ms = _memory_stats(d)
        limit = int(ms.get("bytes_limit", 0))
        if limit <= 0:
            continue
        free = limit - int(ms.get("bytes_in_use", 0))
        headroom = free if headroom is None else min(headroom, free)
    return headroom


def gauges() -> dict:
    """Per-device OpenMetrics gauges (merged into the session's
    ``export_metrics`` gauge set)."""
    out = {}
    for row in sample_devices():
        did = row["device_id"]
        out[f"device.bytes_in_use.{did}"] = row["bytes_in_use"]
        out[f"device.peak_bytes.{did}"] = row["peak_bytes"]
        out[f"device.bytes_limit.{did}"] = row["bytes_limit"]
        out[f"device.dispatch_wall_s.{did}"] = row["dispatch_wall_s"]
    return out
