"""Device mesh setup — the worker set.

Reference parity: the coordinator's view of the cluster
(``DiscoveryNodeManager``'s NodeMap + ``NodeScheduler`` placing tasks
on workers [SURVEY §2.1]). TPU-first: the "cluster" is a
``jax.sharding.Mesh``; placement is a sharding annotation, and the
entire REST control plane collapses into the single-controller driver
(SURVEY §7.1).

One mesh axis ``"workers"`` plays the role of Presto's worker set: scan
splits are data-parallel across it, hash-partitioned exchanges are
``all_to_all`` along it, broadcasts are ``all_gather``.

Multi-host (SURVEY §2.5 DCN row): ``make_dcn_mesh`` builds a 2-D
``("dcn", "ici")`` mesh — the outer axis crosses hosts, the inner axis
stays on-slice. Fragment steps shard and exchange over the COMBINED
axes (every collective here accepts an axis tuple), so the same
compiled programs run on either mesh shape; XLA routes the inter-host
legs of the collectives over DCN and the intra-host legs over ICI.
Bootstrap a real multi-process run with ``parallel.multihost``.
"""

from __future__ import annotations

import jax
import numpy as np
from jax import shard_map  # noqa: F401 — re-exported to the executors
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from presto_tpu.runtime.errors import UserError

WORKERS = "workers"
DCN = "dcn"
ICI = "ici"


def make_mesh(n_devices: int | None = None, devices=None) -> Mesh:
    devs = list(devices) if devices is not None else jax.devices()
    if n_devices is not None:
        if len(devs) < n_devices:
            raise UserError(f"need {n_devices} devices, have {len(devs)}")
        devs = devs[:n_devices]
    return Mesh(np.array(devs), (WORKERS,))


def make_dcn_mesh(n_hosts: int, per_host: int | None = None, devices=None) -> Mesh:
    """2-D multi-host mesh: outer ``dcn`` axis across hosts, inner
    ``ici`` axis within a host. Devices are explicitly sorted
    host-major — ``jax.devices()`` order follows device ids/topology
    and is NOT guaranteed host-contiguous, and a row mixing hosts
    would silently route "ici" traffic over DCN."""
    devs = list(devices) if devices is not None else jax.devices()
    devs.sort(key=lambda d: (d.process_index, d.id))
    if per_host is None:
        if len(devs) % n_hosts:
            raise UserError(f"{len(devs)} devices not divisible by {n_hosts}")
        per_host = len(devs) // n_hosts
    need = n_hosts * per_host
    if len(devs) < need:
        raise UserError(f"need {need} devices, have {len(devs)}")
    return Mesh(np.array(devs[:need]).reshape(n_hosts, per_host), (DCN, ICI))


def worker_axes(mesh: Mesh) -> tuple[str, ...]:
    """The axis names playing the worker-set role for this mesh shape;
    collectives and shardings use the full tuple."""
    return tuple(mesh.axis_names)


def row_sharding(mesh: Mesh) -> NamedSharding:
    """Shard batch rows across the worker axes (data parallel scan)."""
    return NamedSharding(mesh, PartitionSpec(worker_axes(mesh)))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())
