"""Multi-controller (multi-host) SPMD support: the DCN-class analogue of
the reference's cross-executor shuffle transport.

The reference moves inter-node bytes through the host engine's block
store / RSS clients (SURVEY.md §5.8); the TPU-native design instead runs
ONE jax program per host in a multi-controller group
(`jax.distributed.initialize`), builds a GLOBAL mesh over every host's
devices, and lets the same `lax.all_to_all` / `psum` collectives that ride
ICI within a slice ride DCN (gRPC on CPU backends) across hosts — the
exchange code in parallel/mesh_exchange.py is byte-identical in both
settings because jax global meshes hide the fabric.

This module holds the thin host-runtime plumbing that setting needs:
process-group init, the global data mesh, and host-local ↔ global array
conversion for feeding per-host partitions into a global SPMD program.

Tested two-process-for-real in tests/test_multihost.py (each process owns
a disjoint set of virtual CPU devices; collectives cross the process
boundary), mirroring the reference's two-process RSS proof
(tests/test_rss_shuffle.py).
"""

from __future__ import annotations

from typing import Sequence

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P


def init_process_group(coordinator: str, num_processes: int,
                       process_id: int) -> None:
    """Join the multi-controller group (reference analogue: executor
    registration with the driver's block-manager/RSS endpoints).

    Must run before any other jax call in the process.
    """
    jax.distributed.initialize(coordinator_address=coordinator,
                               num_processes=num_processes,
                               process_id=process_id)


def global_mesh(axis: str = "data") -> Mesh:
    """One-axis mesh over EVERY process's devices, in process order (so
    shard p of a host-local array lands on process p's devices)."""
    return Mesh(np.array(jax.devices()), (axis,))


def to_global(mesh: Mesh, host_local: np.ndarray, axis: str = "data"):
    """Per-host rows → one global sharded array: each process contributes
    its local block; the result's global shape concatenates all hosts."""
    from jax.experimental import multihost_utils
    return multihost_utils.host_local_array_to_global_array(
        host_local, mesh, P(axis))


def to_host_local(mesh: Mesh, global_arr, axis: str = "data") -> np.ndarray:
    """Global sharded array → this host's rows (the reverse boundary)."""
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.global_array_to_host_local_array(
        global_arr, mesh, P(axis)))


def replicated_to_host(mesh: Mesh, global_arr) -> np.ndarray:
    from jax.experimental import multihost_utils
    return np.asarray(multihost_utils.global_array_to_host_local_array(
        global_arr, mesh, P()))


def exchange_host_partitions(mesh: Mesh, cols: Sequence[np.ndarray],
                             pids: np.ndarray, num_rows_local: int,
                             axis: str = "data"):
    """Cross-host hash exchange: every host feeds its local rows (padded
    to the shared per-device capacity), the global all-to-all routes each
    row to the device owning its partition id, and each host gets back
    the rows it owns.

    cols: host-local column arrays [local_cap * local_devices, ...]
    pids: int32 target GLOBAL device per row
    Returns (local_out_cols, local_out_num_rows) for THIS host.
    """
    from auron_tpu.parallel.mesh_exchange import exchange_device_batches
    n_local = len(jax.local_devices())
    per_dev = cols[0].shape[0] // n_local
    g_cols = tuple(to_global(mesh, np.asarray(c), axis) for c in cols)
    g_pids = to_global(mesh, np.asarray(pids, np.int32), axis)
    # per-device live-row counts for this host's devices
    counts = np.zeros(n_local, np.int32)
    remaining = num_rows_local
    for d in range(n_local):
        counts[d] = max(0, min(per_dev, remaining))
        remaining -= counts[d]
    g_counts = to_global(mesh, counts, axis)
    out_cols, out_nr, _quota = exchange_device_batches(
        mesh, g_cols, g_pids, g_counts)
    local_cols = [to_host_local(mesh, c, axis) for c in out_cols]
    local_nr = to_host_local(mesh, out_nr, axis)
    return local_cols, local_nr
