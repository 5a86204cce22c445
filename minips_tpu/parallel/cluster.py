"""Multi-host bootstrap — the rebuild of the launch-script + mailbox bind.

The reference spawns one process per node via ssh with ``--my_id i`` and a
hostfile; the mailbox binds zmq ROUTER sockets (SURVEY.md §1 L7, §3.1). On
TPU pods the moral equivalent is ``jax.distributed.initialize`` — the
coordination service wires processes into one JAX runtime, after which the
*data plane* is XLA collectives over ICI/DCN and needs no sockets at all
(SURVEY.md §2.3). Only the SSP clock gossip + heartbeats keep a socket bus
(minips_tpu/comm/bus.py).

The launcher (minips_tpu/launch.py) exports ``MINIPS_COORDINATOR`` +
``MINIPS_PROC_ID``/``MINIPS_NUM_PROCS`` for every rank, so a worker that
calls :func:`initialize` with no arguments joins the job it was spawned
into; single-process (this sandbox, no launcher) everything degrades to
no-ops. The 2-process loopback smoke (tests/test_multihost.py) runs this
exact path on the CPU backend — the "threads as nodes" trick one level up:
processes as hosts.
"""

from __future__ import annotations

import os
from typing import Optional

import jax


def initialize(coordinator_address: Optional[str] = None,
               num_processes: Optional[int] = None,
               process_id: Optional[int] = None) -> bool:
    """Join the cluster. Mirrors the reference's ``--my_id`` flag surface:
    pass explicit args, or rely on the launcher's ``MINIPS_*`` env (or
    JAX's own ``JAX_COORDINATOR_ADDRESS``); single-process if none is
    present. Returns True iff a multi-process runtime was initialized.

    On the CPU loopback smoke each process fakes its local devices via
    ``xla_force_host_platform_device_count`` BEFORE calling this (see
    apps/multihost_example.py); jax.distributed then registers them with
    the coordination service automatically.
    """
    if coordinator_address is None:
        coordinator_address = os.environ.get("MINIPS_COORDINATOR") \
            or os.environ.get("JAX_COORDINATOR_ADDRESS")
    if num_processes is None and "MINIPS_NUM_PROCS" in os.environ:
        num_processes = int(os.environ["MINIPS_NUM_PROCS"])
    if process_id is None and "MINIPS_PROC_ID" in os.environ:
        process_id = int(os.environ["MINIPS_PROC_ID"])
    if coordinator_address is None:
        return False  # single-process (no launcher, no JAX cluster env)
    if num_processes is not None and num_processes <= 1:
        return False  # launcher run with --n 1
    # num_processes/process_id may legitimately still be None here (pure
    # JAX-standard env: JAX_NUM_PROCESSES/JAX_PROCESS_ID) — pass through
    # and let jax.distributed resolve them itself rather than silently
    # degrading a pod job to N independent single-process runs
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )
    return True


def process_index() -> int:
    return jax.process_index()


def process_count() -> int:
    return jax.process_count()


def barrier(name: str = "minips_barrier", timeout_s: int = 120) -> None:
    """Cluster-wide barrier (reference Engine::Barrier, SURVEY.md §3.4)."""
    if jax.process_count() == 1:
        return
    from jax.experimental import multihost_utils

    multihost_utils.sync_global_devices(name)


def shutdown() -> None:
    """Leave the cluster COORDINATED: barrier, then disconnect from the
    coordination service. Without the explicit disconnect, ranks race at
    interpreter exit — the coordinator (process 0) can die while a
    follower's error-polling thread is still attached, and that follower
    then terminates itself with a fatal 'leader task died' error AFTER
    its work (and its result line) completed: a clean run reported as
    rc!=0. Call this as the last cluster op of every multi-process job;
    single-process it is a no-op."""
    if jax.process_count() == 1:
        return
    barrier("minips_shutdown")
    jax.distributed.shutdown()


def global_batch(mesh, batch: dict, axis: str = "data",
                 spec=None) -> dict:
    """Per-process local batch leaves → ONE global array dict — the
    multi-host feeding step (each host contributes the slice it loaded;
    SURVEY.md §1 L5 "data shards per worker"). Default: rows sharded
    along ``axis`` (axis 0); pass ``spec`` (a PartitionSpec, or a dict of
    them keyed like ``batch``) to shard other axes — e.g.
    ``P(None, "data")`` feeds per-process SEQUENCE slices for ring-
    attention sequence parallelism. Single-process this is a plain
    device_put with the same sharding."""
    from jax.sharding import NamedSharding, PartitionSpec

    def sharding_for(k):
        if isinstance(spec, dict):
            if k not in spec:  # a typo'd key must not silently row-shard
                raise KeyError(
                    f"global_batch spec has no entry for batch key {k!r} "
                    f"(spec keys: {sorted(spec)})")
            s = spec[k]
        else:
            s = spec
        return NamedSharding(mesh, s if s is not None
                             else PartitionSpec(axis))

    if jax.process_count() == 1:
        return {k: jax.device_put(v, sharding_for(k))
                for k, v in batch.items()}
    return {k: jax.make_array_from_process_local_data(sharding_for(k), v)
            for k, v in batch.items()}


def host_copy(x):
    """Full host value of a (possibly non-addressable, multi-process
    sharded) array — the multi-host-safe ``np.asarray``. Collective: every
    process must call it on the same array."""
    import numpy as np

    if getattr(x, "is_fully_addressable", True):
        return np.asarray(x)
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(x, tiled=True))
