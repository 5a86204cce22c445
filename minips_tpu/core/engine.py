"""Engine — rebuild of the reference driver layer (SURVEY.md §1 L4, §3.1-3.2).

The reference ``Engine`` boots mailbox/id-mapper/server/worker-helper actors,
creates tables, runs ``MLTask`` UDFs on worker threads, and barriers. Here:

- ``StartEverything`` = build the device mesh (the mailbox/id-mapper
  equivalent — SURVEY.md §3.1's zmq bind/connect becomes mesh construction;
  on multi-host, ``jax.distributed.initialize`` upstream of this).
- ``CreateTable`` = allocate a Dense/Sparse table sharded over the mesh plus
  its consistency controller.
- ``Run(MLTask)`` = spawn one host thread per logical worker running the UDF
  against an ``Info`` handle — the threaded PS-emulation path that preserves
  the reference's programming model (UDF + pull/push/clock) and its
  BSP/SSP/ASP semantics exactly. Each worker thread drives jitted TPU
  compute; consistency gates live on the host (SURVEY.md §7.4).
- ``Barrier`` = join + controller barrier (the reference's mailbox barrier,
  SURVEY.md §3.4).

The *fast* path for BSP throughput is not threads: apps fuse the whole
iteration into one SPMD step via ``DenseTable.make_step`` and drive it from
a single host loop (SURVEY.md §7.1). The Engine exposes both because the
reference's distinctive capability — bounded staleness — needs per-worker
clocks, while the TPU-native capability — fused collectives — needs SPMD.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

import jax
import numpy as np

from minips_tpu.consistency import ConsistencyController, make_controller
from minips_tpu.core.config import TableConfig
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.tables.dense import DenseTable
from minips_tpu.tables.sparse import SparseTable


@dataclass
class MLTask:
    """UDF + worker allocation — reference ``MLTask`` (SURVEY.md §1 L4)."""

    fn: Optional[Callable[["Info"], Any]] = None
    num_workers: int = 0  # 0 = use the engine's worker count

    def set_lambda(self, fn: Callable[["Info"], Any]) -> "MLTask":
        self.fn = fn
        return self

    def set_worker_alloc(self, num_workers: int) -> "MLTask":
        self.num_workers = num_workers
        return self


class KVClientTable:
    """Worker-facing table handle — the reference's entire user-facing PS API
    (SURVEY.md §2 "KVClientTable"): ``pull``/``push``/``clock`` with the
    consistency gate applied on pull."""

    def __init__(self, table, controller: ConsistencyController,
                 worker_id: int, lock: threading.Lock):
        self._table = table
        self._controller = controller
        self._worker_id = worker_id
        self._lock = lock

    # Get/Pull: blocks until the consistency model admits (SURVEY.md §3.3).
    def pull(self, keys: Optional[np.ndarray] = None, timeout: float = 60.0):
        if not self._controller.wait_until_admitted(self._worker_id, timeout):
            raise TimeoutError(
                f"worker {self._worker_id} pull not admitted within "
                f"{timeout}s (min_clock={self._controller.min_clock}, "
                f"my_clock={self._controller.tracker.clock_of(self._worker_id)})")
        with self._lock:
            if keys is None:
                out = self._table.pull()
            elif isinstance(self._table, SparseTable):
                out = self._table.pull(keys)
            else:
                out = self._table.pull_keys(keys)
            # Materialize INSIDE the lock: reading a mesh-sharded table
            # compiles to a cross-device gather, and JAX dispatch is lazy —
            # returning the lazy value would let two worker threads run
            # collective programs concurrently, which deadlocks the
            # backend's rendezvous. A host copy also matches reference pull
            # semantics (the worker owns a snapshot, SURVEY.md §3.3), and
            # keeps worker-side grad jits single-device/collective-free.
            return jax.tree.map(np.asarray, out)

    # Add/Push: fire-and-forget-ish; server-side updater applies (§3.3).
    def push(self, grads, keys: Optional[np.ndarray] = None) -> None:
        with self._lock:
            if keys is None:
                self._table.push(grads)
            elif isinstance(self._table, SparseTable):
                self._table.push(keys, grads)
            else:
                self._table.push_keys(keys, grads)

    def clock(self) -> None:
        self._controller.clock(self._worker_id)

    @property
    def worker_id(self) -> int:
        return self._worker_id


@dataclass
class Info:
    """Handle passed into the UDF — reference ``Info`` (SURVEY.md §1 L4)."""

    worker_id: int
    num_workers: int
    tables: dict = field(default_factory=dict)

    def table(self, name: str) -> KVClientTable:
        return self.tables[name]


class Engine:
    """Driver: mesh bootstrap + tables + threaded task runner."""

    def __init__(self, num_workers: Optional[int] = None):
        self._requested_workers = num_workers
        self.mesh = None
        self.tables: dict[str, Any] = {}
        self.controllers: dict[str, ConsistencyController] = {}
        # ONE dispatch lock shared by every table: concurrent multi-device
        # *collective* programs from different worker threads deadlock the
        # backend rendezvous, and per-table locks would still allow a pull
        # on table A to race a pull on table B. All mesh-touching dispatch
        # in the threaded path serializes here.
        self._dispatch_lock = threading.Lock()
        self.num_workers = 0
        self._started = False

    # -------------------------------------------------------------- lifecycle
    def start_everything(self) -> "Engine":
        """Mesh bootstrap (SURVEY.md §3.1). Logical workers default to the
        mesh data-axis size; more logical workers than devices is allowed
        (they timeshare the chip — the single-chip dev story)."""
        self.mesh = make_mesh()
        self.num_workers = (self._requested_workers
                            or self.mesh.shape["data"])
        self._started = True
        return self

    def stop_everything(self) -> None:
        for c in self.controllers.values():
            c.stop()
        self._started = False

    # ----------------------------------------------------------------- tables
    def create_table(self, cfg: TableConfig, template=None,
                     tx=None) -> str:
        """Reference ``CreateTable(ModelType, StorageType)`` (SURVEY.md §1
        L4): storage kind from cfg.kind, consistency model from
        cfg.consistency, updater from cfg.updater."""
        assert self._started, "call start_everything() first"
        if cfg.kind == "dense":
            if template is None:
                raise ValueError("dense table needs a parameter template")
            table = DenseTable(template, self.mesh, name=cfg.name,
                               updater=cfg.updater, lr=cfg.lr, tx=tx)
        elif cfg.kind == "sparse":
            table = SparseTable(cfg.num_slots, cfg.dim, self.mesh,
                                name=cfg.name, updater=cfg.updater,
                                lr=cfg.lr, init_scale=cfg.init_scale)
        else:
            raise ValueError(f"unknown table kind {cfg.kind!r}")
        controller = make_controller(
            cfg.consistency, self.num_workers,
            staleness=cfg.staleness, sync_every=cfg.sync_every)
        return self.register_table(cfg.name, table, controller)

    def register_table(self, name: str, table,
                       controller: ConsistencyController) -> str:
        """Register an externally-built table with its controller (apps that
        construct tables directly, e.g. MF's user/item factor tables)."""
        assert self._started, "call start_everything() first"
        self.tables[name] = table
        self.controllers[name] = controller
        return name

    # ------------------------------------------------------------------- run
    def run(self, task: MLTask) -> list[Any]:
        """Spawn one host thread per logical worker running the UDF
        (SURVEY.md §3.2). Returns per-worker UDF results in worker order."""
        assert self._started and task.fn is not None
        n = task.num_workers or self.num_workers
        if n != self.num_workers:
            raise ValueError(
                f"task wants {n} workers but engine tables/controllers were "
                f"sized for {self.num_workers}")
        for c in self.controllers.values():
            c.reset_stop()  # a previous failed run() must not poison this one
        results: list[Any] = [None] * n
        errors: list[BaseException | None] = [None] * n

        def runner(wid: int) -> None:
            info = Info(
                worker_id=wid,
                num_workers=n,
                tables={
                    name: KVClientTable(tbl, self.controllers[name], wid,
                                        self._dispatch_lock)
                    for name, tbl in self.tables.items()
                },
            )
            try:
                results[wid] = task.fn(info)
            except BaseException as e:  # surfaced after join
                errors[wid] = e
                # unblock peers parked on this worker's clock
                for c in self.controllers.values():
                    c.stop()

        threads = [threading.Thread(target=runner, args=(w,), daemon=True)
                   for w in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        real = [e for e in errors if e is not None]
        if real:
            # Prefer the root cause: victim TimeoutErrors from the stop()
            # cascade must not mask the worker error that triggered it.
            root = next((e for e in real if not isinstance(e, TimeoutError)),
                        real[0])
            raise root
        return results

    def make_checkpointer(self, directory: str, **kwargs):
        """Checkpointer over every table + controller this engine owns
        (reference Dump/Load, SURVEY.md §3.5)."""
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        return make_checkpointer(directory, self.tables, self.controllers,
                            **kwargs)

    def barrier(self) -> None:
        """All logical workers are joined at the end of run(); a standalone
        barrier is only meaningful multi-host, where it delegates to the
        cluster coordination service (SURVEY.md §3.4)."""
        from minips_tpu.parallel.cluster import barrier as cluster_barrier
        cluster_barrier()
