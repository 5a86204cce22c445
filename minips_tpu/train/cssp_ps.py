"""CollectiveSSPPS — the consistency axis over the FLAGSHIP workload.

``train/ssp_spmd.py``'s CollectiveSSP proves the north-star clause ("the
consistency controller gates XLA collective barriers", BASELINE.json:5)
on a dense LR table; this module takes the same axis to the workloads the
reference is actually about (SURVEY §7.4.1 + §2.2): W&D/DeepFM's hashed
SparseTables + dense deep tower (``PSTrainStep``), i.e. sparse embedding
PS shards under BSP/SSP/ASP.

The one structural problem beyond dense CSSP: a sparse table's
cross-process delta is TABLE-shaped if merged densely — 2^26 slots of
Criteo embeddings cannot ride a per-sync all-reduce. But each process
only ever touches the slots its batches hashed to, so the honest merge is
ROW-SPARSE:

- every process accumulates its touched slot ids host-side (the same
  ``hash_to_slots_np`` twin the sharded PS routes with — bit-identical
  to the device hash by test);
- at each sync round the processes allgather their touched-id arrays
  over the control bus (``comm.bus.BlobExchange`` — host wire, sized by
  batch rows x sync_every, never by the table) and compute the same
  sorted UNION;
- ONE ``[C, row]`` delta block per table leaf (embedding + optimizer
  rows) rides the collective plane (``SyncPlane.allreduce_sum`` — the
  psum's replica groups cross the process boundary), where C = the
  union size rounded to a power of two. Traffic is O(touched-rows x
  dim), never O(num_slots x dim) — the same batch-sized-traffic
  invariant tests/test_sharded_traffic.py pins for the pull/push plane.

Merge semantics per leaf (the additive replicated-PS rule, applied to
rows): ``new = base + Σ_p (leaf_p − base)`` over the union rows. Rows
touched by nobody are equal to base on every replica already, so the
union merge is EXACT vs a dense merge. For the OPTIMIZER rows:

- sgd has no state — exact;
- adagrad accumulators are sums of squared gradients, an order-free
  additive quantity — the merged accumulator is EXACTLY the accumulator
  a centralized server would hold after the same pushes;
- adam rows (m/v EMAs + per-row step counts) merge additively too: the
  step counts are exact totals, the moments are the local-SGD-family
  approximation documented in docs/consistency.md (same honesty note as
  the dense-table moments).

The deep tower (DenseTable) syncs exactly like CollectiveSSP's dense
vector, including the same optimizer-state stance (see
``opt_sync`` there / docs/consistency.md).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.comm.bus import BlobExchange
from minips_tpu.consistency.gate import publish_clock
from minips_tpu.parallel.mesh import DATA_AXIS
from minips_tpu.tables.dense import DenseTable
from minips_tpu.tables.sparse import SparseTable, hash_to_slots_np, next_pow2
from minips_tpu.train.ssp_spmd import (SyncPlane, avg_table_opt_state,
                                        check_avg_opt_sync_supported,
                                        is_avg_leaf, make_control)

__all__ = ["CollectiveSSPPS", "sync_block_rows"]

PyTree = Any


def sync_block_rows(union_size: int, n_local: int) -> int:
    """Rows of the per-sync delta block: the union size rounded up to a
    power of two (keeps the retrace count small — the jitted merge
    recompiles per shape) and then up to a MULTIPLE of ``n_local``
    (shard_map over the local mesh axis needs even divisibility; a
    6-device host would otherwise get C=8 and abort in the sharding
    check, since next_pow2 is only divisible by non-power-of-two device
    counts by luck)."""
    c = max(next_pow2(int(union_size)), int(n_local))
    return -(-c // int(n_local)) * int(n_local)


class CollectiveSSPPS:
    """Local fused PSTrainStep per process; staleness-gated row-sparse
    collective syncs for its sparse tables, vector syncs for its dense
    tables.

    Parameters
    ----------
    build_fn: ``(local_mesh) -> (ps, tables)`` — constructs the fused
        step and its tables ON THE GIVEN MESH (each process's own
        devices). ``tables`` is a name->table dict; DenseTable and
        SparseTable entries are synced, anything else refuses loudly.
        Every process must build identical tables (same seeds) — the
        additive merge assumes a common base.
    staleness / sync_every / bus / monitor: as CollectiveSSP. The bus is
        REQUIRED multi-process: both the clock gossip and the touched-row
        union exchange ride it.
    """

    def __init__(
        self,
        build_fn: Callable,
        *,
        staleness: float = 0,
        sync_every: int = 1,
        bus=None,
        monitor=None,
        gate_timeout: float = 60.0,
        exchange_timeout: float = 120.0,
        opt_sync: str = "local",
    ):
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        if opt_sync not in ("local", "avg"):
            raise ValueError(f"opt_sync must be 'local' or 'avg', got "
                             f"{opt_sync!r}")
        self.opt_sync = opt_sync
        self.staleness = staleness
        self.sync_every = int(sync_every)
        self.nprocs = jax.process_count()
        if self.nprocs > 1 and bus is None:
            raise ValueError(
                "CollectiveSSPPS needs the control bus in multi-process "
                "runs: clock gossip AND the touched-row union exchange "
                "ride it (pass bus= from launch.init_from_env)")
        # register the blob handler BEFORE build_fn: a fast peer may
        # publish its first union while we are still compiling in
        # build_fn, and pub/sub drops frames with no handler (the
        # exchange also re-publishes while waiting, so either side of
        # the race is covered)
        self.exchange = (BlobExchange(bus, self.nprocs)
                         if bus is not None and self.nprocs > 1 else None)

        self.plane = SyncPlane()
        self.local_mesh = self.plane.local_mesh
        self.sync_mesh = self.plane.mesh
        self.ps, tables = build_fn(self.local_mesh)
        for name, t in tables.items():
            if not isinstance(t, (DenseTable, SparseTable)):
                raise TypeError(f"table {name!r} is {type(t).__name__}; "
                                "CollectiveSSPPS syncs DenseTable and "
                                "SparseTable state only")
        self.dense = {k: t for k, t in tables.items()
                      if isinstance(t, DenseTable)}
        self.sparse = {k: t for k, t in tables.items()
                       if isinstance(t, SparseTable)}
        if opt_sync == "avg":
            for t in self.dense.values():
                check_avg_opt_sync_supported(t)
            # sparse opt ROWS already merge additively in _sync_sparse —
            # exact for adagrad (order-free sums), documented heuristic
            # for adam moments; 'avg' only changes the DENSE tables
        for name, t in self.sparse.items():
            if self.ps.key_fns.get(name) is None:
                raise ValueError(
                    f"sparse table {name!r} has no key_fn on the fused "
                    "step — the host-side touched-slot tracking needs it")

        # ---- base snapshots (params = base + Σ deltas across procs) --
        self._copy = jax.jit(jnp.copy)
        self._sub = jax.jit(lambda a, b: a - b)
        self._add = jax.jit(lambda a, b: a + b)
        self._dense_base = {k: self._copy(t.params)
                            for k, t in self.dense.items()}
        self._sparse_base = {
            k: {ln: self._copy(leaf) for ln, leaf in self._leaves(t)}
            for k, t in self.sparse.items()}

        # ---- row-sparse merge programs (retrace per union size C) ----
        self._rep_sharding = NamedSharding(self.local_mesh, P())
        vec_sharding = NamedSharding(self.local_mesh, P(DATA_AXIS))

        def rows_delta(cur, base, idx):
            # idx is padded to C with num_slots (out of bounds): fill-0
            # gathers make padding rows contribute nothing to the psum
            d = (cur.at[idx].get(mode="fill", fill_value=0)
                 - base.at[idx].get(mode="fill", fill_value=0))
            return d.reshape(-1)

        self._rows_delta = jax.jit(rows_delta, out_shardings=vec_sharding)
        self._apply_cache: dict = {}

        # ---- host-side control plane -----------------------------------
        self.clock = 0
        self.sync_rounds = 0
        self._synced_at = 0
        self._monitor = monitor
        self._xt = float(exchange_timeout)
        self.gossip, self._gate = make_control(
            bus, self.nprocs, staleness, monitor=monitor,
            timeout=gate_timeout)
        self._touched: dict[str, set] = {k: set() for k in self.sparse}
        self.sync_rows_max = 0       # largest padded union C seen
        self.union_wire_bytes = 0    # host-wire bytes of the id exchange
        self._last_emb_len = 0       # C*dim of the last emb merge (HLO)

    # -------------------------------------------------------------- helpers
    @staticmethod
    def _leaves(t: SparseTable):
        """(name, array) pairs of a sparse table's row-indexed state."""
        return [("emb", t.emb)] + [(k, getattr(t, k))
                                   for k in t._OPT_KEYS[t.updater]]

    def _apply_for(self, sharding):
        """Jitted (cur, base, idx, merged) -> (cur', base') preserving the
        leaf's sharding; cached per sharding (retraces per shape)."""
        fn = self._apply_cache.get(sharding)
        if fn is None:
            def rows_apply(cur, base, idx, merged_flat):
                rows = merged_flat.reshape((idx.shape[0],) + cur.shape[1:])
                new_rows = base.at[idx].get(mode="fill", fill_value=0) \
                    + rows
                # out-of-bounds padding indices DROP: padding writes
                # nothing, real rows land once (the union is unique)
                return (cur.at[idx].set(new_rows, mode="drop"),
                        base.at[idx].set(new_rows, mode="drop"))

            fn = jax.jit(rows_apply, out_shardings=(sharding, sharding))
            self._apply_cache[sharding] = fn
        return fn

    # ------------------------------------------------------------ metrics
    @property
    def gate_waits(self) -> int:
        return self._gate.gate_waits if self._gate else 0

    @property
    def max_skew_seen(self) -> int:
        return self._gate.max_skew_seen if self._gate else 0

    def sync_hlo(self) -> str:
        """HLO of the LAST embedding-row merge — union-sized by
        construction; smokes assert it contains an all-reduce whose
        operand is C*dim elements, not num_slots*dim."""
        if not self._last_emb_len:
            raise RuntimeError("no row merge has run yet")
        return self.plane.sync_hlo(self._last_emb_len)

    # ------------------------------------------------------------------ api
    def step(self, batch) -> float:
        """One LOCAL fused step on my batch rows, touched-slot tracking,
        clock tick, SSP gate, then (at sync boundaries) the merges. Gate
        placement matches CollectiveSSP (step, clock++, publish, wait)."""
        loss = self.ps(self.ps.shard_batch(batch))
        for name, t in self.sparse.items():
            keys = np.asarray(self.ps.key_fns[name](batch))
            slots = hash_to_slots_np(keys.reshape(-1), t.num_slots,
                                     t.salt, t.identity)
            self._touched[name].update(np.unique(slots).tolist())
        self.clock += 1
        if self._gate is not None:
            publish_clock(self.gossip, self.clock, False)
            self._gate.wait(self.clock)
        if self.clock % self.sync_every == 0:
            self._sync()
        return float(loss)

    def _sync(self) -> None:
        """One merge round: dense vectors then sparse row blocks, every
        table in sorted-name order so all processes launch the same
        collective sequence."""
        rnd = self.sync_rounds
        if self.nprocs == 1:
            # a merge with zero peers is the IDENTITY — and it must be
            # bitwise (``base + (params − base)`` re-rounds in float, so
            # running the arithmetic would perturb a single-process
            # trajectory away from the raw fused-step run the fast tier
            # pins). Only the bases refresh.
            for name, t in self.dense.items():
                self._dense_base[name] = self._copy(t.params)
            for name, t in self.sparse.items():
                self._touched[name].clear()
                self._sparse_base[name] = {
                    ln: self._copy(leaf) for ln, leaf in self._leaves(t)}
            self.sync_rounds += 1
            self._synced_at = self.clock
            return
        for name in sorted(self.dense):
            t = self.dense[name]
            delta = self._sub(t.params, self._dense_base[name])
            # the plane blocks per collective (SyncPlane.allreduce_sum:
            # one in flight at a time, or Gloo communicator setup races)
            merged = self.plane.allreduce_sum(delta)
            new = self._add(self._dense_base[name], merged)
            t.params = new
            self._dense_base[name] = self._copy(new)
            if self.opt_sync == "avg":
                avg_table_opt_state(t, self.plane)
        for name in sorted(self.sparse):
            self._sync_sparse(rnd, name)
        self.sync_rounds += 1
        self._synced_at = self.clock

    def _sync_sparse(self, rnd: int, name: str) -> None:
        t = self.sparse[name]
        mine = np.asarray(sorted(self._touched[name]), dtype=np.int64)
        self._touched[name].clear()
        # multi-process by construction: nprocs==1 took _sync's identity
        # path, and __init__ rejected bus=None for nprocs>1
        assert self.exchange is not None
        parts = self.exchange.allgather(rnd, name, mine,
                                        timeout=self._xt,
                                        monitor=self._monitor)
        self.union_wire_bytes += sum(int(p.nbytes) for p in parts)
        union = (np.unique(np.concatenate(parts))
                 if any(p.size for p in parts) else mine)
        if union.size == 0:
            return  # nobody touched this table: replicas already agree
        C = sync_block_rows(union.size, self.plane.n_local)
        self.sync_rows_max = max(self.sync_rows_max, C)
        idx = np.full(C, t.num_slots, np.int64)
        idx[: union.size] = union
        idxd = jax.device_put(jnp.asarray(idx, jnp.int32),
                              self._rep_sharding)
        bases = self._sparse_base[name]
        for lname, leaf in self._leaves(t):
            delta = self._rows_delta(leaf, bases[lname], idxd)
            if lname == "emb":
                self._last_emb_len = int(delta.shape[0])
            merged = self.plane.allreduce_sum(delta)
            new_leaf, new_base = self._apply_for(leaf.sharding)(
                leaf, bases[lname], idxd, merged)
            if lname == "emb":
                t.emb = new_leaf
            else:
                setattr(t, lname, new_leaf)
            bases[lname] = new_base

    def finalize(self) -> None:
        """Merge any unsynced tail; afterwards every process holds
        identical tables. All processes call this together (it may launch
        one last round of collectives). Idempotent at the same clock —
        an unmatched extra collective on one process would hang the job."""
        if self.clock != self._synced_at:
            self._sync()

    def fingerprint(self) -> float:
        """One float over ALL synced state — dense params, sparse emb AND
        the sparse optimizer rows (they merge additively every round),
        plus dense opt state when opt_sync='avg' reconciles it. Equal
        across processes after finalize; a broken merge of ANY synced
        leaf breaks the equality, not just a param one."""
        total = 0.0
        for name in sorted(self.dense):
            t = self.dense[name]
            total += float(np.asarray(t.params, dtype=np.float64).sum())
            if self.opt_sync == "avg":
                for leaf in jax.tree.leaves(t.opt_state):
                    if is_avg_leaf(leaf, t.padded):
                        total += float(np.asarray(leaf,
                                                  dtype=np.float64).sum())
        for name in sorted(self.sparse):
            for _, leaf in self._leaves(self.sparse[name]):
                total += float(np.asarray(leaf, dtype=np.float64).sum())
        return total
