"""CollectiveSSP — BSP/SSP/ASP whose SYNC is an XLA collective.

This is SURVEY.md §7.4.1 implemented as written — the one north-star
clause ("the consistency controller gates XLA collective barriers",
BASELINE.json:5) the host-relay paths don't embody:

- each process drives its OWN jitted shard-local fused step
  (``DenseTable.make_step`` over a per-process mesh: pull/push collectives
  stay on intra-host ICI);
- the cross-host sync is an explicit COLLECTIVE the host chooses to
  launch — a ``psum`` of parameter deltas over a ``(proc, local)`` global
  mesh, compiled by XLA into an all-reduce whose replica groups cross the
  process boundary (DCN on a pod; Gloo on the CPU loopback smoke). No
  parameter bytes ever ride the zmq bus;
- the SSP gate is host-side: the clock vector gossips over the control
  bus (``ClockGossip``) and the shared ``StalenessGate`` blocks a fast
  host before local step ``c+1`` until ``global_min >= c + 1 - s``
  (s=0 BSP lockstep, s>0 SSP, inf ASP-never-waits) — SURVEY §7.4.1's
  "blocking the fast host's sync when my_clock − min_clock > s".

Sync semantics are the relay path's additive replicated-PS rule
(train/ssp_trainer.py): every process applies the SUM of all processes'
parameter deltas since the last sync, so after a sync every replica holds
``base + Σ_p delta_p`` — bitwise-identical state across processes (the
all-reduce gives every participant the same reduction result). Between
syncs, replicas drift by their own local updates; the staleness gate
bounds that drift in CLOCK distance, exactly SSP's contract.

Collective rendezvous constraint (inherent, documented): sync rounds are
launched at fixed clocks (every ``sync_every`` local steps), so every
process must take the same number of steps — XLA collectives need all
participants. Dynamic retirement / uneven step counts stay on the
host-relay paths (SSPTrainer), which have no such constraint. ASP here is
therefore bounded-rendezvous local SGD: the gate never blocks, but the
periodic merge still does — the same drift honesty as
docs/consistency.md's SPMD-ASP note, now with the merge on the collective
plane.
"""

from __future__ import annotations

import os
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.comm.bus import ClockGossip
from minips_tpu.consistency.gate import StalenessGate, publish_clock
from minips_tpu.parallel.mesh import DATA_AXIS
from minips_tpu.tables.dense import DenseTable

__all__ = ["CollectiveSSP", "SyncPlane", "make_control"]

PyTree = Any


def _process_local_devices(all_devices, proc_index):
    """The global view of one process's devices, in the order every
    process can reconstruct (jax.devices() is globally ordered)."""
    return [d for d in all_devices if d.process_index == proc_index]


class SyncPlane:
    """The (proc, local) global mesh + the jitted psum-over-proc merge —
    the collective sync plumbing shared by every CollectiveSSP-family
    trainer (dense vector deltas here; row-sparse blocks in
    train/cssp_ps.py ride the same plane with different lengths — the
    one jitted merge retraces per shape/dtype, so callers round lengths
    to powers of two to keep the compile count small)."""

    def __init__(self):
        all_devs = list(jax.devices())
        self.nprocs = jax.process_count()
        me = jax.process_index()
        mine = _process_local_devices(all_devs, me)
        if mine != list(jax.local_devices()):
            # the (proc, local) sync mesh below assumes the global device
            # order restricted to one process IS that process's local
            # order; true for every backend here, but a silent mismatch
            # would scatter delta shards to wrong columns
            raise RuntimeError("jax.devices() per-process order differs "
                               "from jax.local_devices() — sync mesh "
                               "construction needs them equal")
        self.local_mesh = Mesh(np.asarray(mine), (DATA_AXIS,))
        self.n_local = len(mine)
        grid = np.array(
            [_process_local_devices(all_devs, p)
             for p in range(self.nprocs)])
        self.mesh = Mesh(grid, ("proc", "local"))
        self._gspec = NamedSharding(self.mesh, P("proc", "local"))

        def merge(block):             # [1, length/L] on each device
            return jax.lax.psum(block, "proc")

        self._merge = jax.jit(jax.shard_map(
            merge, mesh=self.mesh,
            in_specs=P("proc", "local"), out_specs=P(None, "local")))
        self._mean_cache: dict = {}
        self._qmerge_cache: dict = {}
        self._pad_cache: dict = {}
        self._slice_cache: dict = {}

    def allreduce_sum(self, vec: jax.Array) -> jax.Array:
        """Sum a local-mesh-sharded vector across processes: local shards
        become one ROW of the (nprocs, length) global array device-to-
        device (no host copy), the psum's replica groups cross the
        process boundary (DCN on a pod), and the replicated result maps
        back to a local-mesh vector with the caller's sharding.

        BLOCKS before returning — the plane runs one collective in
        flight at a time. A sync round launches MANY distinct collective
        programs (per table, per optimizer leaf, row merges retraced per
        union size); letting them pile up in the async dispatch queue
        intermittently deadlocked the Gloo communicator setups on the
        loopback smokes (both ranks stuck inside a LOCAL jit while the
        backend blocked on a half-constructed communicator). The sync is
        a rendezvous anyway, so serializing costs only pipelining the
        merge with local work it never overlapped usefully."""
        n = int(vec.shape[0])
        shards = sorted(vec.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        rows = [s.data.reshape(1, -1) for s in shards]
        garr = jax.make_array_from_single_device_arrays(
            (self.nprocs, n), self._gspec, rows)
        merged = jax.block_until_ready(self._merge(garr))
        cols = sorted(merged.addressable_shards,
                      key=lambda s: s.index[1].start or 0)
        return jax.make_array_from_single_device_arrays(
            (n,), vec.sharding, [s.data.reshape(-1) for s in cols])

    def sync_hlo(self, length: int, dtype=jnp.float32) -> str:
        """Compiled HLO of the merge at this length — the comm_analysis
        hook: tests/smokes assert the cross-host sync IS a collective op
        (and, for the row-sparse plane, that its operand is union-sized,
        not table-sized)."""
        shape = jax.ShapeDtypeStruct((self.nprocs, length), dtype,
                                     sharding=self._gspec)
        return self._merge.lower(shape).compile().as_text()

    # ---------------------------------------------- quantized sync wire
    def _q_merge_for(self, comm: str):
        """Jitted quantized all-reduce over 'proc' (cached per comm),
        built on the SAME wire primitives as the pull/push plane
        (ops/quantized_comm.py: ``a2a_reduce`` + ``gather_broadcast`` —
        one source of truth for the wire format): reduce leg = a2a of
        compressed chunks + f32 accumulation; replicate leg = all-gather
        of the compressed merged chunk, which every process dequantizes
        IDENTICALLY — replicas stay bitwise equal, the CollectiveSSP
        invariant. Returns (merged, sent, gap): ``sent`` is my
        contribution after the reduce-leg compression; ``gap`` is the
        replicate-leg compression error of MY reduced chunk, placed at
        its position in my vector — folding BOTH into the residual makes
        error feedback cover both legs, so neither bias accumulates."""
        fn = self._qmerge_cache.get(comm)
        if fn is not None:
            return fn
        from minips_tpu.ops.quantized_comm import (a2a_reduce,
                                                   gather_broadcast)

        def merge_q(block):            # [1, Lb] on each device
            n = jax.lax.axis_size("proc")
            v = block.reshape(n, -1)   # my row split into per-proc chunks
            c = v.shape[1]
            mine, sent = a2a_reduce(v, "proc", comm)
            full, gap_c = gather_broadcast(mine, "proc", comm)
            # my reduced chunk sits at offset p*c of this Lb segment —
            # scatter its gap there so it folds into my residual
            p = jax.lax.axis_index("proc")
            gap = jax.lax.dynamic_update_slice(
                jnp.zeros(n * c, jnp.float32), gap_c, (p * c,))
            return (full.reshape(1, -1), sent.reshape(1, -1),
                    gap.reshape(1, -1))

        # check_vma=False: the merged output IS replicated over 'proc'
        # (every process all-gathers the same compressed chunks and
        # dequantizes identically), but the varying-axis checker cannot
        # infer replication through all_gather the way it can through
        # psum
        fn = jax.jit(jax.shard_map(
            merge_q, mesh=self.mesh, in_specs=P("proc", "local"),
            out_specs=(P(None, "local"), P("proc", "local"),
                       P("proc", "local")),
            check_vma=False))
        self._qmerge_cache[comm] = fn
        return fn

    def allreduce_sum_ef(self, vec: jax.Array, comm: str):
        """Quantized-wire all-reduce with the error-feedback hook:
        returns ``(merged, sent, gap)`` as local-mesh vectors. Callers
        keep ``residual = send − sent + gap`` and add it to the next
        round's delta — EF over BOTH compression points (my reduce-leg
        contribution and my chunk's replicate-leg broadcast), so
        compression bias cannot accumulate. The vector is zero-padded so
        each device row splits evenly into per-process chunks; padding
        compresses to zeros and is sliced off on return."""
        if comm == "float32":
            raise ValueError("allreduce_sum_ef is for compressed wires; "
                             "use allreduce_sum for float32")
        L = int(vec.shape[0])
        M = self.n_local * self.nprocs
        padded = -(-L // M) * M
        if padded != L:
            key = (L, padded, vec.dtype, vec.sharding)
            pad_fn = self._pad_cache.get(key)
            if pad_fn is None:
                pad_fn = jax.jit(
                    lambda x: jnp.zeros(padded, x.dtype).at[:L].set(x),
                    out_shardings=vec.sharding)
                self._pad_cache[key] = pad_fn
            vec_p = pad_fn(vec)
        else:
            vec_p = vec
        shards = sorted(vec_p.addressable_shards,
                        key=lambda s: s.index[0].start or 0)
        rows = [s.data.reshape(1, -1) for s in shards]
        garr = jax.make_array_from_single_device_arrays(
            (self.nprocs, padded), self._gspec, rows)
        # block: one collective in flight at a time (see allreduce_sum)
        merged_g, sent_g, gap_g = jax.block_until_ready(
            self._q_merge_for(comm)(garr))

        def back(arr):
            cols = sorted(arr.addressable_shards,
                          key=lambda s: s.index[1].start or 0)
            return jax.make_array_from_single_device_arrays(
                (padded,), vec_p.sharding,
                [s.data.reshape(-1) for s in cols])

        outs = [back(merged_g), back(sent_g), back(gap_g)]
        if padded != L:
            key = (L, vec.dtype, vec.sharding)
            slice_fn = self._slice_cache.get(key)
            if slice_fn is None:
                slice_fn = jax.jit(lambda x: x[:L],
                                   out_shardings=vec.sharding)
                self._slice_cache[key] = slice_fn
            outs = [slice_fn(o) for o in outs]
        return tuple(outs)

    def sync_hlo_q(self, length: int, comm: str) -> str:
        """Compiled HLO of the quantized merge — smokes assert the wire
        collectives are all-to-all/all-gather of the COMPRESSED dtype."""
        M = self.n_local * self.nprocs
        padded = -(-length // M) * M
        shape = jax.ShapeDtypeStruct((self.nprocs, padded), jnp.float32,
                                     sharding=self._gspec)
        return self._q_merge_for(comm).lower(shape).compile().as_text()

    def allreduce_mean(self, vec: jax.Array) -> jax.Array:
        """psum-AVERAGE a float leaf across processes — the
        ``opt_sync='avg'`` moment reconciliation: accumulate in f32
        (bf16 moments must not lose mantissa to the reduction itself),
        divide by the process count, cast back to the leaf's dtype."""
        dt = jnp.dtype(vec.dtype)
        fns = self._mean_cache.get(dt)
        if fns is None:
            n = self.nprocs
            up = jax.jit(lambda x: x.astype(jnp.float32))
            down = jax.jit(lambda x: (x / n).astype(dt))
            fns = self._mean_cache[dt] = (up, down)
        up, down = fns
        v = vec if dt == jnp.float32 else up(vec)
        return down(self.allreduce_sum(v))


def staleness_for(mode: str, ssp_staleness: int) -> float:
    """The one mode→staleness encoding (bsp pins 0, asp pins inf) shared
    by every CollectiveSSP-family runner — lr, wd, and lm must not be
    able to drift on what a mode means."""
    return {"bsp": 0, "ssp": ssp_staleness, "asp": float("inf")}[mode]


def make_control(bus, nprocs: int, staleness: float, *,
                 monitor=None, timeout: float = 60.0):
    """(gossip, gate) for the host-side consistency control plane, or
    (None, None) when single-process or bus-less — callers enforce their
    own bus-requirement rules before this."""
    if bus is None or nprocs <= 1:
        return None, None
    gossip = ClockGossip(bus, nprocs, workers_per_process=1)
    return gossip, StalenessGate(gossip, staleness, timeout=timeout,
                                 monitor=monitor)


def check_avg_opt_sync_supported(table: DenseTable) -> None:
    """opt_sync='avg' refusal for quantized moments: adam8's uint8 codes
    + blockwise scales have no meaningful elementwise mean, and silently
    averaging nothing would be the requested reconciliation not
    happening."""
    from minips_tpu.tables.updaters import Adam8bitState

    leaves = jax.tree.leaves(
        table.opt_state, is_leaf=lambda x: isinstance(x, Adam8bitState))
    if any(isinstance(x, Adam8bitState) for x in leaves):
        raise ValueError(
            "opt_sync='avg' cannot average adam8's quantized moments; "
            "use opt_sync='local' (drift documented in "
            "docs/consistency.md) or adam/adam_bf16")


def is_avg_leaf(leaf, padded: int) -> bool:
    """THE predicate for which opt-state leaves opt_sync='avg' touches:
    float params-length vectors (adam/adam_bf16 moments, adagrad
    accumulators, momentum traces). One definition — the reconciliation,
    the fingerprint, the oracle simulation, and the drift test all key
    on it, so 'which leaves count' cannot silently diverge between the
    implementation and its spec/observables."""
    return (getattr(leaf, "ndim", None) == 1 and leaf.shape[0] == padded
            and jnp.issubdtype(leaf.dtype, jnp.floating))


def avg_table_opt_state(table: DenseTable, plane: SyncPlane) -> None:
    """The ``opt_sync='avg'`` reconciliation for one dense table: every
    ``is_avg_leaf`` opt leaf is psum-averaged across processes. Scalar
    counts stay local — sync rounds happen at fixed clocks, so they are
    equal everywhere already. Runs INSIDE the sync round, so it is part
    of the same rendezvous as the param merge."""
    table.opt_state = jax.tree.map(
        lambda leaf: (plane.allreduce_mean(leaf)
                      if is_avg_leaf(leaf, table.padded) else leaf),
        table.opt_state)


class CollectiveSSP:
    """Local jitted steps per process; staleness-gated collective syncs.

    Parameters
    ----------
    template: parameter pytree (identical on every process).
    grad_fn: ``(params, batch) -> (loss, grads)`` for the local fused
        step (``DenseTable.make_step`` semantics, run on the per-process
        mesh).
    staleness: 0 = BSP lockstep, s = SSP bounded staleness,
        ``float('inf')`` = ASP (gate never blocks; syncs still rendezvous).
    sync_every: launch the collective merge every k local steps. The skew
        the gate can actually permit is ``min(staleness, steps to the
        next sync boundary)`` — the collective is its own barrier.
    bus: the launcher's ControlBus for clock gossip (None single-process).
    monitor: optional HeartbeatMonitor; a gate timeout consults it so a
        dead peer raises PeerFailureError instead of hanging the gate.
    opt_sync: what happens to OPTIMIZER state at each merge.
        ``"local"`` (default): nothing — each process's moments evolve
        against its locally-drifting params between syncs; exact for
        sgd, a local-SGD-family heuristic for stateful updaters, with
        the drift documented and pinned in docs/consistency.md.
        ``"avg"``: psum-AVERAGE every float params-length opt leaf
        alongside the param deltas (adam/adam_bf16 moments, adagrad
        accumulators; f32 accumulation, scalar counts stay local — they
        are equal at the fixed sync clocks anyway). adam8's quantized
        moments cannot be averaged and refuse loudly.
    """

    def __init__(
        self,
        template: PyTree,
        grad_fn: Callable,
        *,
        updater: str = "sgd",
        lr=0.1,
        staleness: float = 0,
        sync_every: int = 1,
        bus=None,
        monitor=None,
        gate_timeout: float = 60.0,
        name: str = "cssp",
        opt_sync: str = "local",
        sync_comm: str = "float32",
    ):
        if opt_sync not in ("local", "avg"):
            raise ValueError(f"opt_sync must be 'local' or 'avg', got "
                             f"{opt_sync!r}")
        self.opt_sync = opt_sync
        from minips_tpu.ops.quantized_comm import _check as _check_comm
        _check_comm(sync_comm)
        self.sync_comm = sync_comm
        if sync_comm != "float32" and opt_sync == "avg":
            raise ValueError(
                "sync_comm compression + opt_sync='avg' is not wired: "
                "the moment average would ride the full-precision plane "
                "while the deltas ride the compressed one — a misleading "
                "half-measure; pick one lever per run")
        if sync_every < 1:
            raise ValueError("sync_every must be >= 1")
        self.staleness = staleness
        self.sync_every = int(sync_every)
        self.nprocs = jax.process_count()
        self._me = jax.process_index()
        if bus is None and self.nprocs > 1 and staleness < sync_every:
            # without the bus there is NO clock gossip: skew would grow
            # to sync_every (the collective is the only barrier left)
            # while gate_waits/max_skew_seen report zeros — the requested
            # consistency contract silently not enforced. Refuse loudly
            # (house rule); staleness >= sync_every is allowed bus-less
            # because the rendezvous itself bounds skew below s.
            raise ValueError(
                f"staleness {staleness} < sync_every {sync_every} needs "
                "the control bus for clock gossip in a multi-process "
                "run; pass bus= (launch.init_from_env) or raise "
                "staleness/sync alignment")

        # ---- local data plane: the fused step on MY devices only -----
        self.plane = SyncPlane()
        self.local_mesh = self.plane.local_mesh
        self.sync_mesh = self.plane.mesh
        self.table = DenseTable(template, self.local_mesh, name=name,
                                updater=updater, lr=lr)
        if opt_sync == "avg":
            check_avg_opt_sync_supported(self.table)
        self._step = self.table.make_step(grad_fn)
        self._n_local = self.plane.n_local

        self._copy = jax.jit(jnp.copy)
        # params = base + sum_of_deltas; base snapshot is refreshed to a
        # SEPARATE buffer after each sync (the fused step donates its
        # params argument, so base must never alias the live params)
        self._apply = jax.jit(lambda base, merged: base + merged)
        self._delta = jax.jit(lambda params, base: params - base)
        self._base = self._copy(self.table.params)
        self._residual = None
        if sync_comm != "float32":
            # error-feedback state: what compression dropped last round
            # rides into this round's delta, so the bias cannot
            # accumulate (the standard EF-SGD recipe, over both wire
            # legs — see SyncPlane.allreduce_sum_ef)
            self._residual = self._copy(
                jax.jit(jnp.zeros_like)(self.table.params))
            self._ef = jax.jit(
                lambda send, sent, gap: send - sent + gap)

        # ---- host-side control plane: clock gossip + staleness gate --
        self.clock = 0
        self.sync_rounds = 0
        self._synced_at = 0  # clock of the last merge (finalize idempotence)
        self.gossip, self._gate = make_control(
            bus, self.nprocs, staleness, monitor=monitor,
            timeout=gate_timeout)

    # ------------------------------------------------------------ metrics
    @property
    def gate_waits(self) -> int:
        return self._gate.gate_waits if self._gate else 0

    @property
    def max_skew_seen(self) -> int:
        return self._gate.max_skew_seen if self._gate else 0

    @property
    def params(self) -> PyTree:
        return self.table.pull()

    # ------------------------------------------------------------- plumbing
    def sync_hlo(self) -> str:
        """Compiled HLO of the ACTIVE sync program — the comm_analysis
        hook: the test/smoke asserts the cross-host sync IS a collective
        op (and, compressed, that the wire ops carry the compressed
        dtype; nothing else ever leaves the process on the data
        plane)."""
        if self.sync_comm != "float32":
            return self.plane.sync_hlo_q(self.table.padded,
                                         self.sync_comm)
        return self.plane.sync_hlo(self.table.padded,
                                   self.table.params.dtype)

    # ------------------------------------------------------------------ api
    def step(self, batch) -> float:
        """One LOCAL step, clock tick, SSP gate, then (at sync-every
        boundaries) the collective merge. ``batch`` is my process's local
        rows; leaves are placed sharded over my local mesh.

        Gate placement matches SSPTrainer (step, clock++, publish, wait):
        after completing step ``c`` block until ``global_min >= c - s`` —
        at s=0 that is BSP lockstep with transient skew <= 1, and the
        smoke-suite invariant ``max_skew_seen <= s + 1`` holds for both
        trainers by the same argument. (Gating BEFORE the step with a
        ``c+1`` threshold would deadlock at s=0: every process would wait
        for the others to finish a step none has started.)"""
        sharding = NamedSharding(self.local_mesh, P(DATA_AXIS))
        local = {k: jax.device_put(v, sharding) for k, v in batch.items()}
        loss = self.table.step_inplace(self._step, local)
        self.clock += 1
        if self._gate is not None:
            publish_clock(self.gossip, self.clock, False)
            self._gate.wait(self.clock)
        if self.clock % self.sync_every == 0:
            self._sync()
        return float(loss)

    def _sync(self) -> None:
        """base + psum_over_processes(delta) -> every replica identical.
        The all-reduce is the rendezvous: a fast host blocks HERE (inside
        XLA, on the DCN plane) until every process launches the round."""
        delta = self._delta(self.table.params, self._base)
        if self.sync_comm == "float32":
            merged = self.plane.allreduce_sum(delta)
        else:
            send = self._apply(delta, self._residual)  # delta + residual
            merged, sent, gap = self.plane.allreduce_sum_ef(
                send, self.sync_comm)
            # EF over both compression points: what the reduce leg
            # dropped of MY contribution + what the replicate leg
            # dropped of MY chunk of the merge
            self._residual = self._ef(send, sent, gap)
        new_params = self._apply(self._base, merged)
        self.table.params = new_params
        self._base = self._copy(new_params)
        if self.opt_sync == "avg":
            avg_table_opt_state(self.table, self.plane)
        self.sync_rounds += 1
        self._synced_at = self.clock

    def finalize(self) -> PyTree:
        """Merge any tail of local steps not yet synced; afterwards every
        process holds identical parameters. All processes must call this
        together (it may launch one last collective). Idempotent: a
        second finalize at the same clock launches nothing — an UNMATCHED
        extra collective on one process would hang the job."""
        if self.clock != self._synced_at:
            self._sync()
        return self.params


def validate_snapshot_schedule(ckpt_dir, save_at: int, restore_from: int,
                               iters: int, sync_every: int) -> int:
    """Checkpoint/recovery drill plumbing (SURVEY §5.3 on the
    collective-SSP path): snapshots are only meaningful at SYNC
    boundaries (replicas are bitwise-identical right after a merge, so
    every rank can save/restore its own copy and the clock vector
    restarts coherent — an off-boundary snapshot would save N different
    divergent replicas). Returns the resolved save step; refuses loudly
    (SystemExit) on any schedule that would violate the invariant."""
    if ckpt_dir and not save_at and not restore_from:
        # --save-at 0 means "at the end" (the fused path's semantics);
        # here the end must be a sync boundary, so round DOWN — silently
        # writing nothing would strand the restore leg
        save_at = (iters // sync_every) * sync_every
        if save_at == 0:
            raise SystemExit(
                f"--checkpoint-dir with --iters {iters} < "
                f"--sync-every {sync_every}: no sync boundary ever "
                "happens, nothing to snapshot")
    for flag, val in (("--save-at", save_at),
                      ("--restore-from", restore_from)):
        if val and val % sync_every:
            raise SystemExit(
                f"{flag} {val} is not a sync boundary (sync-every "
                f"{sync_every}); CollectiveSSP snapshots must land "
                "right after a merge, where replicas are identical")
    if (save_at or restore_from) and not ckpt_dir:
        raise SystemExit("--save-at/--restore-from need --checkpoint-dir")
    return save_at


def run_ssp_spmd(args, rank: int, nprocs: int, multi: bool,
                 watchdog) -> int:
    """The multihost_example ``--mode bsp|ssp|asp`` runner: LR on
    synthetic data, per-process batch slices, CollectiveSSP training,
    one JSON result line per rank (smoke protocol).

    ``--oracle-hosts K`` (single-process only) instead SIMULATES K hosts
    sequentially — same local-step math on K disjoint submeshes, same
    fixed-clock merge schedule — producing the exact per-host loss
    streams the real K-process run must reproduce: the gate changes
    overlap/timing, never math, so ssp/bsp/asp runs all match this
    oracle bitwise (up to float reduction noise).
    """
    import json

    from minips_tpu.parallel import cluster
    from minips_tpu.models import lr as lr_model

    B, D = args.batch, args.dim
    staleness = staleness_for(args.mode, args.staleness)
    rng = np.random.default_rng(args.seed)
    w_true = rng.normal(size=D)

    def next_global():
        x = rng.normal(size=(B, D)).astype(np.float32)
        y = (x @ w_true > 0).astype(np.float32)
        return x, y

    if args.oracle_hosts:
        if getattr(args, "sync_comm", "float32") != "float32":
            raise SystemExit(
                "--oracle-hosts is the BITWISE float32 oracle; the "
                "compressed wire has its own tolerance test "
                "(tests/test_cssp_ps.py) — run the oracle without "
                "--sync-comm")
        if nprocs > 1:
            # under the launcher every rank would simulate ALL K hosts,
            # print duplicate oracle lines, and skip the watchdog
            # disarm/barrier protocol (spurious peer_failure exit 42)
            raise SystemExit("--oracle-hosts is a single-process "
                             "simulation; run it without the launcher")
        return _run_oracle(args, rng, next_global)

    if B % nprocs:
        raise SystemExit(f"--batch {B} must divide by {nprocs} processes")
    per = B // nprocs
    t0 = time.monotonic()
    trainer = CollectiveSSP(
        lr_model.init(D), lr_model.grad_fn_dense, updater=args.updater,
        lr=args.lr, staleness=staleness, sync_every=args.sync_every,
        bus=getattr(watchdog, "bus", None),
        monitor=getattr(watchdog, "monitor", None),
        opt_sync=getattr(args, "opt_sync", "local"),
        sync_comm=getattr(args, "sync_comm", "float32"))

    ckpt_dir = getattr(args, "checkpoint_dir", None)
    save_at = validate_snapshot_schedule(
        ckpt_dir, getattr(args, "save_at", 0),
        getattr(args, "restore_from", 0), args.iters, args.sync_every)
    restore_from = getattr(args, "restore_from", 0)

    start = 0
    if restore_from:
        path = os.path.join(ckpt_dir,
                            f"cssp_step{restore_from}_r{rank}.npz")
        if not os.path.exists(path):
            # the replica plane deliberately has NO elastic resume (the
            # sharded PS does — ckpt/elastic.py): CSSP snapshots are
            # per-rank because optimizer moments are rank-PRIVATE state
            # under opt_sync='local' (docs/consistency.md), so a new
            # world size would need moments that never existed. Refuse
            # loudly rather than np.load's bare FileNotFoundError.
            raise SystemExit(
                f"no CSSP snapshot for rank {rank} at step "
                f"{restore_from} under {ckpt_dir} — CollectiveSSP "
                "resumes at the world size that saved (per-rank "
                "optimizer moments cannot be resharded); relaunch with "
                "the original process count or start fresh")
        state = np.load(path)
        # the exists-check above only catches GROWS; a shrink finds its
        # file and would silently resume with a smaller world (dropped
        # ranks' private moments, different batch slicing) — the saved
        # world size is the authority for both directions
        saved_n = int(state["nprocs"]) if "nprocs" in state.files else None
        if saved_n is not None and saved_n != nprocs:
            raise SystemExit(
                f"CSSP snapshot at step {restore_from} was saved by "
                f"{saved_n} processes, this relaunch has {nprocs} — "
                "CollectiveSSP resumes at the world size that saved "
                "(per-rank optimizer moments cannot be resharded)")
        trainer.table.params = jax.device_put(
            jnp.asarray(state["params"]), trainer.table.params.sharding)
        opt_leaves, treedef = jax.tree.flatten(trainer.table.opt_state)
        n_saved = len([k for k in state.files if k.startswith("opt")])
        if n_saved != len(opt_leaves):
            raise SystemExit(
                f"checkpoint carries {n_saved} optimizer leaves but "
                f"this run's --updater produces {len(opt_leaves)} — "
                "resume with the updater the snapshot was saved under")
        for j, cur in enumerate(opt_leaves):
            if tuple(state[f"opt{j}"].shape) != tuple(cur.shape):
                raise SystemExit(
                    f"checkpoint optimizer leaf {j} has shape "
                    f"{state[f'opt{j}'].shape}, this run expects "
                    f"{cur.shape} — different updater or model shape")
        trainer.table.opt_state = jax.tree.unflatten(treedef, [
            jax.device_put(jnp.asarray(state[f"opt{j}"]), cur.sharding)
            for j, cur in enumerate(opt_leaves)])
        trainer._base = trainer._copy(trainer.table.params)
        if trainer._residual is not None:
            # the error-feedback residual is part of the trajectory: a
            # compressed-wire resume with a zeroed residual would
            # silently diverge from the uninterrupted run
            if "residual" not in state:
                raise SystemExit(
                    "checkpoint has no error-feedback residual but this "
                    "run uses --sync-comm compression — it was written "
                    "by a float32-wire run; resume with the same "
                    "--sync-comm it was saved under")
            trainer._residual = jax.device_put(
                jnp.asarray(state["residual"]),
                trainer.table.params.sharding)
        elif "residual" in state:
            raise SystemExit(
                "checkpoint carries an error-feedback residual (written "
                "under --sync-comm compression) but this run uses the "
                "float32 wire — resume with the same --sync-comm")
        # the CLOCK VECTOR restarts where the snapshot was taken: the
        # next step publishes restore_from+1, so gossiped clocks and the
        # sync schedule continue exactly as the uninterrupted run's
        trainer.clock = trainer._synced_at = int(state["clock"])
        trainer.sync_rounds = int(state["sync_rounds"])
        start = restore_from
        for _ in range(start):      # shared-stream fast-forward
            next_global()

    losses = []
    jitter_rng = np.random.default_rng(1000 + rank)

    def run_steps():
        for i in range(start, args.iters):
            if getattr(args, "kill_at", 0) and rank == args.kill_rank \
                    and i == args.kill_at:
                os._exit(137)
            x, y = next_global()
            if args.slow_ms and rank == args.slow_rank:
                time.sleep(args.slow_ms / 1000.0)
            if args.jitter_ms and jitter_rng.random() < args.jitter_prob:
                time.sleep(args.jitter_ms / 1000.0)
            losses.append(trainer.step(
                {"x": x[rank * per:(rank + 1) * per],
                 "y": y[rank * per:(rank + 1) * per]}))
            if save_at and i + 1 == save_at:
                # the merge for this boundary already ran inside step(),
                # so PARAMS are identical on every replica — but with
                # opt_sync='local' the optimizer moments are rank-PRIVATE
                # state (exactly the drift docs/consistency.md documents),
                # so each rank snapshots its own copy, like the
                # reference's per-server-shard Dump. Atomic tmp+rename: a
                # crash mid-write must not leave a truncated snapshot
                # that parses.
                os.makedirs(ckpt_dir, exist_ok=True)
                opt_leaves = jax.tree.leaves(trainer.table.opt_state)
                path = os.path.join(ckpt_dir,
                                    f"cssp_step{save_at}_r{rank}.npz")
                extra = ({"residual": np.asarray(trainer._residual)}
                         if trainer._residual is not None else {})
                np.savez(path + ".tmp.npz",
                         params=np.asarray(trainer.table.params),
                         clock=trainer.clock,
                         sync_rounds=trainer.sync_rounds,
                         nprocs=nprocs,
                         **extra,
                         **{f"opt{j}": np.asarray(leaf)
                            for j, leaf in enumerate(opt_leaves)})
                os.replace(path + ".tmp.npz", path)

    # a dead peer surfaces as an INSTANT Gloo transport error in the
    # sync collective, beating the heartbeat watchdog — absorbing() holds
    # for the monitor to confirm+name the corpse (prints peer_failure,
    # exits 42) or re-raises if nobody is dead. finalize() and the
    # fingerprint allgather are collectives too, so they stay inside.
    with watchdog.absorbing():
        run_steps()
        trainer.finalize()
        fp = float(cluster.host_copy(trainer.table.params).sum())
    hlo = trainer.sync_hlo()
    comm = getattr(args, "sync_comm", "float32")
    # wire proof per format: f32 sync is ONE all-reduce; compressed syncs
    # are all-to-all (reduce leg) + all-gather (replicate leg) carrying
    # the compressed dtype (HLO spells int8 as s8)
    wire_ok = ("all-reduce" in hlo if comm == "float32" else
               ("all-to-all" in hlo and "all-gather" in hlo
                and ("s8" if comm == "int8" else "bf16") in hlo))

    watchdog.disarm()
    cluster.barrier("cssp_done")
    print(json.dumps({
        "rank": rank, "event": "done", "mode": args.mode,
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi, "process_count": nprocs,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "staleness": (None if staleness == float("inf")
                      else int(staleness)),
        "sync_every": args.sync_every,
        "opt_sync": getattr(args, "opt_sync", "local"),
        "sync_comm": getattr(args, "sync_comm", "float32"),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "gate_waits": trainer.gate_waits,
        "max_skew_seen": trainer.max_skew_seen,
        "sync_rounds": trainer.sync_rounds,
        "sync_hlo_has_all_reduce": "all-reduce" in hlo,
        "sync_hlo_wire_ok": wire_ok,
        "sync_plane_devices": len(trainer.sync_mesh.devices.ravel()),
        "resumed_from": start,
    }), flush=True)
    watchdog.close()
    return 0


def _run_oracle(args, rng, next_global) -> int:
    """Sequential K-virtual-host simulation (single process): DenseTables
    on disjoint submeshes run the identical local-step program, and the
    merge applies the delta SUM at the same fixed clocks — the bitwise
    reference for the real K-process run."""
    import json

    from minips_tpu.models import lr as lr_model

    K = args.oracle_hosts
    devs = jax.devices()
    if len(devs) % K:
        raise SystemExit(f"{len(devs)} devices do not split into "
                         f"{K} oracle hosts")
    L = len(devs) // K
    B = args.batch
    if B % K:
        raise SystemExit(f"--batch {B} must divide by {K} oracle hosts")
    per = B // K
    tables, steps, bases = [], [], []
    copy = jax.jit(jnp.copy)
    for h in range(K):
        mesh = Mesh(np.asarray(devs[h * L:(h + 1) * L]), (DATA_AXIS,))
        t = DenseTable(lr_model.init(args.dim), mesh, name=f"h{h}",
                       updater=args.updater, lr=args.lr)
        tables.append(t)
        steps.append(t.make_step(lr_model.grad_fn_dense))
        bases.append(copy(t.params))
    losses = [[] for _ in range(K)]
    for i in range(args.iters):
        x, y = next_global()
        for h in range(K):
            sh = NamedSharding(tables[h].mesh, P(DATA_AXIS))
            batch = {"x": jax.device_put(x[h * per:(h + 1) * per], sh),
                     "y": jax.device_put(y[h * per:(h + 1) * per], sh)}
            losses[h].append(float(
                tables[h].step_inplace(steps[h], batch)))
        if (i + 1) % args.sync_every == 0 or i + 1 == args.iters:
            # merged = base + sum of per-host deltas, like the collective
            deltas = [np.asarray(tables[h].params)
                      - np.asarray(bases[h]) for h in range(K)]
            total = np.sum(deltas, axis=0)
            for h in range(K):
                merged = jnp.asarray(np.asarray(bases[h]) + total)
                tables[h].params = jax.device_put(
                    merged, tables[h].params.sharding)
                bases[h] = copy(tables[h].params)
            if getattr(args, "opt_sync", "local") == "avg":
                # the moment reconciliation, simulated: average the
                # hosts' float params-length opt leaves in f32 (exactly
                # avg_table_opt_state's rule) and install everywhere
                padded = tables[0].padded
                flat = [jax.tree.leaves(t.opt_state) for t in tables]
                for j in range(len(flat[0])):
                    leaf = flat[0][j]
                    if not is_avg_leaf(leaf, padded):
                        continue
                    mean = np.mean(
                        [np.asarray(f[j], np.float32) for f in flat],
                        axis=0).astype(leaf.dtype)
                    for h in range(K):
                        lv, treedef = jax.tree.flatten(tables[h].opt_state)
                        lv[j] = jax.device_put(jnp.asarray(mean),
                                               lv[j].sharding)
                        tables[h].opt_state = jax.tree.unflatten(treedef,
                                                                 lv)
    fps = [float(np.asarray(t.params).sum()) for t in tables]
    print(json.dumps({
        "rank": 0, "event": "done", "mode": args.mode, "oracle": True,
        "oracle_hosts": K, "sync_every": args.sync_every,
        "opt_sync": getattr(args, "opt_sync", "local"),
        "losses_per_host": [[round(x, 8) for x in ls] for ls in losses],
        "param_fingerprints": fps,
    }), flush=True)
    return 0
