"""Multi-host SPMD data plane — the real-pod story, smoke-sized.

The reference actually runs N processes on N nodes glued by the mailbox
(SURVEY.md §1 L7, §3.1); the rebuild's equivalent for the SPMD data plane
is ``jax.distributed.initialize`` + ONE global mesh spanning every
process's devices (SURVEY.md §2.3 "DCN"): the same fused
pull→grad→push→update step (tables/dense.py) compiles unchanged, XLA
routes its collectives across the process boundary (ICI intra-host, DCN
inter-host; Gloo on the CPU loopback smoke), and batches are fed
per-process via ``make_array_from_process_local_data`` — each host
contributes the rows it loaded.

Run under the launcher (which exports MINIPS_COORDINATOR + ranks):

    python -m minips_tpu.launch --n 2 --base-port 59XX -- \
        python -m minips_tpu.apps.multihost_example --iters 30

Each rank prints ONE JSON line (smoke protocol): losses, process/device
counts, a post-training parameter fingerprint (process-allgathered, so
ranks can be compared for SPMD agreement), and the result of a
globally-sharded orbax checkpoint save→restore drill in which every
process writes/reads only its addressable shards (SURVEY.md §5.4).

Single-process (no launcher) the exact same code runs on the local
devices — that run is the loss-parity oracle for the 2-process smoke.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from contextlib import contextmanager


class _Watchdog:
    """Fast failure detection for multi-host SPMD jobs (SURVEY §5.3): a
    peer death leaves the survivors BLOCKED inside a collective — the
    host thread cannot poll anything — so detection rides the
    HeartbeatMonitor's own thread via its ``on_failure`` callback (2s
    timeout over the launcher's control bus): print the structured
    peer_failure event and exit 42, the same protocol as the sharded-PS
    apps. Recovery is the all-or-nothing relaunch + checkpoint restore
    the reference uses (SURVEY §3.5, §7.4.5); jax's own coordination
    service is the ~100s backstop for deaths in the disarm→barrier
    window."""

    def __init__(self, rank: int):
        from minips_tpu.comm.heartbeat import HeartbeatMonitor
        from minips_tpu.launch import init_from_env

        _, n, self.bus = init_from_env()
        self.monitor = None
        self._armed = True
        if self.bus is None:
            return

        def on_dead(peer: int) -> None:
            if self._armed:
                print(json.dumps({"rank": rank, "event": "peer_failure",
                                  "dead": [peer]}), flush=True)
                os._exit(42)

        self.monitor = HeartbeatMonitor(
            self.bus, peer_ids=list(range(n)), interval=0.2,
            timeout=2.0, on_failure=on_dead).start()

    def disarm(self) -> None:
        """Call once training is complete, BEFORE the final barrier: a
        peer closing its bus after finishing must not read as a death."""
        self._armed = False

    def absorb_collective_failure(self, exc: BaseException) -> None:
        """A dead peer does NOT always leave survivors blocked: on the
        Gloo loopback transport the broken TCP pair surfaces INSTANTLY
        as a JaxRuntimeError in whoever touches the collective's output
        — faster than the heartbeat timeout, so the structured
        peer_failure protocol would lose the race to a raw traceback.
        Hold the rank here long enough for the monitor to confirm and
        NAME the corpse (its on_failure callback prints peer_failure
        and exits 42); if no peer is confirmed dead the error was not a
        death — re-raise it."""
        if self.monitor is not None and self._armed:
            deadline = time.monotonic() + 3 * self.monitor.timeout + 2.0
            while time.monotonic() < deadline:
                self.monitor.check()  # on_failure → print + exit 42
                time.sleep(0.1)
        raise exc

    @contextmanager
    def absorbing(self):
        """Run a training loop under the instant-Gloo-error →
        peer_failure translation (one spelling for every runner — see
        absorb_collective_failure)."""
        import jax

        try:
            yield
        except jax.errors.JaxRuntimeError as e:
            self.absorb_collective_failure(e)

    def close(self) -> None:
        self.disarm()
        if self.monitor is not None:
            self.monitor.stop()
        if self.bus is not None:
            self.bus.close()


def _finish(rc: int) -> int:
    """Clean-exit join point: coordinated jax.distributed disconnect
    (cluster.shutdown) AFTER the result line is printed — without it the
    coordinator rank's exit races the followers' error-polling threads
    and a finished follower can be fatally terminated into rc!=0."""
    from minips_tpu.parallel import cluster

    cluster.shutdown()
    return rc


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--mode", default="fused",
                    choices=["fused", "bsp", "ssp", "asp"],
                    help="fused: the one-global-mesh BSP data plane "
                         "(implicit-barrier collectives, the default); "
                         "bsp/ssp/asp: CollectiveSSP (train/ssp_spmd.py) "
                         "— per-process local fused steps under the "
                         "host-side staleness gate, cross-process sync "
                         "as an XLA collective (SURVEY §7.4.1)")
    ap.add_argument("--staleness", type=int, default=4,
                    help="SSP bound s for --mode ssp (bsp pins 0, "
                         "asp pins inf)")
    ap.add_argument("--sync-every", type=int, default=1,
                    help="collective merge every k local steps "
                         "(CollectiveSSP modes)")
    ap.add_argument("--sync-comm", default="float32",
                    choices=["float32", "bfloat16", "int8"],
                    help="CollectiveSSP modes: wire format of the delta "
                         "merge — bfloat16/int8 compress the all-reduce "
                         "with an error-feedback residual (on a pod this "
                         "is DCN bandwidth); lr/lm models only")
    ap.add_argument("--opt-sync", default="local",
                    choices=["local", "avg"],
                    help="CollectiveSSP modes, stateful updaters: "
                         "'local' keeps each process's moments (drift "
                         "documented in docs/consistency.md); 'avg' "
                         "psum-averages float moments alongside the "
                         "param deltas at every merge")
    ap.add_argument("--slow-rank", type=int, default=-1)
    ap.add_argument("--slow-ms", type=int, default=0,
                    help="straggler injection: sleep this long before "
                         "each of --slow-rank's local steps")
    ap.add_argument("--jitter-ms", type=float, default=0.0,
                    help="TRANSIENT stall injection on every rank "
                         "(rank-seeded): sleep this long before a step "
                         "with --jitter-prob — the regime where SSP's "
                         "slack window beats BSP's stall union "
                         "(bench_ssp --collective)")
    ap.add_argument("--jitter-prob", type=float, default=0.0)
    ap.add_argument("--oracle-hosts", type=int, default=0,
                    help="single-process: SIMULATE this many hosts "
                         "sequentially (disjoint submeshes, same merge "
                         "schedule) — the bitwise loss oracle for the "
                         "real N-process CollectiveSSP run")
    ap.add_argument("--model", default="lr", choices=["lr", "wd", "lm"],
                    help="lr: DenseTable LR (checkpoint drill supported); "
                         "wd: the flagship DeepFM fused step — hashed "
                         "SparseTables + deep tower over the GLOBAL mesh, "
                         "collectives crossing the process boundary; "
                         "lm: ring-attention SEQUENCE parallelism over "
                         "the global mesh — each host feeds its sequence "
                         "slice, the K/V ring ppermutes cross the "
                         "process boundary (long-context x multi-host)")
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--sp-attn", default="reference",
                    choices=["reference", "flash", "a2a", "a2a_flash"],
                    help="lm model: the sequence-parallel strategy over "
                         "the GLOBAL mesh — ring (reference/flash, K/V "
                         "ppermute hops cross the process boundary) or "
                         "all-to-all (a2a/a2a_flash: the head/sequence "
                         "exchange crosses it instead; needs heads "
                         "divisible by the global device count — the "
                         "model auto-widens to that head count)")
    ap.add_argument("--num-slots", type=int, default=1 << 14)
    ap.add_argument("--batch", type=int, default=64,
                    help="GLOBAL batch size (split across processes)")
    ap.add_argument("--dim", type=int, default=None,
                    help="lr: feature dim (default 16); wd: embedding "
                         "dim (default 8)")
    ap.add_argument("--lr", type=float, default=None,
                    help="default: 0.3 (lr model) / 0.05 (wd)")
    ap.add_argument("--updater", default="adagrad",
                    choices=["sgd", "adagrad", "adam", "adam_bf16",
                             "adam8"])  # dense-table paths (fused +
    # CollectiveSSP) take the low-precision states too; the sharded-PS
    # apps keep their numpy-twin trio and refuse these loudly
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--checkpoint-dir", default=None,
                    help="shared dir for the globally-sharded orbax "
                         "save→restore drill (skipped when absent)")
    ap.add_argument("--save-at", type=int, default=0,
                    help="iteration AFTER which to save (0 = at the end)")
    ap.add_argument("--restore-from", type=int, default=0,
                    help="restore the step-N checkpoint before training "
                         "(the relaunch leg of the recovery drill)")
    ap.add_argument("--kill-at", type=int, default=0)
    ap.add_argument("--kill-rank", type=int, default=-1)
    args = ap.parse_args(argv)
    if args.dim is None:  # per-model default: lr feature dim / wd emb dim
        args.dim = 16 if args.model == "lr" else 8
    if args.lr is None:
        args.lr = 0.3 if args.model == "lr" else 0.05
    if args.save_at > args.iters:
        ap.error(f"--save-at {args.save_at} exceeds --iters {args.iters}: "
                 "the restore drill would read a checkpoint never saved")
    if args.restore_from >= args.iters:
        ap.error(f"--restore-from {args.restore_from} must be < --iters "
                 f"{args.iters} (nothing left to train)")
    if args.opt_sync != "local" and args.mode == "fused":
        ap.error("--opt-sync is a CollectiveSSP-mode flag; the fused "
                 "global-mesh path has ONE optimizer state (nothing to "
                 "reconcile)")
    if args.sync_comm != "float32" and args.mode == "fused":
        ap.error("--sync-comm compresses the CollectiveSSP delta merge; "
                 "the fused path's wire format is make_step(comm=...)")

    # CPU smoke path: fake local devices BEFORE any backend-touching call
    # (same bootstrap as tests/conftest.py)
    local_devs = int(os.environ.get("MINIPS_MH_LOCAL_DEVICES", "0"))
    if local_devs:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={local_devs}")
    import jax

    if os.environ.get("MINIPS_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    from minips_tpu.parallel import cluster

    multi = cluster.initialize()
    rank = jax.process_index()
    nprocs = jax.process_count()
    watchdog = _Watchdog(rank)

    import numpy as np

    from minips_tpu.models import lr as lr_model
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.dense import DenseTable

    mesh = make_mesh(len(jax.devices()))  # ONE mesh over every process
    B, D = args.batch, args.dim
    if B % nprocs:
        raise SystemExit(f"--batch {B} must divide by {nprocs} processes")
    per = B // nprocs
    # every rank generates the identical GLOBAL batch stream and feeds its
    # own row slice — so an n-process run and the single-process oracle
    # train on the same data and must produce the same losses (the smoke's
    # parity assertion)
    rng = np.random.default_rng(args.seed)

    if args.mode != "fused":
        # the staleness axis covers the flagship workloads, not just LR:
        # lr = dense CollectiveSSP (+ the bitwise oracle), wd = row-sparse
        # CollectiveSSPPS over the DeepFM tables, lm = dense CollectiveSSP
        # over the transformer (per-process DP islands)
        if args.model == "lr":
            from minips_tpu.train.ssp_spmd import run_ssp_spmd

            return _finish(run_ssp_spmd(args, rank, nprocs, multi, watchdog))
        if args.oracle_hosts:
            raise SystemExit("--oracle-hosts is the lr model's bitwise "
                             "oracle; wd/lm assert replica agreement "
                             "via fingerprints instead")
        if args.checkpoint_dir or args.save_at or args.restore_from \
                or args.kill_at:
            # refuse-loudly convention: the checkpoint/kill recovery
            # drill lives on the lr CollectiveSSP path (and the fused
            # path); silently ignoring the flags here would complete a
            # run with no snapshot and crash the restore leg later
            raise SystemExit("--checkpoint-dir/--save-at/--restore-from/"
                             "--kill-at are not wired for the wd/lm "
                             "CollectiveSSP paths; use --model lr for "
                             "the collective-SSP recovery drill")
        if args.model == "wd":
            return _finish(_run_wd_cssp(args, rank, nprocs, multi, watchdog))
        return _finish(_run_lm_cssp(args, rank, nprocs, multi, watchdog))
    if args.model == "wd":
        return _finish(_run_wd(args, mesh, rank, nprocs, per, multi,
                               rng, watchdog))
    if args.model == "lm":
        return _finish(_run_lm_sp(args, mesh, rank, nprocs, multi,
                               watchdog))

    dt = DenseTable(lr_model.init(args.dim), mesh, updater=args.updater,
                    lr=args.lr)
    step = dt.make_step(lr_model.grad_fn_dense)
    w_true = rng.normal(size=D)

    def next_global():
        x = rng.normal(size=(B, D)).astype(np.float32)
        y = (x @ w_true > 0).astype(np.float32)
        return x, y

    ckpt_fp = None
    save_at = args.save_at or args.iters
    ckptr = None
    if args.checkpoint_dir:
        import orbax.checkpoint as ocp

        # synchronous Checkpointer: its primary-host dir creation +
        # barrier protocol is what coordinates a multi-process save (the
        # async StandardCheckpointer races per-process signaling threads
        # on the shared tmp dir in this orbax version)
        ckptr = ocp.Checkpointer(ocp.StandardCheckpointHandler())
        ocp_args = ocp.args

    start = 0
    if args.restore_from:  # relaunch leg of the recovery drill
        if ckptr is None:
            raise SystemExit("--restore-from needs --checkpoint-dir")
        restored = ckptr.restore(
            os.path.join(args.checkpoint_dir, f"step{args.restore_from}"),
            args=ocp_args.StandardRestore(dt.global_arrays()))
        dt.params = restored["params"]
        dt.opt_state = restored["opt_state"]
        start = args.restore_from
        # replay the shared batch stream up to the restore point so the
        # resumed run continues the SAME data sequence (TrainLoop's
        # fast-forward semantics, here at the multihost smoke's scale)
        for _ in range(start):
            next_global()

    losses = []
    t0 = time.monotonic()
    with watchdog.absorbing():
        for i in range(start, args.iters):
            if args.kill_at and rank == args.kill_rank \
                    and i == args.kill_at:
                os._exit(137)
            x, y = next_global()
            batch = cluster.global_batch(
                mesh, {"x": x[rank * per:(rank + 1) * per],
                       "y": y[rank * per:(rank + 1) * per]})
            losses.append(float(dt.step_inplace(step, batch)))
            if ckptr is not None and i + 1 == save_at:
                # coordinated multi-host save: every process writes ONLY
                # its addressable shards of the live sharded arrays
                # (TensorStore under orbax) — no host gather, no full
                # copy anywhere
                ckptr.save(
                    os.path.join(args.checkpoint_dir, f"step{i + 1}"),
                    args=ocp_args.StandardSave(dt.global_arrays()),
                    force=True)
                ckpt_fp = float(cluster.host_copy(dt.params).sum())

    # fingerprint + checkpoint roundtrip are collectives too — same
    # death translation as the training loop
    with watchdog.absorbing():
        # SPMD agreement fingerprint (allgathered => comparable across
        # ranks)
        fp = float(cluster.host_copy(dt.params).sum())

        ckpt_ok = None
        if ckptr is not None and ckpt_fp is not None:
            # restore into a FRESH table (same template/shardings) and
            # check it reproduces the state that was saved — the
            # recovery path of SURVEY.md §3.5 with globally-sharded state
            dt2 = DenseTable(lr_model.init(args.dim), mesh,
                             updater=args.updater, lr=args.lr)
            restored = ckptr.restore(
                os.path.join(args.checkpoint_dir, f"step{save_at}"),
                args=ocp_args.StandardRestore(dt2.global_arrays()))
            dt2.params = restored["params"]
            dt2.opt_state = restored["opt_state"]
            ckpt_ok = bool(abs(float(cluster.host_copy(dt2.params).sum())
                               - ckpt_fp) < 1e-5)
        if ckptr is not None:
            ckptr.close()

    watchdog.disarm()  # peers closing their buses after finishing is fine
    cluster.barrier("multihost_done")  # reference Engine::Barrier
    print(json.dumps({
        "rank": rank, "event": "done",
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi,
        "process_count": nprocs,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "ckpt_roundtrip_ok": ckpt_ok,
        "resumed_from": start,
    }), flush=True)
    watchdog.close()
    return _finish(0)


def _run_wd_cssp(args, rank: int, nprocs: int, multi: bool,
                 watchdog) -> int:
    """multihost_example ``--model wd --mode bsp|ssp|asp``: the flagship
    DeepFM (hashed wide + field embeddings + deep tower) under the
    collective-gated consistency axis. Emits the smoke-protocol JSON
    line with the row-sparse traffic observables."""
    import jax
    import numpy as np

    from minips_tpu.apps.wide_deep_example import build
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.data import synthetic
    from minips_tpu.parallel import cluster
    from minips_tpu.train.cssp_ps import CollectiveSSPPS
    from minips_tpu.train.ssp_spmd import staleness_for

    staleness = staleness_for(args.mode, args.staleness)
    if getattr(args, "sync_comm", "float32") != "float32":
        raise SystemExit(
            "--sync-comm compression is not wired for the wd row-sparse "
            "merge (the error-feedback residual is defined over a "
            "per-round-changing row union — per-slot EF bookkeeping is "
            "future work); use --model lr or lm")
    if args.batch % nprocs:
        raise SystemExit(f"--batch {args.batch} must divide by {nprocs} "
                         "processes")
    per = args.batch // nprocs

    def build_fn(mesh):
        cfg = Config(
            table=TableConfig(name="ctr", kind="sparse",
                              updater=args.updater, lr=args.lr,
                              dim=args.dim, num_slots=args.num_slots),
            train=TrainConfig(batch_size=per, num_iters=args.iters),
        )
        ps, (wide_t, emb_t, deep_t) = build(cfg, use_fm=True, mesh=mesh,
                                            seed=args.seed)
        return ps, {"wide": wide_t, "emb": emb_t, "deep": deep_t}

    t0 = time.monotonic()
    trainer = CollectiveSSPPS(
        build_fn, staleness=staleness, sync_every=args.sync_every,
        bus=getattr(watchdog, "bus", None),
        monitor=getattr(watchdog, "monitor", None),
        opt_sync=getattr(args, "opt_sync", "local"))
    # ONE dataset (one ground truth) on every rank; batches sampled with
    # a shared stream, each rank training on its row slice
    data = synthetic.criteo_like(8192, seed=args.seed)
    rng = np.random.default_rng(args.seed)
    jitter_rng = np.random.default_rng(1000 + rank)
    losses = []
    with watchdog.absorbing():  # dead peer ⇒ instant Gloo error in sync
        for i in range(args.iters):
            sel = rng.integers(0, data["y"].shape[0], size=args.batch)
            if args.slow_ms and rank == args.slow_rank:
                time.sleep(args.slow_ms / 1000.0)
            if args.jitter_ms and jitter_rng.random() < args.jitter_prob:
                time.sleep(args.jitter_ms / 1000.0)
            lo, hi = rank * per, (rank + 1) * per
            losses.append(trainer.step(
                {k: v[sel][lo:hi] for k, v in data.items()}))
        # finalize + fingerprint are collectives too — keep them under
        # the same death translation
        trainer.finalize()
        fp = trainer.fingerprint()
    hlo = trainer.sync_hlo() if trainer._last_emb_len else ""
    watchdog.disarm()
    cluster.barrier("cssp_wd_done")
    print(json.dumps({
        "rank": rank, "event": "done", "model": "wd", "mode": args.mode,
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi, "process_count": nprocs,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "staleness": (None if staleness == float("inf")
                      else int(staleness)),
        "sync_every": args.sync_every,
        "opt_sync": getattr(args, "opt_sync", "local"),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "gate_waits": trainer.gate_waits,
        "max_skew_seen": trainer.max_skew_seen,
        "sync_rounds": trainer.sync_rounds,
        "sync_rows_max": trainer.sync_rows_max,
        "num_slots": int(args.num_slots),
        "union_wire_bytes": trainer.union_wire_bytes,
        "sync_hlo_has_all_reduce": "all-reduce" in hlo,
        "sync_plane_devices": len(trainer.sync_mesh.devices.ravel()),
    }), flush=True)
    watchdog.close()
    return 0


def _run_lm_cssp(args, rank: int, nprocs: int, multi: bool,
                 watchdog) -> int:
    """multihost_example ``--model lm --mode bsp|ssp|asp``: the LM family
    on the collective consistency axis. Each process is a data-parallel
    ISLAND (its local mesh shards batch rows); the cross-process sync is
    CollectiveSSP's dense delta psum over the transformer's raveled
    parameters — sequence parallelism stays intra-island (ring/a2a need
    one mesh spanning the sequence; under the staleness axis the
    processes deliberately do NOT share a mesh, that is the point)."""
    import jax
    import numpy as np

    from minips_tpu.models import transformer as tfm
    from minips_tpu.parallel import cluster
    from minips_tpu.train.ssp_spmd import CollectiveSSP, staleness_for

    staleness = staleness_for(args.mode, args.staleness)
    if args.batch % nprocs:
        raise SystemExit(f"--batch {args.batch} must divide by {nprocs} "
                         "processes")
    per = args.batch // nprocs
    T = args.seq_len
    model = dict(vocab=64, dim=32, heads=2, depth=2, max_len=T)
    template = tfm.init(jax.random.PRNGKey(args.seed), **model)

    def grad(p, b):
        return tfm.grad_fn(p, b, heads=model["heads"])

    t0 = time.monotonic()
    trainer = CollectiveSSP(
        template, grad, updater=args.updater, lr=args.lr,
        staleness=staleness, sync_every=args.sync_every,
        bus=getattr(watchdog, "bus", None),
        monitor=getattr(watchdog, "monitor", None), name="lm_cssp",
        opt_sync=getattr(args, "opt_sync", "local"),
        sync_comm=getattr(args, "sync_comm", "float32"))
    rng = np.random.default_rng(args.seed)
    jitter_rng = np.random.default_rng(1000 + rank)
    losses = []
    with watchdog.absorbing():  # dead peer ⇒ instant Gloo error in sync
        for i in range(args.iters):
            toks = rng.integers(0, model["vocab"],
                                size=(args.batch, T + 1)).astype(np.int32)
            if args.slow_ms and rank == args.slow_rank:
                time.sleep(args.slow_ms / 1000.0)
            if args.jitter_ms and jitter_rng.random() < args.jitter_prob:
                time.sleep(args.jitter_ms / 1000.0)
            losses.append(trainer.step(
                {"tokens": toks[rank * per:(rank + 1) * per]}))
        # finalize + fingerprint are collectives too — keep them under
        # the same death translation
        trainer.finalize()
        fp = float(cluster.host_copy(trainer.table.params).sum())
    hlo = trainer.sync_hlo()
    watchdog.disarm()
    cluster.barrier("cssp_lm_done")
    print(json.dumps({
        "rank": rank, "event": "done", "model": "lm", "mode": args.mode,
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi, "process_count": nprocs,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "staleness": (None if staleness == float("inf")
                      else int(staleness)),
        "sync_every": args.sync_every,
        "opt_sync": getattr(args, "opt_sync", "local"),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "gate_waits": trainer.gate_waits,
        "max_skew_seen": trainer.max_skew_seen,
        "sync_rounds": trainer.sync_rounds,
        "sync_hlo_has_all_reduce": "all-reduce" in hlo,
        "sync_plane_devices": len(trainer.sync_mesh.devices.ravel()),
    }), flush=True)
    watchdog.close()
    return 0


def _run_lm_sp(args, mesh, rank, nprocs, multi, watchdog):
    """Long-context x multi-host: the transformer LM with ring-attention
    SEQUENCE parallelism over the global multi-process mesh. The sequence
    axis is sharded across every device of every process, each host feeds
    only its own sequence slice, and the ring's K/V ppermute hops cross
    the process boundary — the 'ring attention ... scales to multi-host'
    requirement made literal (SURVEY brief; parallel/ring_attention.py).
    Deterministic data (same stream everywhere) so ranks must agree and a
    1-process run with the same global devices is an exact oracle."""
    import time

    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from minips_tpu.parallel import cluster
    from minips_tpu.models import transformer as tfm
    from minips_tpu.parallel.mesh import DATA_AXIS
    from minips_tpu.tables.dense import DenseTable

    t0 = time.monotonic()
    n_shards = len(jax.devices())
    T = args.seq_len
    if T % n_shards:
        raise SystemExit(f"--seq-len {T} must divide by the {n_shards}-"
                         "device global mesh")
    heads = 2
    if args.sp_attn in ("a2a", "a2a_flash"):
        # all-to-all shards HEAD groups over the global mesh: widen to
        # one head per device
        heads = n_shards
    # dim must divide by heads AND keep head_dim >= 4; a plain
    # max(32, 4*heads) breaks divisibility for device counts that don't
    # divide 32 (e.g. a 2x3 mesh -> heads 6)
    model = dict(vocab=64, dim=heads * max(4, -(-32 // heads)),
                 heads=heads, depth=2, max_len=T)
    params = tfm.init(jax.random.PRNGKey(args.seed), **model)
    dt = DenseTable(params, mesh, updater=args.updater, lr=args.lr,
                    name="lm_sp")
    T_local = T // n_shards
    sp_grad, sp_spec = tfm.sp_train_wiring(model["heads"], T_local,
                                           attn_impl=args.sp_attn)
    step = dt.make_step(sp_grad, batch_spec=sp_spec)
    seq_spec = P(None, DATA_AXIS)
    B = args.batch
    rng = np.random.default_rng(args.seed)
    # my PROCESS's sequence span (devices within split it further)
    dev_per_proc = n_shards // nprocs
    lo = rank * dev_per_proc * T_local
    hi = lo + dev_per_proc * T_local
    losses = []
    with watchdog.absorbing():
        for i in range(args.iters):
            toks = rng.integers(0, model["vocab"], size=(B, T + 1))
            batch = cluster.global_batch(
                mesh,
                {"inp": toks[:, :-1][:, lo:hi].astype(np.int32),
                 "tgt": toks[:, 1:][:, lo:hi].astype(np.int32)},
                spec=seq_spec)
            losses.append(float(dt.step_inplace(step, batch)))

    with watchdog.absorbing():  # the fingerprint allgather too
        fp = float(cluster.host_copy(dt.params).sum())
    watchdog.disarm()
    cluster.barrier("multihost_lm_done")
    print(json.dumps({
        "rank": rank, "event": "done", "model": "lm",
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi,
        "process_count": nprocs,
        "global_devices": n_shards,
        "local_devices": len(jax.local_devices()),
        "seq_len": T, "seq_local": hi - lo,
        "sp_attn": args.sp_attn, "heads": heads,
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "ckpt_roundtrip_ok": None,
    }), flush=True)
    watchdog.close()
    return 0


def _run_wd(args, mesh, rank, nprocs, per, multi, rng, watchdog):
    """Flagship DeepFM over the global multi-process mesh: hashed
    SparseTables (wide + field embeddings) and the dense deep tower,
    one fused PSTrainStep whose gathers/scatters and grad collectives
    cross the process boundary — the sparse-embedding-PS-on-a-pod story
    (BASELINE.json config 4) on real processes. Traffic stays batch-sized
    by the same GSPMD shardings tests/test_sharded_traffic.py pins."""
    import time

    import jax
    import numpy as np

    from minips_tpu.apps.wide_deep_example import build
    from minips_tpu.parallel import cluster
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.data import synthetic

    t0 = time.monotonic()
    cfg = Config(
        table=TableConfig(name="ctr", kind="sparse", updater=args.updater,
                          lr=args.lr, dim=args.dim,
                          num_slots=args.num_slots),
        train=TrainConfig(batch_size=args.batch, num_iters=args.iters),
    )
    ps, (wide_t, emb_t, deep_t) = build(cfg, use_fm=True, mesh=mesh,
                                        seed=args.seed)
    # ONE dataset (one ground truth), identical on every rank; batches are
    # sampled from it with a shared stream and each rank feeds its slice
    data = synthetic.criteo_like(8192, seed=args.seed)
    losses = []
    with watchdog.absorbing():
        for i in range(args.iters):
            sel = rng.integers(0, data["y"].shape[0], size=args.batch)
            lo, hi = rank * per, (rank + 1) * per
            batch = cluster.global_batch(
                mesh, {k: v[sel][lo:hi] for k, v in data.items()})
            losses.append(float(ps(batch)))

    with watchdog.absorbing():  # the fingerprint allgathers too
        fp = float(cluster.host_copy(emb_t.emb).sum()) \
            + float(cluster.host_copy(deep_t.params).sum())
    watchdog.disarm()
    cluster.barrier("multihost_wd_done")
    import json
    print(json.dumps({
        "rank": rank, "event": "done", "model": "wd",
        "wall_s": round(time.monotonic() - t0, 4),
        "multi": multi,
        "process_count": nprocs,
        "global_devices": len(jax.devices()),
        "local_devices": len(jax.local_devices()),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": [round(x, 8) for x in losses],
        "param_fingerprint": fp,
        "ckpt_roundtrip_ok": None,
        "emb_slots": int(args.num_slots),
    }), flush=True)
    watchdog.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
