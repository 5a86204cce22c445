"""Distributed SSP training — the multi-process smoke workload.

``--model lr`` (default) is sparse-free logistic regression; ``--model
mlp`` is the 3-layer MLP on MNIST-shaped data — the BASELINE.json config
"3-layer MLP on MNIST, SSP staleness = 4" — through the very same
SSPTrainer (it is model-agnostic: any jitted (params, batch) -> (params,
loss) step).

The reference's distributed smoke story is its launch scripts run against a
hostfile of localhost entries: N real processes, real zmq over loopback
(SURVEY.md §4). Same here: run under the launcher

    python -m minips_tpu.launch --n 3 -- python -m minips_tpu.apps.ssp_lr_example \
        --iters 60 --mode ssp --staleness 2

and each process trains LR on its own data shard via SSPTrainer (delta
gossip + clock gate over the bus), then prints ONE JSON line of results for
the driver/test to assert on: loss fell, the staleness bound held, replicas
agree after finalize.

Fault drill (SURVEY.md §5.3): ``--kill-at K --kill-rank R`` makes rank R
die abruptly at step K; survivors detect via heartbeat, exit with code 42;
the driver relaunches everyone with ``--resume`` to restore the latest
checkpoint and finish — restart-from-checkpoint, the reference's recovery
semantics (SURVEY.md §3.5).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["lr", "mlp"], default="lr",
                    help="lr: logistic regression; mlp: 3-layer MLP on "
                         "MNIST-shaped data (BASELINE.json config 2)")
    ap.add_argument("--iters", type=int, default=60)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--dim", type=int, default=None,
                    help="lr: feature dim (default 64); mlp: fixed at 784 "
                         "(MNIST-shaped), passing --dim is an error")
    ap.add_argument("--lr", type=float, default=0.1)
    ap.add_argument("--mode", choices=["bsp", "ssp", "asp"], default="ssp")
    ap.add_argument("--staleness", type=int, default=2)
    ap.add_argument("--push-every", type=int, default=1)
    ap.add_argument("--compress", type=float, default=1.0,
                    help="fraction of delta entries per push (<1 = top-k "
                         "sparsification with error feedback)")
    ap.add_argument("--slow-rank", type=int, default=-1,
                    help="rank to artificially slow (straggler injection)")
    ap.add_argument("--slow-ms", type=float, default=0.0)
    ap.add_argument("--jitter-ms", type=float, default=0.0,
                    help="transient-stall injection: every rank sleeps "
                         "this long on a random --jitter-prob fraction of "
                         "its steps (rank-seeded; the workload where SSP "
                         "beats BSP wall-clock — the slack window absorbs "
                         "stalls instead of propagating them)")
    ap.add_argument("--jitter-prob", type=float, default=0.2)
    ap.add_argument("--data-file", default=None,
                    help="libsvm file fed via DYNAMIC block assignment "
                         "(rank 0 = BlockMaster, SURVEY.md §1 L5): fast "
                         "ranks take more blocks, a dead rank's blocks "
                         "re-queue to survivors. --model lr only.")
    ap.add_argument("--block-lines", type=int, default=200,
                    help="lines per assigned block (--data-file mode)")
    ap.add_argument("--max-nnz", type=int, default=32,
                    help="--data-file mode: padded features per row; rows "
                         "with more index:value pairs are TRUNCATED to "
                         "this many")
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--checkpoint-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--kill-at", type=int, default=0,
                    help="die abruptly at this step (fault injection)")
    ap.add_argument("--kill-rank", type=int, default=-1)
    args = ap.parse_args(argv)

    import jax

    # the tests' per-child CPU pin (matches apps/common.py), applied
    # before any backend touch
    if os.environ.get("MINIPS_FORCE_CPU"):
        jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from minips_tpu.comm.heartbeat import HeartbeatMonitor
    from minips_tpu.data import synthetic
    from minips_tpu.launch import init_from_env
    from minips_tpu.train.ssp_trainer import PeerFailureError, SSPTrainer

    rank, nprocs, bus = init_from_env()
    staleness = {"bsp": 0, "ssp": args.staleness,
                 "asp": float("inf")}[args.mode]

    # --- dynamic block assignment (--data-file): rank 0 coordinates
    master = client = None
    requeued = {"n": 0}
    if args.data_file:
        if args.model != "lr":
            ap.error("--data-file implies --model lr")
        from minips_tpu.data import blocks as blk

        if bus is None:  # single-process: plain list, no coordination
            client = blk.split_file_lines(args.data_file, args.block_lines)
        else:
            if rank == 0:
                master = blk.BlockMaster(
                    bus, blk.split_file_lines(args.data_file,
                                              args.block_lines))
            client = blk.BlockClient(bus, local_master=master)

    # my shard: different seed per rank = disjoint data (SURVEY.md §2.2 DP)
    if args.model == "mlp":
        if args.dim is not None:
            ap.error("--dim applies to --model lr only (mlp input is "
                     "fixed at 784, MNIST-shaped)")
        from minips_tpu.models import mlp as mlp_model

        data = synthetic.mnist_like(n=args.batch * 8, seed=100 + rank)
        params = mlp_model.init(jax.random.PRNGKey(0),
                                sizes=(784, 256, 128, 10))
        loss_fn = mlp_model.loss
    else:
        from minips_tpu.models import lr as lr_model

        # file mode defaults to the a9a feature space (123, SURVEY.md §7.3)
        dim = args.dim if args.dim is not None else (
            123 if args.data_file else 64)
        data = None if args.data_file else synthetic.classification_dense(
            n=args.batch * 8, dim=dim, seed=100 + rank)
        params = lr_model.init(dim)
        loss_fn = lr_model.loss_dense

    @jax.jit
    def local_step(p, batch):
        loss, g = jax.value_and_grad(loss_fn)(p, batch)
        new = jax.tree.map(lambda w, gw: w - args.lr * gw / nprocs, p, g)
        return new, loss

    monitor = None
    if bus is not None:
        on_fail = None
        if master is not None:
            def on_fail(pid):  # dead rank's blocks back to the survivors
                requeued["n"] += master.handle_failure(pid)
        monitor = HeartbeatMonitor(
            bus, peer_ids=list(range(nprocs)),
            interval=0.2, timeout=2.0, on_failure=on_fail).start()

    trainer = SSPTrainer(local_step, params, bus, nprocs,
                         staleness=staleness, push_every=args.push_every,
                         gate_timeout=30.0, monitor=monitor,
                         compress=args.compress) \
        if bus is not None else None
    if bus is not None:
        # AFTER all handlers (delta/clock/heartbeat) are registered — a
        # handler-less recv loop drops messages, so handshaking first would
        # reopen the very lost-traffic window it exists to close.
        bus.handshake(nprocs)

    ckpt = None
    start_step = 0
    if args.checkpoint_dir and trainer is not None:
        from minips_tpu.ckpt.orbax_backend import make_checkpointer

        ckpt = make_checkpointer(args.checkpoint_dir, {"ssp": trainer},
                                 keep=2)
        if args.resume:
            start_step = ckpt.restore()

    losses = []
    consumed = {"n": 0}
    rng = np.random.default_rng(rank)
    jitter_rng = np.random.default_rng(1000 + rank)
    code = 0
    t_loop0 = time.monotonic()

    def step_tail(i, loss):
        losses.append(loss)
        if rank == args.slow_rank and args.slow_ms > 0:
            time.sleep(args.slow_ms / 1000.0)
        if args.jitter_ms > 0 and jitter_rng.random() < args.jitter_prob:
            time.sleep(args.jitter_ms / 1000.0)
        if (ckpt is not None and rank == 0 and args.checkpoint_every
                and (i + 1) % args.checkpoint_every == 0):
            ckpt.save(step=i + 1)

    try:
        if args.data_file:
            # ---- dynamic block-driven loop: batches stream out of blocks
            # the master hands this rank; fast ranks naturally take more
            from minips_tpu.data.blocks import (iter_block_batches,
                                                read_block_bytes)
            from minips_tpu.data.libsvm import (apply_one_based_shift,
                                                densify,
                                                detect_one_based,
                                                parse_libsvm_block,
                                                parse_libsvm_lines)

            # 1-based-vs-0-based is a WHOLE-FILE property: decide it once
            # from the head (per-block detection would silently shift only
            # the blocks that happen to lack feature 0)
            with open(args.data_file, "rb") as f:
                one_based = detect_one_based(parse_libsvm_lines(
                    [ln for ln, _ in zip(f, range(1000))]))

            def counting(it):
                for b in it:
                    consumed["n"] += 1
                    yield b

            def parse_block(b):
                # native mem parse of the block's raw bytes (6x the
                # python line loop; python stays the fallback/oracle)
                d = parse_libsvm_block(read_block_bytes(b),
                                       width=args.max_nnz)
                if one_based:
                    apply_one_based_shift(d)
                return densify(d, dim)

            i = start_step
            for batch in iter_block_batches(counting(client), parse_block,
                                            args.batch):
                if (args.kill_at and rank == args.kill_rank
                        and i == args.kill_at):
                    os._exit(137)
                if trainer is not None:
                    loss = trainer.step(batch)
                else:
                    params, loss = local_step(params, batch)
                    loss = float(loss)
                step_tail(i, loss)
                i += 1
                if i >= args.iters:
                    break
            if trainer is not None:
                # unequal per-rank step counts are the point of dynamic
                # assignment: a finished rank must never stall peers' gates
                trainer.retire()
        else:
            for i in range(start_step, args.iters):
                if (args.kill_at and rank == args.kill_rank
                        and i == args.kill_at):
                    os._exit(137)  # abrupt death: no close(), no flush
                sel = rng.integers(0, data["y"].shape[0], size=args.batch)
                batch = {"x": data["x"][sel], "y": data["y"][sel]}
                if trainer is not None:
                    loss = trainer.step(batch)
                else:  # single-process degenerate case
                    params, loss = local_step(params, batch)
                    loss = float(loss)
                step_tail(i, loss)
        if trainer is not None:
            final = trainer.finalize(timeout=20.0)
    except PeerFailureError as e:
        print(json.dumps({"rank": rank, "event": "peer_failure",
                          "dead": sorted(e.dead),
                          "at_clock": trainer.clock}), flush=True)
        code = 42
    except TimeoutError as e:
        print(json.dumps({"rank": rank, "event": "gate_timeout",
                          "err": str(e)}), flush=True)
        code = 43

    if code == 0 and trainer is not None:
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(final)
        flat = np.asarray(flat)
        print(json.dumps({
            "rank": rank, "event": "done",
            "wall_s": round(time.monotonic() - t_loop0, 4),
            "loss_first": losses[0] if losses else None,
            "loss_last": float(np.mean(losses[-5:])) if losses else None,
            "gate_waits": trainer.gate_waits,
            "max_skew_seen": trainer.max_skew_seen,
            "deltas_applied": trainer.deltas_applied,
            "bytes_pushed": trainer.bytes_pushed,
            "param_sum": float(flat.sum()),
            "param_norm": float(np.linalg.norm(flat)),
            "clock": trainer.clock,
            "blocks_consumed": consumed["n"],
            "blocks_requeued": requeued["n"],
            "blocks_remaining": (master.assigner.remaining
                                 if master is not None else None),
        }), flush=True)

    if monitor is not None:
        monitor.stop()
    if bus is not None:
        bus.close()
    return code


if __name__ == "__main__":
    sys.exit(main())
