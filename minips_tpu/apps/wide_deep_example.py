"""wide_deep_example — Wide&Deep / DeepFM CTR on Criteo-shaped data
(BASELINE.json:10: "Wide&Deep / DeepFM on Criteo-1TB, sparse embedding PS
shards on TPU mesh"). The flagship workload: hashed wide weights (dim 1) +
hashed field embeddings + a dense deep tower, all in one fused SPMD step.

Usage: python -m minips_tpu.apps.wide_deep_example --model deepfm
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from minips_tpu.apps.common import (app_main, holdout_split,
                                    log_tables_built, score_holdout)
from minips_tpu.core.config import Config, TableConfig, TrainConfig
from minips_tpu.data.loader import BatchIterator
from minips_tpu.data import synthetic
from minips_tpu.models import wide_deep as wd_model
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.tables.dense import DenseTable
from minips_tpu.tables.sparse import SparseTable
from minips_tpu.train.loop import TrainLoop
from minips_tpu.train.ps_step import PSTrainStep

DEFAULT = Config(
    table=TableConfig(name="ctr", kind="sparse", consistency="bsp",
                      updater="adagrad", lr=0.05, dim=8,
                      num_slots=1 << 18),
    train=TrainConfig(batch_size=1024, num_iters=200),
)
NUM_DENSE, NUM_CAT = 13, 26


def build(cfg: Config, *, use_fm: bool, mesh=None, seed: int = 0,
          compute_dtype=None):
    """Tables + fused step for W&D/DeepFM; also used by
    __graft_entry__.dryrun_multichip."""
    mesh = mesh or make_mesh()
    emb_dim = cfg.table.dim
    wide_t = SparseTable(cfg.table.num_slots, 1, mesh, name="wide",
                         updater=cfg.table.updater, lr=cfg.table.lr,
                         init_scale=0.0, salt=1, seed=seed)
    emb_t = SparseTable(cfg.table.num_slots, emb_dim, mesh, name="emb",
                        updater=cfg.table.updater, lr=cfg.table.lr,
                        init_scale=0.01, salt=2, seed=seed + 1)
    deep_t = DenseTable(
        wd_model.init_deep(jax.random.PRNGKey(seed + 2), NUM_CAT, emb_dim,
                           NUM_DENSE),
        mesh, name="deep", updater="adam", lr=1e-3)

    def loss_fn(deep_params, rows, batch):
        return wd_model.loss(rows["wide"], rows["emb"], deep_params, batch,
                             use_fm=use_fm)

    ps = PSTrainStep(loss_fn, dense=deep_t,
                     sparse={"wide": wide_t, "emb": emb_t},
                     key_fns={"wide": lambda b: b["cat"],
                              "emb": lambda b: b["cat"]},
                     compute_dtype=compute_dtype)
    return ps, (wide_t, emb_t, deep_t)


def _run_streaming(cfg: Config, args, metrics, path: str, *,
                   use_fm: bool) -> dict:
    """One-pass streaming training: the Criteo file is NEVER resident —
    a producer thread parses ~4MB chunks while earlier batches train
    (data/criteo.py stream_criteo_batches; the Criteo-1TB posture). The
    loop ends at min(num_iters, file exhaustion). Holdout eval needs
    resident rows, so --eval_frac is rejected loudly here."""
    if getattr(args, "eval_frac", None):
        raise SystemExit("--eval_frac needs resident rows; it is not "
                         "available with --stream (run a separate "
                         "non-stream eval pass)")
    from minips_tpu.data.criteo import log_transform, stream_criteo_batches

    ps, tables = build(cfg, use_fm=use_fm, seed=cfg.train.seed,
                       compute_dtype=(jnp.bfloat16
                                      if getattr(args, "dtype", "float32")
                                      == "bfloat16" else None))

    def xform(d):  # producer-thread preprocessing
        return {"dense": log_transform(d["dense"], d["dense_mask"]),
                "cat": d["cat"], "y": d["y"]}

    stream_stats: dict = {}
    batches = stream_criteo_batches(path, cfg.train.batch_size,
                                    transform=xform, stats=stream_stats)
    loop = TrainLoop(lambda b: ps(ps.shard_batch(b)), batches,
                     metrics=metrics, log_every=cfg.train.log_every,
                     batch_size=cfg.train.batch_size)
    losses = loop.run(cfg.train.num_iters)
    metrics.log(final_loss=losses[-1] if losses else None,
                samples_per_sec=loop.timer.samples_per_sec,
                # no-silent-caps: rows short of one final batch (absent
                # when num_iters ended the loop before EOF)
                stream_dropped_rows=stream_stats.get("dropped_rows"),
                streamed=True)
    return {"losses": losses,
            "samples_per_sec": loop.timer.samples_per_sec,
            "tables": tables}


def _make_predict(wide_t, emb_t, deep_params, use_fm: bool):
    """Holdout scorer over the live tables + a pulled deep snapshot —
    shared by the spmd and threaded paths so their AUC is computed by one
    code path."""
    def predict(b):
        cats = jnp.asarray(b["cat"])
        return wd_model.logits(
            wide_t.pull(cats), emb_t.pull(cats), deep_params,
            {"dense": jnp.asarray(b["dense"])}, use_fm=use_fm)
    return predict


def _log_collisions(metrics, cats, num_slots) -> dict:
    """Measured key→slot collision rate of the hashed tables over this
    run's key stream (sampled) — hash merging is invisible quality loss
    unless logged (VERDICT r2 #5; sizing guidance in docs/api.md). Both
    tables hash the same cat keys under their own salt."""
    from minips_tpu.tables.sparse import collision_stats

    out = {}
    for name, salt in (("wide", 1), ("emb", 2)):
        st = collision_stats(cats, num_slots, salt=salt)
        out[name] = st
        metrics.log(table=name, **{f"collision_{k}": v
                                   for k, v in st.items()})
    return out


def run(cfg: Config, args, metrics) -> dict:
    use_fm = getattr(args, "model", "widedeep") == "deepfm"
    if getattr(args, "stream", False) \
            and getattr(args, "exec_mode", "spmd") != "spmd":
        # loud beats silently dropping either flag (same convention as
        # _run_threaded's --dtype rejection)
        raise SystemExit("--stream is only wired into --exec spmd")
    if getattr(args, "exec_mode", "spmd") == "multiproc":
        return _run_multiproc(cfg, args, metrics, use_fm=use_fm)
    path = getattr(args, "data_file", None)
    if path and getattr(args, "stream", False):
        return _run_streaming(cfg, args, metrics, path, use_fm=use_fm)
    if getattr(args, "stream", False):
        raise SystemExit("--stream needs --data_file (a file to stream)")
    if path:  # real Criteo TSV through the native/python reader
        from minips_tpu.data.criteo import log_transform, read_criteo
        raw = read_criteo(path)
        data = {"dense": log_transform(raw["dense"], raw["dense_mask"]),
                "cat": raw["cat"], "y": raw["y"]}
    else:
        data = synthetic.criteo_like(16384, seed=cfg.train.seed)
    data, holdout = holdout_split(data,
                                  getattr(args, "eval_frac", None) or 0.0,
                                  seed=cfg.train.seed)
    if getattr(args, "exec_mode", "spmd") == "threaded":
        return _run_threaded(cfg, args, metrics, data, holdout,
                             use_fm=use_fm)
    ps, tables = build(cfg, use_fm=use_fm, seed=cfg.train.seed,
                       compute_dtype=(jnp.bfloat16
                                      if getattr(args, "dtype", "float32")
                                      == "bfloat16" else None))
    wide_t, emb_t, deep_t = tables
    log_tables_built(metrics, [(wide_t.emb, wide_t.opt_state()),
                               (emb_t.emb, emb_t.opt_state()),
                               (deep_t.params, deep_t.opt_state)])
    _log_collisions(metrics, data["cat"], cfg.table.num_slots)
    batches = BatchIterator(data, cfg.train.batch_size, seed=cfg.train.seed)
    loop = TrainLoop(lambda b: ps(ps.shard_batch(b)), batches,
                     metrics=metrics, log_every=cfg.train.log_every,
                     batch_size=cfg.train.batch_size)
    losses = loop.run(cfg.train.num_iters)
    metrics.log(final_loss=losses[-1],
                samples_per_sec=loop.timer.samples_per_sec)
    return score_holdout(
        _make_predict(wide_t, emb_t, deep_t.pull(), use_fm), holdout,
        {"losses": losses, "samples_per_sec": loop.timer.samples_per_sec,
         "tables": tables, "step": ps}, metrics)


def _run_threaded(cfg: Config, args, metrics, data, holdout, *,
                  use_fm: bool) -> dict:
    """Reference-semantics worker threads for the flagship workload: each
    thread pulls the batch's embedding rows + the deep tower through the
    consistency gate, pushes grads, clocks — the threaded Engine path the
    other apps already have (SURVEY.md §3.3 hot loop, thread-per-worker)."""
    from minips_tpu.consistency import make_controller
    from minips_tpu.core.engine import Engine
    from minips_tpu.apps.common import threaded_train

    if getattr(args, "dtype", "float32") != "float32":
        # loud beats silently training f32 while reporting bf16 (same
        # convention as lm_example's --remat off-dp rejection)
        raise SystemExit("--dtype is only wired into --exec spmd/multiproc")
    _, (wide_t, emb_t, deep_t) = build(cfg, use_fm=use_fm,
                                       seed=cfg.train.seed)
    engine = Engine(num_workers=cfg.train.num_workers).start_everything()
    for name, t in (("wide", wide_t), ("emb", emb_t), ("deep", deep_t)):
        engine.register_table(name, t, make_controller(
            cfg.table.consistency, engine.num_workers,
            staleness=cfg.table.staleness, sync_every=0))

    @jax.jit
    def g(wide_rows, emb_rows, deep_params, batch):
        def f(w, e, dp):
            return wd_model.loss(w, e, dp, batch, use_fm=use_fm)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
            wide_rows, emb_rows, deep_params)
        return (loss,) + grads

    NW = engine.num_workers

    def step_fn(info, batch):
        wt, et, dt = (info.table(n) for n in ("wide", "emb", "deep"))
        cats = jnp.asarray(batch["cat"])
        w_rows = wt.pull(keys=cats)  # [B, NUM_CAT, 1]
        e_rows = et.pull(keys=cats)  # [B, NUM_CAT, dim]
        deep_params = dt.pull()
        loss, gw, ge, gd = g(w_rows, e_rows, deep_params,
                             {"dense": jnp.asarray(batch["dense"]),
                              "y": jnp.asarray(batch["y"])})
        # NW workers each push once per clock; /NW keeps the per-round
        # update magnitude equal to the spmd path's single mean-loss push
        # for EVERY updater (adagrad normalizes constants away, sgd does
        # not — unscaled pushes would be an NW-times effective lr)
        wt.push(gw / NW, keys=cats)
        et.push(ge / NW, keys=cats)
        dt.push(jax.tree.map(lambda x: x / NW, gd))
        return loss

    mean_losses = threaded_train(engine, cfg, data, step_fn,
                                 clock_tables=["wide", "emb", "deep"])
    deep_params = deep_t.pull()
    engine.stop_everything()
    metrics.log(final_loss=mean_losses[-1])
    return score_holdout(
        _make_predict(wide_t, emb_t, deep_params, use_fm), holdout,
        {"losses": mean_losses, "samples_per_sec": 0.0,
         "tables": (wide_t, emb_t, deep_t)}, metrics)


def _run_multiproc(cfg: Config, args, metrics, *, use_fm: bool) -> dict:
    """The flagship sparse workload on the key-range-sharded PS
    (VERDICT r1 #3): N launcher processes, each with its own Criteo data
    shard; wide/emb tables PARTITIONED across processes (per-process
    memory ~1/N), pushes ship only the batch's touched rows per owner —
    row-sparse, never a table-sized blob; the deep tower rides the dense
    range path; BSP/SSP/ASP via the owner-side staleness gate. Prints the
    one-JSON-line launcher protocol (smoke tests / bench)."""
    import os
    import sys
    import time

    import numpy as np

    import jax.numpy as jnp

    from minips_tpu.apps.common import (emit_multiproc_done, holdout_split,
                                        init_multiproc, run_multiproc_body,
                                        shard_checkpointing)
    from minips_tpu.data import synthetic
    from minips_tpu.tables.sparse import hash_to_slots_np
    from minips_tpu.train.sharded_ps import (ShardedTable, ShardedPSTrainer)
    from minips_tpu.utils.evaluation import StreamingAUC, padded_chunks

    rank, nprocs, bus, monitor, staleness = init_multiproc(
        cfg.table.consistency, cfg.table.staleness)

    path = getattr(args, "data_file", None)
    if path:  # real Criteo TSV; round-robin row shard per rank
        from minips_tpu.data.criteo import log_transform, read_criteo
        raw = read_criteo(path)
        data = {"dense": log_transform(raw["dense"], raw["dense_mask"]),
                "cat": raw["cat"], "y": raw["y"]}
        data = {k: v[rank::nprocs] for k, v in data.items()}
    else:  # per-rank synthetic shard (disjoint seeds, shared signal)
        data = synthetic.criteo_like(8192, seed=100 + rank)
    # explicit --eval_frac 0 disables eval (the flag's contract); only an
    # UNSET flag takes the multiproc default of 0.2
    frac = getattr(args, "eval_frac", None)
    frac = 0.2 if frac is None else frac
    data, holdout = holdout_split(data, frac, seed=cfg.train.seed)

    slots = cfg.table.num_slots
    emb_dim = cfg.table.dim
    # per-rank measured collision accounting for the hashed tables (the
    # multiproc twin of _log_collisions; same salts)
    coll = _log_collisions(metrics, data["cat"], slots)
    updater = cfg.table.updater  # sgd/adagrad/adam all server-side now
    push_comm = getattr(args, "push_comm", "float32")
    mk = lambda name, dim, scale, seed, comm="float32": ShardedTable(  # noqa: E731
        name, slots, dim, bus, rank, nprocs, updater=updater,
        lr=cfg.table.lr, init_scale=scale, seed=seed, monitor=monitor,
        pull_timeout=30.0, push_comm=comm)
    # --push-comm compresses only wide-DIMENSION tables (the emb table):
    # at dim 1 (wide_t) the per-row f32 scale outweighs the int8 saving
    wide_t = mk("wide", 1, 0.0, 1)
    emb_t = mk("emb", emb_dim, 0.01, 2, comm=push_comm)
    # deep tower: flat param vector on the dense range path (adagrad
    # server-side — the reference's dense-updater family)
    import jax
    from jax.flatten_util import ravel_pytree
    deep0 = wd_model.init_deep(jax.random.PRNGKey(cfg.train.seed + 2),
                               NUM_CAT, emb_dim, NUM_DENSE)
    deep_flat0, unravel = ravel_pytree(deep0)
    deep_t = ShardedTable("deep", deep_flat0.shape[0], 1, bus, rank, nprocs,
                          updater="adagrad", lr=0.02, monitor=monitor,
                          pull_timeout=30.0)
    trainer = ShardedPSTrainer(
        {"wide": wide_t, "emb": emb_t, "deep": deep_t}, bus, nprocs,
        staleness=staleness, gate_timeout=30.0, monitor=monitor)
    resume = shard_checkpointing(bus, nprocs, cfg.train.checkpoint_dir,
                                 rank)
    bus.handshake(nprocs)
    # the deep table stores the DELTA from a shared deterministic init
    # (every rank derives deep_flat0 from the same PRNGKey): the zero
    # table needs no init broadcast, and range pushes stay pure grads
    start_iter, save_hook = resume(
        {"wide": wide_t, "emb": emb_t, "deep": deep_t, "trainer": trainer},
        cfg.train.checkpoint_every)

    @jax.jit
    def wd_grads(wide_rows, emb_rows, deep_vec, batch):
        def f(w, e, dv):
            return wd_model.loss(w, e, unravel(dv[:, 0] + deep_flat0),
                                 batch, use_fm=use_fm)
        loss, grads = jax.value_and_grad(f, argnums=(0, 1, 2))(
            wide_rows, emb_rows, deep_vec)
        return (loss,) + grads

    B = cfg.train.batch_size
    # resumed runs reseed on (rank, start): sampling is with-replacement,
    # so resume is convergence-equivalent, not bit-exact
    rng = np.random.default_rng((rank, start_iter))
    losses = []
    auc_val = None
    fp = 0.0
    t0 = time.monotonic()

    def body():
        nonlocal auc_val, fp
        for i in range(start_iter, cfg.train.num_iters):
            kill_at = getattr(args, "kill_at", 0)
            if kill_at and rank == getattr(args, "kill_rank", -1) \
                    and i == kill_at:
                os._exit(137)
            sel = rng.integers(0, data["y"].shape[0], size=B)
            cats = data["cat"][sel]
            wide_keys = hash_to_slots_np(cats, slots, 1).reshape(-1)
            emb_keys = hash_to_slots_np(cats, slots, 2).reshape(-1)
            wide_rows = wide_t.pull(wide_keys).reshape(B, NUM_CAT, 1)
            emb_rows = emb_t.pull(emb_keys).reshape(B, NUM_CAT, emb_dim)
            deep_vec = deep_t.pull_all()
            loss, gw, ge, gd = wd_grads(
                jnp.asarray(wide_rows), jnp.asarray(emb_rows),
                jnp.asarray(deep_vec),
                {"dense": jnp.asarray(data["dense"][sel]),
                 "y": jnp.asarray(data["y"][sel])})
            wide_t.push(wide_keys, np.asarray(gw).reshape(-1, 1))
            emb_t.push(emb_keys, np.asarray(ge).reshape(-1, emb_dim))
            deep_t.push_dense(np.asarray(gd))
            losses.append(float(loss))
            trainer.tick()
            save_hook(i)
            slow_rank = getattr(args, "slow_rank", -1)
            if rank == slow_rank and getattr(args, "slow_ms", 0) > 0:
                time.sleep(args.slow_ms / 1000.0)
        trainer.finalize(timeout=30.0)
        # ---- streaming holdout AUC on the FINAL shared tables
        if holdout is not None:
            auc = StreamingAUC()
            deep_final = unravel(deep_t.pull_all()[:, 0] + deep_flat0)
            for chunk, n_valid in padded_chunks(holdout, 4096):
                cats = chunk["cat"]
                cb = cats.shape[0]
                w_rows = wide_t.pull(
                    hash_to_slots_np(cats, slots, 1).reshape(-1)
                ).reshape(cb, NUM_CAT, 1)
                e_rows = emb_t.pull(
                    hash_to_slots_np(cats, slots, 2).reshape(-1)
                ).reshape(cb, NUM_CAT, emb_dim)
                lg = wd_model.logits(
                    jnp.asarray(w_rows), jnp.asarray(e_rows), deep_final,
                    {"dense": jnp.asarray(chunk["dense"])}, use_fm=use_fm)
                auc.update(np.asarray(lg)[:n_valid], chunk["y"][:n_valid])
            auc_val = auc.result()
        # fingerprints for the replica-agreement assertion
        fp = (float(np.sum(wide_t.pull_all()))
              + float(np.sum(emb_t.pull_all()))
              + float(np.sum(deep_t.pull_all())))
        trainer.shutdown_barrier(timeout=10.0)

    code = run_multiproc_body(rank, trainer, body)
    if code == 0:
        from minips_tpu.train.sharded_ps import table_state_bytes
        # deep table is always adagrad server-side (shard + accumulator)
        table_bytes = (table_state_bytes(slots, 1, updater)        # wide
                       + table_state_bytes(slots, emb_dim, updater)  # emb
                       + table_state_bytes(deep_flat0.shape[0], 1,
                                           "adagrad"))             # deep
        # metrics BEFORE the protocol line: the launcher harvests the LAST
        # JSON line on stdout as the result dict
        metrics.log(final_loss=losses[-1] if losses else None,
                    holdout_auc=auc_val)
        emit_multiproc_done(
            trainer, rank, t0, losses, table_bytes, fp,
            auc=auc_val, resumed_from=start_iter,
            push_comm=push_comm,
            emb_collision_rate=coll["emb"]["collision_rate"],
            emb_unique_keys=coll["emb"]["unique_keys"],
            # embedding-table wire alone: the row-sparse claim is about
            # these (the deep tower is inherently dense-range traffic)
            sparse_bytes_pushed=wide_t.bytes_pushed + emb_t.bytes_pushed,
            emb_bytes_pushed=emb_t.bytes_pushed)
    monitor.stop()
    bus.close()
    if code:
        sys.exit(code)
    return {"losses": losses, "auc": auc_val}


def _flags(parser):
    parser.add_argument("--model", default="widedeep",
                        choices=["widedeep", "deepfm"])
    parser.add_argument("--data_file", default=None,
                        help="Criteo TSV file instead of synthetic data")
    parser.add_argument("--stream", action="store_true",
                        help="one-pass streaming read of --data_file: a "
                             "producer thread parses chunks while training "
                             "runs; the file is never resident (Criteo-1TB "
                             "posture). Ends at min(num_iters, EOF)")
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="worker-math precision (master tables stay "
                             "float32)")
    parser.add_argument("--eval_frac", type=float, default=None,
                        help="fraction of rows held out and scored by "
                             "streaming ROC-AUC after training; 0 disables "
                             "(default: 0 for spmd/threaded, 0.2 for "
                             "multiproc)")
    from minips_tpu.apps.common import add_push_comm_flag

    add_push_comm_flag(parser)
    # multiproc straggler/fault injection (smoke tests)
    parser.add_argument("--slow-rank", dest="slow_rank", type=int,
                        default=-1)
    parser.add_argument("--slow-ms", dest="slow_ms", type=float,
                        default=0.0)
    parser.add_argument("--kill-at", dest="kill_at", type=int, default=0)
    parser.add_argument("--kill-rank", dest="kill_rank", type=int,
                        default=-1)


def main(argv=None, metrics=None):
    return app_main("wide_deep_example", DEFAULT, run, extra_flags=_flags,
                    exec_choices=("spmd", "threaded", "multiproc"),
                    argv=argv, metrics=metrics)


if __name__ == "__main__":
    main()
