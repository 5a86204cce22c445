"""Benchmark harness — emits ONE JSON line for the driver.

Primary metric (BASELINE.json:2): **samples/sec/chip, LR + MLP on
Criteo-shaped data**. The reference publishes no numbers (BASELINE.json:14
``"published": {}``); the quantitative anchor is the north-star target of
>= 1M samples/sec aggregate on a TPU v4-32 (16 chips) for LR + 3-layer MLP
on Criteo with SSP staleness <= 4 (BASELINE.json:3-4) → 62,500
samples/sec/chip; ``vs_baseline`` = measured / target. A run that finds
no TPU exits non-zero at once; ``--cpu`` is the explicit harness check (8
fake CPU devices, shrunk shapes) and reports ``vs_baseline: null`` — a CPU
rate must never masquerade as a TPU number (VERDICT r1 weak #7).

Round-2 credibility upgrades (VERDICT r1 "Next round" #2):

- **Chained-scan timing**: K steps are folded into ONE dispatch via
  ``lax.scan`` over the pure fused-step transition with donated state, and
  the reported rate is the median of R such calls — per-step host
  timing cannot see through the dispatch floor and call-to-call noise.
- **FLOP accounting**: every suite reports analytic matmul FLOPs/step,
  achieved TFLOP/s, and MFU against the chip's bf16 peak (by device_kind)
  so the headline survives arithmetic (a rate implying > peak is a bug,
  not a result).
- **Suites where MFU is meaningful**: ``lm`` (decoder LM with the flash-
  attention kernel, bf16 compute) and ``wd`` (Wide&Deep with a 2^22-slot
  embedding table — the memory-bound end) alongside the primary
  ``lrmlp``.
- **e2e**: streams a Criteo-format TSV from disk through the (native if
  available) parser and a prefetch thread into the fused step —
  samples/sec INCLUDING input IO, which the microbench deliberately
  excludes.

Usage: python bench.py [--cpu] [--suite all|lrmlp|lm|wd|mf|w2v|e2e|ps]

Round 3 adds ``mf`` and ``w2v`` so every BASELINE.json workload config
(1-2 lrmlp, 3 mf, 4 wd, 5 w2v) has a measured per-config rate.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time

_NO_TPU_RC = 3  # a chip suite found no TPU; --suite all stops on it

# bf16 peak matmul TFLOP/s per chip, by jax device_kind (public specs).
# MFU is reported against bf16 peak even for f32 suites — a deliberate
# lower bound, labeled as such.
_BF16_PEAK = {
    "TPU v2": 46e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12,
    "TPU v5": 459e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def _peak_for(device) -> float:
    """bf16 peak of a TPU by exact ``device_kind``; an unknown kind is an
    error, not a default (a wrong peak silently rescales every MFU)."""
    kind = device.device_kind
    if kind not in _BF16_PEAK:
        raise SystemExit(
            f"bench: no bf16 peak recorded for device_kind {kind!r}; add "
            "it to _BF16_PEAK with its source")
    return _BF16_PEAK[kind]


def _mlp_flops_per_sample(sizes) -> float:
    """Matmul-only analytic cost: fwd = 2·MACs, bwd ≈ 2× fwd → 3× fwd."""
    fwd = sum(2 * a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return 3.0 * fwd


_PROFILE_DIR = None  # set by --profile: capture one steady-state rep


def _chain_timed(jitted_chain, state, reps):
    """Median seconds per chained call. The chain is compiled once; each
    timed call is one dispatch running K steps on device; block on the
    returned loss so the timer covers the device work. With --profile one
    EXTRA steady-state rep runs under jax.profiler before the timed loop
    — captured but never timed, so profiler overhead can't leak into the
    reported numbers at any --reps."""
    import jax

    state, loss = jitted_chain(state)          # compile + warmup
    jax.block_until_ready(loss)
    if _PROFILE_DIR:
        from minips_tpu.utils.profiling import profile_trace
        with profile_trace(_PROFILE_DIR):
            state, loss = jitted_chain(state)  # captured, untimed
            jax.block_until_ready(loss)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        state, loss = jitted_chain(state)
        jax.block_until_ready(loss)
        times.append(time.perf_counter() - t0)
    return state, statistics.median(times)


def _suite_result(samples, dt, n_chips, flops_per_step, peak):
    sps_chip = samples / dt / n_chips
    tflops = flops_per_step / dt / 1e12 / n_chips  # per chip
    out = {"samples_per_sec_per_chip": round(sps_chip, 1),
           # 9 decimals: tiny CPU-validation runs live in the micro-TFLOP
           # range (the mf suite's analytic cost is ~200k FLOPs/call at
           # test shapes) and must not round to a test-failing hard zero
           "tflops_per_chip": round(tflops, 9),
           "mfu_vs_bf16_peak": (round(tflops * 1e12 / peak, 4)
                                if peak else None)}
    if peak and tflops * 1e12 > peak:
        out["warning"] = ("achieved TFLOP/s exceeds chip peak — timing or "
                          "FLOP accounting is broken; do not trust")
    return out


def _batch_rotation(batches, K):
    """Stack >= 2 DISTINCT batches and return ``(stacked, idx)`` where
    ``idx`` is the scan's xs (step -> batch index). The body dynamically
    gathers its step's batch from ``stacked``, so per-batch work (key
    hashing, dedup, sort) varies across scan iterations and XLA cannot
    hoist it out of the timed region — the loop-invariant-batch hazard of
    VERDICT r2 weak #5. Real training pays that cost on every fresh
    batch; now the microbenches do too."""
    import jax
    import jax.numpy as jnp

    stacked = jax.tree.map(lambda *ls: jnp.stack(ls), *batches)
    return stacked, jnp.arange(K) % len(batches)


def _pick(stacked, i):
    import jax

    return jax.tree.map(lambda l: l[i], stacked)


def _ps_chain_timed(ps, batches, args, k_div=2):
    """Chained-scan timing for one PSTrainStep: rotate the given distinct
    sharded batches through K = max(chain//k_div, 2) steps in a single
    donated-state ``lax.scan`` dispatch (shared by the wd/mf/w2v suites —
    the timing contract lives in exactly one place). Returns
    ``(K, dt, final_state)``; final_state is live (the initial state's
    buffers were donated into the chain)."""
    import functools

    import jax

    K = max(args.chain // k_div, 2)
    stacked, idx = _batch_rotation(batches, K)
    pure = ps.step_fn_pure

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chained(state):
        def body(s, i):
            s2, loss = pure(s, _pick(stacked, i))
            return s2, loss
        s, losses = jax.lax.scan(body, state, idx)
        return s, losses[-1]

    state, dt = _chain_timed(chained, ps._collect_state(), args.reps)
    return K, dt, state


# --------------------------------------------------------------- suites
def bench_lrmlp(args, n_chips, peak):
    """The primary metric: every sample through BOTH fused steps (sparse
    LR and the 3-layer MLP over dense+embeddings), f32 masters."""
    import functools

    import jax
    import jax.numpy as jnp

    from minips_tpu.data import synthetic
    from minips_tpu.models import lr as lr_model
    from minips_tpu.models import mlp as mlp_model
    from minips_tpu.models import wide_deep as wd_model
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.dense import DenseTable
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    mesh = make_mesh()
    B = args.batch
    data = synthetic.criteo_like(B, seed=0)
    data2 = synthetic.criteo_like(B, seed=1)

    wide_t = SparseTable(1 << 18, 1, mesh, name="wide", updater="adagrad",
                         lr=0.05, init_scale=0.0, salt=1)
    lin_t = DenseTable(lr_model.init(13), mesh, name="lin",
                       updater="adagrad", lr=0.05)

    def lr_loss(dp, rows, batch):
        logits = (jnp.sum(rows["wide"][..., 0], axis=-1)
                  + lr_model.logits_dense(dp, batch["dense"]))
        return lr_model.bce_with_logits(logits, batch["y"])

    lr_step = PSTrainStep(lr_loss, dense=lin_t, sparse={"wide": wide_t},
                          key_fns={"wide": lambda b: b["cat"]})

    emb_t = SparseTable(1 << 18, 8, mesh, name="emb", updater="adagrad",
                        lr=0.05, init_scale=0.01, salt=2)
    deep_t = DenseTable(
        wd_model.init_deep(jax.random.PRNGKey(0), 26, 8, 13,
                           hidden=(256, 128)),
        mesh, name="deep", updater="adam", lr=1e-3)

    def mlp_loss(dp, rows, batch):
        bsz = rows["emb"].shape[0]
        x = jnp.concatenate([batch["dense"], rows["emb"].reshape(bsz, -1)],
                            axis=-1)
        logits = mlp_model.apply(dp, x)[:, 0]
        return lr_model.bce_with_logits(logits, batch["y"])

    mlp_step = PSTrainStep(mlp_loss, dense=deep_t, sparse={"emb": emb_t},
                           key_fns={"emb": lambda b: b["cat"]})

    # one chained program runs BOTH models' pure transitions K times,
    # rotating 2 distinct batches so per-batch hash/dedup stays timed
    lr_pure, mlp_pure = lr_step.step_fn_pure, mlp_step.step_fn_pure
    K = args.chain
    stacked, idx = _batch_rotation(
        [lr_step.shard_batch(data), lr_step.shard_batch(data2)], K)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chained(state):
        def body(s, i):
            b = _pick(stacked, i)
            s1, l1 = lr_pure(s[0], b)
            s2, l2 = mlp_pure(s[1], b)
            return (s1, s2), (l1, l2)
        s, losses = jax.lax.scan(body, state, idx)
        return s, jax.tree.map(lambda x: x[-1], losses)

    state = (lr_step._collect_state(), mlp_step._collect_state())
    state, dt = _chain_timed(chained, state, args.reps)

    flops_step = B * K * (
        _mlp_flops_per_sample((13 + 26 * 8, 256, 128, 1))   # deep tower
        + _mlp_flops_per_sample((13, 1)))                   # LR linear
    return _suite_result(B * K, dt, n_chips, flops_step, peak)


def bench_lm(args, n_chips, peak):
    """Decoder LM with the flash-attention kernel, bf16 compute — the
    suite where MFU is meaningful (matmul-dominated)."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from minips_tpu.models import transformer as tfm
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.dense import DenseTable

    mesh = make_mesh()
    B, T = args.lm_batch, args.lm_seq
    D, depth, heads = args.lm_dim, args.lm_depth, args.lm_dim // 64
    vocab = 1 << 14
    params = tfm.init(jax.random.PRNGKey(0), vocab=vocab, dim=D,
                      heads=heads, depth=depth, max_len=T,
                      kv_heads=args.lm_kv_heads, rope=args.lm_rope)
    # optimizer-state memory lever (tables/updaters.py): f32 adam state
    # is what HBM-bounds the frontier (BASELINE.md); bf16 moments halve
    # it, int8 blockwise quarters it — buying batch/seq headroom
    updater = {"f32": "adam", "bf16": "adam_bf16",
               "int8": "adam8"}[args.lm_opt_state]
    table = DenseTable(params, mesh, name="lm", updater=updater, lr=1e-3)
    # --cpu validates the harness on the XLA full-scores path; a chip run
    # takes the compiled kernels or fails (ops/flash_attention.py)
    attn = "reference" if args.cpu else "flash"
    remat = False
    if args.lm_remat:
        remat = (True if args.lm_remat_mode == "full"
                 else args.lm_remat_mode)
    step = table.make_step(
        functools.partial(tfm.grad_fn, heads=heads, attn_impl=attn,
                          remat=remat, head_chunk=args.lm_head_chunk),
        jit=False, compute_dtype=jnp.bfloat16)

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from minips_tpu.parallel.mesh import DATA_AXIS

    rng = np.random.default_rng(0)
    sh = NamedSharding(mesh, P(DATA_AXIS))
    K = max(args.chain // 4, 2)
    stacked, idx = _batch_rotation(
        [{"tokens": jax.device_put(
            jnp.asarray(rng.integers(0, vocab, size=(B, T + 1))), sh)}
         for _ in range(2)], K)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chained(state):
        def body(s, i):
            p, o, loss = step(s[0], s[1], _pick(stacked, i))
            return (p, o), loss
        s, losses = jax.lax.scan(body, state, idx)
        return s, losses[-1]

    state, dt = _chain_timed(chained, (table.params, table.opt_state),
                             args.reps)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    tokens = B * T
    m_mat = 6.0 * n_params * tokens                 # matmul 6PT
    m_attn = 12.0 * B * T * T * D * depth * 0.5     # causal attn fwd+bwd
    flops_step = K * (m_mat + m_attn)
    out = _suite_result(K * tokens, dt, n_chips, flops_step, peak)
    out["config"] = {"dim": D, "depth": depth, "batch": B, "seq": T,
                     "remat": (args.lm_remat_mode if args.lm_remat
                               else False),
                     "head_chunk": args.lm_head_chunk,
                     "opt_state": args.lm_opt_state}
    opt_leaves = [x for x in jax.tree.leaves(state[1])
                  if hasattr(x, "dtype")]
    out["opt_state_bytes"] = int(sum(
        x.size * x.dtype.itemsize for x in opt_leaves))
    if args.lm_kv_heads:
        out["kv_heads"] = args.lm_kv_heads
    if args.lm_rope:
        out["rope"] = True
    # HONEST dual accounting: mfu_vs_bf16_peak above is MODEL-FLOPs MFU
    # (the number people compare across systems); remat/chunked-CE
    # recompute is real chip work that the model number hides, so also
    # report the executed estimate and the hardware MFU it implies —
    # without it, "remat costs nothing" would be silently claimable.
    extra = 0.0
    if remat is True:
        extra += (m_mat + m_attn) / 3.0      # whole forward again
    elif remat == "attn":
        extra += m_mat / 3.0                 # forward minus attention
    elif remat == "hybrid":
        extra += m_mat / 9.0            # qkv + attn out-proj: 8/24 of fwd
    elif remat == "hybrid_qkv":
        extra += m_mat / 36.0           # attn out-proj only: 2/24 of fwd
    # "dots" recomputes only elementwise: ~0 extra matmul FLOPs
    if args.lm_head_chunk:
        # backward re-runs the tied-head matmul once per chunk
        extra += 2.0 * vocab * D * tokens
    if extra > 0:
        hw = (flops_step + K * extra) / dt / 1e12 / n_chips
        out["tflops_hw_per_chip"] = round(hw, 6)
        out["mfu_hw_vs_bf16_peak"] = (round(hw * 1e12 / peak, 4)
                                      if peak else None)
        out["recompute_factor"] = round(1.0 + extra / (m_mat + m_attn), 4)
    return out


def bench_wd(args, n_chips, peak):
    """Wide&Deep with a 2^22-slot embedding table (BASELINE config 4's
    scale direction): the memory-bound end — gathers/scatter-adds over a
    268 MB table dominate, so MFU is expected to be tiny; the honest
    numbers are rows/sec and achieved TFLOP/s."""
    import jax
    import jax.numpy as jnp

    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.data import synthetic
    from minips_tpu.apps.wide_deep_example import build

    cfg = Config(
        table=TableConfig(name="ctr", kind="sparse", consistency="bsp",
                          updater="adagrad", lr=0.05, dim=8,
                          num_slots=args.wd_slots),
        train=TrainConfig(batch_size=args.batch, num_iters=1),
    )
    ps, _tables = build(cfg, use_fm=True, compute_dtype=jnp.bfloat16)
    batches = [ps.shard_batch(synthetic.criteo_like(args.batch, seed=s))
               for s in (0, 1)]
    K, dt, state = _ps_chain_timed(ps, batches, args)
    flops_step = args.batch * K * _mlp_flops_per_sample(
        (13 + 26 * 8, 256, 128, 1))
    out = _suite_result(K * args.batch, dt, n_chips, flops_step, peak)
    out["emb_slots"] = args.wd_slots
    if n_chips > 1:
        # collective traffic of ONE fused step: must be batch-sized, never
        # table-sized (VERDICT task 6; tests/test_sharded_traffic.py pins
        # the same invariant on the raw SparseTable ops). `state` is the
        # post-timing live state from the helper.
        from minips_tpu.utils.comm_analysis import traffic_report
        rep = traffic_report(
            jax.jit(ps.step_fn_pure).lower(state, batches[0]).compile())
        out["step_collective_bytes"] = rep["total_bytes"]
    return out


def bench_mf(args, n_chips, peak):
    """Matrix factorization (BASELINE config 3's workload shape —
    MovieLens-scale id spaces): per-key pull/push of user and item factor
    rows through two SparseTables, the pure embedding-bound end of the
    suite family. The honest numbers are ratings/sec and achieved
    TFLOP/s; MFU is expected to be tiny (dot products, no matmul)."""
    import jax.numpy as jnp
    import numpy as np

    from minips_tpu.models import mf as mf_model
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    mesh = make_mesh()
    B, dim = args.batch, args.mf_dim
    users, items = args.mf_users, args.mf_items
    # sgd, matching the app's default updater — under sgd grad_scale=B
    # below genuinely restores per-sample server-add magnitude (adagrad
    # rows would be invariant to a constant scale)
    user_t = SparseTable(users, dim, mesh, name="user",
                         updater="sgd", lr=0.05, init_scale=0.1,
                         seed=1)
    item_t = SparseTable(items, dim, mesh, name="item",
                         updater="sgd", lr=0.05, init_scale=0.1,
                         seed=2)

    def loss_fn(dense_params, rows, batch):
        return mf_model.loss(rows["user"], rows["item"], batch["rating"],
                             mu=3.5, reg=0.02)

    # grad_scale=B: per-sample server-add magnitude (see mf_example)
    ps = PSTrainStep(loss_fn, sparse={"user": user_t, "item": item_t},
                     key_fns={"user": lambda b: b["user"],
                              "item": lambda b: b["item"]},
                     grad_scale=B)

    def batch(seed):
        r = np.random.default_rng(seed)
        return ps.shard_batch({
            "user": jnp.asarray(r.integers(0, users, size=B)),
            "item": jnp.asarray(r.integers(0, items, size=B)),
            "rating": jnp.asarray(
                r.integers(1, 6, size=B).astype(np.float32))})

    K, dt, _ = _ps_chain_timed(ps, [batch(0), batch(1)], args)
    # fwd = the u·i dot (2·dim FLOPs/sample); bwd ≈ 2x fwd
    flops_step = K * B * 3.0 * 2.0 * dim
    out = _suite_result(K * B, dt, n_chips, flops_step, peak)
    out["factor_dim"] = dim
    out["id_space"] = [users, items]
    return out


def bench_w2v(args, n_chips, peak):
    """Word2vec SGNS (BASELINE config 5's workload shape — enwiki-scale
    vocab): center/context/negative rows through two SparseTables with
    host-side alias-table negative sampling baked into the rotated
    batches, per-pair update magnitude via grad_scale. pairs/sec is the
    headline; like mf this is gather/scatter-bound."""
    import jax.numpy as jnp
    import numpy as np

    from minips_tpu.models import word2vec as w2v
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    mesh = make_mesh()
    B, dim, vocab, neg = (args.batch, args.w2v_dim, args.w2v_vocab,
                          args.w2v_neg)
    # sgd per the app's default — see the bench_mf updater note
    in_t = SparseTable(vocab, dim, mesh, name="in", updater="sgd",
                       lr=0.05, init_scale=0.01, seed=1)
    out_t = SparseTable(vocab, dim, mesh, name="out", updater="sgd",
                        lr=0.05, init_scale=0.0, seed=2)

    def loss_fn(dense_params, rows, batch):
        return w2v.sgns_loss(rows["in"], rows["out"][:, 0],
                             rows["out"][:, 1:])

    ps = PSTrainStep(
        loss_fn, sparse={"in": in_t, "out": out_t},
        key_fns={"in": lambda b: b["center"],
                 "out": lambda b: jnp.concatenate(
                     [b["pos"][:, None], b["neg"]], axis=1)},
        grad_scale=B)

    # zipf-shaped unigram counts -> the classic 0.75-power alias table;
    # negatives are drawn per rotated batch on the host, exactly like
    # the app's batch generator (word2vec_example._batch_gen)
    counts = 1.0 / np.arange(1, vocab + 1)
    sampler = w2v.UnigramSampler(np.asarray(counts), power=0.75, seed=0)

    def batch(seed):
        r = np.random.default_rng(seed)
        return ps.shard_batch({
            "center": jnp.asarray(r.integers(0, vocab, size=B)),
            "pos": jnp.asarray(r.integers(0, vocab, size=B)),
            "neg": jnp.asarray(sampler.sample((B, neg)))})

    K, dt, _ = _ps_chain_timed(ps, [batch(0), batch(1)], args)
    # fwd = (1 pos + neg) center·context dots of 2·dim each; bwd ≈ 2x
    flops_step = K * B * 3.0 * 2.0 * dim * (1 + neg)
    out = _suite_result(K * B, dt, n_chips, flops_step, peak)
    out["vocab"] = vocab
    out["dim"] = dim
    out["negatives"] = neg
    return out


def bench_e2e(args, n_chips):
    """End-to-end: Criteo-format TSV on disk → (native) parser → prefetch
    thread → fused LR+MLP steps. samples/sec INCLUDING IO — the number the
    microbench suites deliberately exclude (BASELINE.json:2 names the
    workload 'on Criteo', not 'on resident arrays')."""
    import os
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np

    from minips_tpu.data import synthetic
    from minips_tpu.data.criteo import (log_transform,
                                        stream_criteo_batches, write_criteo)
    from minips_tpu.data.loader import prefetch_to_device
    from minips_tpu.models import lr as lr_model
    from minips_tpu.models import mlp as mlp_model
    from minips_tpu.models import wide_deep as wd_model
    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.dense import DenseTable
    from minips_tpu.tables.sparse import SparseTable
    from minips_tpu.train.ps_step import PSTrainStep

    rows = args.e2e_rows
    d = synthetic.criteo_like(rows, seed=3)
    fd, path = tempfile.mkstemp(suffix=".tsv")
    os.close(fd)
    try:
        dense_raw = np.maximum(
            (d["dense"] * 10).astype(np.int64), 0)
        write_criteo(path, d["y"], dense_raw, d["cat"])

        mesh = make_mesh()
        wide_t = SparseTable(1 << 18, 1, mesh, name="wide",
                             updater="adagrad", lr=0.05, init_scale=0.0,
                             salt=1)
        lin_t = DenseTable(lr_model.init(13), mesh, name="lin",
                           updater="adagrad", lr=0.05)
        emb_t = SparseTable(1 << 18, 8, mesh, name="emb",
                            updater="adagrad", lr=0.05, salt=2)
        deep_t = DenseTable(
            wd_model.init_deep(jax.random.PRNGKey(0), 26, 8, 13,
                               hidden=(256, 128)),
            mesh, name="deep", updater="adam", lr=1e-3)

        def lr_loss(dp, rws, b):
            logits = (jnp.sum(rws["wide"][..., 0], axis=-1)
                      + lr_model.logits_dense(dp, b["dense"]))
            return lr_model.bce_with_logits(logits, b["y"])

        def mlp_loss(dp, rws, b):
            bsz = rws["emb"].shape[0]
            x = jnp.concatenate([b["dense"],
                                 rws["emb"].reshape(bsz, -1)], axis=-1)
            return lr_model.bce_with_logits(
                mlp_model.apply(dp, x)[:, 0], b["y"])

        lr_step = PSTrainStep(lr_loss, dense=lin_t,
                              sparse={"wide": wide_t},
                              key_fns={"wide": lambda b: b["cat"]})
        mlp_step = PSTrainStep(mlp_loss, dense=deep_t,
                               sparse={"emb": emb_t},
                               key_fns={"emb": lambda b: b["cat"]})

        B = args.e2e_batch
        # compile warmup OUTSIDE the timed region (compile is once-ever,
        # the steady-state pipeline is the thing being measured)
        warm = synthetic.criteo_like(B, seed=4)
        wb = lr_step.shard_batch(warm)
        lr_step(wb)
        loss = mlp_step(wb)
        jax.block_until_ready(loss)

        t0 = time.perf_counter()
        try:  # flag which parser actually RAN inside the stream
            from minips_tpu.data.native import native_mem_available
            native = native_mem_available()
        except ImportError:
            native = False

        def xform(d):  # runs on the producer thread, off the train thread
            return {"dense": log_transform(d["dense"], d["dense_mask"]),
                    "cat": d["cat"], "y": d["y"]}

        # streaming ingestion: blocks parse on a producer thread WHILE
        # prior batches train — parse overlaps compute, working set is one
        # block, never the file (the Criteo-1TB posture, SURVEY.md §7.4.4)
        stream_stats: dict = {}
        batches = stream_criteo_batches(path, B, chunk_bytes=4 << 20,
                                        transform=xform, stats=stream_stats)
        n_done = 0
        loss = None
        for batch in prefetch_to_device(
                batches, lr_step.shard_batch, depth=2):
            lr_step(batch)
            loss = mlp_step(batch)
            n_done += B
            if n_done >= rows:
                break
        jax.block_until_ready(loss)
        dt = time.perf_counter() - t0
    finally:
        os.unlink(path)
    return {"samples_per_sec_per_chip": round(n_done / dt / n_chips, 1),
            "rows": n_done, "native_parser": native,
            # no-silent-caps: rows short of a final batch (0 when the break
            # above fires before EOF — the stream was abandoned, not short)
            "dropped_rows": stream_stats.get("dropped_rows", 0),
            "includes_io": True}


def _emit(suites, on_tpu, device_note, device_kind, peak_tflops,
          failed=()) -> None:
    """The ONE place the headline metric line is assembled (single-suite
    and --suite all runs must agree on labels, the north-star constant,
    and the off-TPU vs_baseline refusal)."""
    unit = "samples/sec/chip"
    if "lrmlp" in suites:
        sps = suites["lrmlp"]["samples_per_sec_per_chip"]
        # north-star: 1M samples/sec aggregate on v4-32 = 16 chips
        metric = ("samples/sec/chip (LR+MLP on Criteo-shaped, fused SPMD, "
                  "chained-scan median)")
        vs = round(sps / (1_000_000 / 16), 4) if on_tpu else None
    else:
        only = next(iter(suites))
        sps = suites[only].get("samples_per_sec_per_chip")
        metric = f"samples/sec/chip ({only} suite — NOT the primary " \
                 "LR+MLP metric)"
        if sps is None:  # ps suites: control-plane rates, not chip rates
            sps = suites[only]["rows_per_sec_per_process"]
            unit = "rows/sec/process"
            metric = suites[only].get(
                "metric_note",
                f"rows/sec/process ({only} suite, CPU loopback "
                "control plane — NOT the primary LR+MLP metric)")
        vs = None
    out = {
        "metric": metric,
        "value": sps,
        "unit": unit,
        "vs_baseline": vs,
        "device": device_note,
        "device_kind": device_kind,
        "bf16_peak_tflops": peak_tflops,
        "suites": suites,
    }
    if failed:
        out["failed_suites"] = sorted(failed)
    print(json.dumps(out))


def bench_ps(args) -> dict:
    """Sharded multi-process PS throughput (train/sharded_ps.py) over
    loopback — rows/sec and wire-bytes/sec of the pull→push cycle with
    model math stripped out (apps/sharded_ps_bench.py). This measures the
    CONTROL-PLANE data path (routing + serialization + bus + server
    updater) on host CPUs; it is deliberately NOT a chip rate and never
    feeds vs_baseline. bench_sharded_ps.py publishes the full curve
    (world sizes 1–4, zmq vs native mailbox, sparse vs dense range)."""
    from bench_sharded_ps import _run  # ONE spawn/aggregate protocol

    out = _run(3, "sparse", args.ps_iters, max(2, args.ps_iters // 6),
               "zmq")
    out.update(nprocs=3, bus="zmq", path="sparse",
               compute="cpu-loopback-control-plane")
    return out


def bench_ps_tpu(args) -> dict:
    """The PS topology the north star actually describes (VERDICT r3
    next #5): sharded host PS + workers whose grad math is a REAL jitted
    step, so the row rate includes pull → device → MLP fwd+bwd → host →
    push overlapped with the wire. A chip belongs to one process: rank 0
    keeps the default backend, peers are pinned to the CPU, and the label
    is what the ranks themselves echo (``worker_compute``). Without
    ``--cpu`` a rank 0 that found no TPU is an error, not a label."""
    from bench_sharded_ps import _run

    out = _run(3, "sparse", args.ps_iters, max(2, args.ps_iters // 6),
               "zmq", compute="jit", force_cpu=args.cpu,
               hidden=args.ps_hidden)
    ran_on = out["worker_compute"]
    if not args.cpu and "jit(tpu)" not in ran_on:
        raise SystemExit(f"bench: ps_tpu needs a TPU for rank 0; ranks ran "
                         f"on {ran_on} (use --cpu for the harness check)")
    out.update(nprocs=3, bus="zmq", path="sparse",
               metric_note="rows/sec/process (sharded PS + jitted worker"
                           f" compute; ranks ran on {', '.join(ran_on)})")
    return out


def _run_all(args) -> int:
    """Parent for ``--suite all``: fork one child per suite (the parent
    never initializes JAX — see the call site), merge their JSON, publish
    one line. A child that finds no TPU (``_NO_TPU_RC``) ends the whole
    run at once with the same code."""
    import os
    import subprocess

    suites = {}
    failed = []
    device_note = None
    device_kind = None
    peak_tflops = None
    for s in ("lrmlp", "lm", "wd", "mf", "w2v", "e2e", "ps", "ps_tpu"):
        argv = [sys.executable, os.path.abspath(__file__),
                "--suite", s,
                "--batch", str(args.batch),
                "--chain", str(args.chain),
                "--reps", str(args.reps),
                "--lm-batch", str(args.lm_batch),
                "--lm-seq", str(args.lm_seq),
                "--lm-dim", str(args.lm_dim),
                "--lm-depth", str(args.lm_depth),
                ("--lm-remat" if args.lm_remat else "--no-lm-remat"),
                *(["--lm-kv-heads", str(args.lm_kv_heads)]
                  if args.lm_kv_heads else []),
                *(["--lm-rope"] if args.lm_rope else []),
                "--lm-remat-mode", args.lm_remat_mode,
                "--lm-head-chunk", str(args.lm_head_chunk),
                "--lm-opt-state", args.lm_opt_state,
                "--wd-slots", str(args.wd_slots),
                "--mf-users", str(args.mf_users),
                "--mf-items", str(args.mf_items),
                "--mf-dim", str(args.mf_dim),
                "--w2v-vocab", str(args.w2v_vocab),
                "--w2v-dim", str(args.w2v_dim),
                "--w2v-neg", str(args.w2v_neg),
                "--e2e-rows", str(args.e2e_rows),
                "--e2e-batch", str(args.e2e_batch),
                "--ps-iters", str(args.ps_iters),
                "--ps-hidden", str(args.ps_hidden)]
        if args.cpu:
            argv.append("--cpu")
        proc = subprocess.run(argv, capture_output=True, text=True)
        if proc.returncode == _NO_TPU_RC:
            sys.stderr.write(proc.stderr[-2000:])
            return _NO_TPU_RC
        lines = [ln for ln in proc.stdout.splitlines()
                 if ln.startswith("{")]
        if proc.returncode != 0 or not lines:
            print(f"bench: suite {s} failed (rc={proc.returncode}):\n"
                  f"{proc.stderr[-2000:]}", file=sys.stderr)
            failed.append(s)
            continue
        child = json.loads(lines[-1])
        suites.update(child.get("suites", {}))
        if s in ("ps", "ps_tpu"):
            continue  # PS-topology suites label themselves
        if device_note is None:  # every chip suite ran on the same device
            device_note = child.get("device", "?")
            device_kind = child.get("device_kind")
            peak_tflops = child.get("bf16_peak_tflops")
    if not suites:
        print("bench: every suite failed", file=sys.stderr)
        return 1
    _emit(suites, device_note == "tpu", device_note, device_kind,
          peak_tflops, failed)
    # partial results must not read as a clean run to automation
    return 1 if failed else 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cpu", action="store_true",
                    help="force CPU (8 fake devices) for development")
    ap.add_argument("--suite", default="all",
                    choices=["all", "lrmlp", "lm", "wd", "mf", "w2v",
                             "e2e", "ps", "ps_tpu"])
    ap.add_argument("--ps-iters", type=int, default=40,
                    help="pull/push cycles per rank in the ps suite")
    ap.add_argument("--ps-hidden", type=int, default=256,
                    help="ps_tpu suite: hidden width of the jitted "
                         "worker MLP (the MXU work per cycle)")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of one steady-state"
                         " rep into DIR and attach the top-op table to the"
                         " suite result (single-suite runs only; --suite "
                         "all forks children and ignores it)")
    # defaults = the measured sweet spots on the v5-lite here (2026-07-30
    # sweep: 16k->65k batch buys +13% lrmlp and +11% wd; lm saturates MFU
    # at micro-batch 64 and regresses at 128)
    ap.add_argument("--batch", type=int, default=65536)
    ap.add_argument("--chain", type=int, default=20,
                    help="steps folded into one dispatch (lax.scan)")
    ap.add_argument("--reps", type=int, default=5,
                    help="timed chained calls; median reported")
    # lm defaults = the measured 2026-07-31 frontier winner (43.5% model
    # MFU on the v5 lite: d=2048x8, B=16, remat=dots, chunked head 128 —
    # BASELINE.md / sweep_lm.sh); the r2 base config is reproducible with
    # --lm-dim 512 --lm-depth 4 --lm-batch 64 --no-lm-remat
    # --lm-head-chunk 0. CPU validation runs clamp the shapes anyway.
    ap.add_argument("--lm-batch", type=int, default=16)
    ap.add_argument("--lm-seq", type=int, default=1024)
    ap.add_argument("--lm-dim", type=int, default=2048)
    ap.add_argument("--lm-depth", type=int, default=8)
    ap.add_argument("--lm-kv-heads", type=int, default=None,
                    help="grouped-query attention KV heads (1 = MQA; "
                         "default = dim/64 q-heads, classic MHA) — "
                         "shrinks KV projection + activations")
    ap.add_argument("--lm-rope", action="store_true",
                    help="rotary position embeddings instead of the "
                         "learned table")
    ap.add_argument("--lm-remat", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="recompute block activations in backward "
                         "(fits larger --lm-dim/--lm-depth in HBM)")
    ap.add_argument("--lm-remat-mode", default="dots",
                    choices=["full", "attn", "dots", "hybrid",
                             "hybrid_qkv"],
                    help="with --lm-remat: full = recompute whole blocks; "
                         "attn = save attention outputs (backward never "
                         "re-runs attention); dots = save matmul outputs "
                         "(recompute only elementwise)")
    ap.add_argument("--lm-opt-state", default="f32",
                    choices=["f32", "bf16", "int8"],
                    help="adam moment storage (tables/updaters.py): "
                         "bf16 halves, int8 (blockwise) quarters the "
                         "optimizer-state HBM that bounds the frontier")
    ap.add_argument("--lm-head-chunk", type=int, default=128,
                    help="sequence-chunked tied head + CE: the [B,T,vocab]"
                         " logits never materialize (models/transformer.py"
                         " nll_chunked); 0 = plain head")
    ap.add_argument("--wd-slots", type=int, default=1 << 22)
    # mf: ML-20M-scale id spaces (138k users / 27k movies, next pow2)
    ap.add_argument("--mf-users", type=int, default=1 << 18)
    ap.add_argument("--mf-items", type=int, default=1 << 15)
    ap.add_argument("--mf-dim", type=int, default=32)
    # w2v: enwiki-scale vocab, classic SGNS hyperparams
    ap.add_argument("--w2v-vocab", type=int, default=1 << 20)
    ap.add_argument("--w2v-dim", type=int, default=128)
    ap.add_argument("--w2v-neg", type=int, default=5)
    # 512k rows ≈ 0.7s of steady-state pipeline at the measured rate — a
    # 131k-row run finishes in ~0.2s, short enough for run-to-run jitter
    # to dominate the reading
    ap.add_argument("--e2e-rows", type=int, default=524288)
    ap.add_argument("--e2e-batch", type=int, default=16384,
                    help="e2e streams this batch size (decoupled from "
                         "--batch so the pipeline sees many batches)")
    args = ap.parse_args()
    if args.chain < 1 or args.reps < 1:
        ap.error("--chain and --reps must be >= 1")
    if args.lm_dim % 64 or args.lm_dim < 64:
        # heads = lm_dim/64 (64-dim heads, MXU-shaped); a non-multiple
        # would derive a head count that doesn't divide the model dim
        ap.error("--lm-dim must be a positive multiple of 64")
    if args.lm_head_chunk and args.lm_seq % args.lm_head_chunk:
        # the chunked head scans whole chunks; with a default chunk of
        # 128 an odd --lm-seq must not crash the suite — drop to the
        # plain head and say so
        print(f"bench: --lm-seq {args.lm_seq} not divisible by "
              f"--lm-head-chunk {args.lm_head_chunk}; using the plain "
              "head (--lm-head-chunk 0)", file=sys.stderr)
        args.lm_head_chunk = 0

    if args.profile and args.suite not in ("lrmlp", "lm", "wd", "mf",
                                           "w2v"):
        # only the chained-scan suites run under _chain_timed and can
        # capture; ps is jax-free, e2e times a streaming loop, and "all"
        # forks children without forwarding the flag
        print(f"bench: --profile is ignored for --suite {args.suite} "
              "(profilable: lrmlp, lm, wd, mf, w2v)", file=sys.stderr)
        args.profile = None

    if args.cpu:
        # the explicit harness check: 8 fake CPU devices, stated in the
        # environment BEFORE jax is imported so every child inherits it
        import os
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=8")
        # ... at shrunk shapes: this path exists to validate the harness,
        # never to publish numbers (vs_baseline stays null)
        args.batch = min(args.batch, 2048)
        args.e2e_batch = min(args.e2e_batch, 2048)
        args.lm_batch = min(args.lm_batch, 8)
        args.wd_slots = min(args.wd_slots, 1 << 18)
        args.mf_users = min(args.mf_users, 1 << 14)
        args.mf_items = min(args.mf_items, 1 << 12)
        args.w2v_vocab = min(args.w2v_vocab, 1 << 14)
        args.e2e_rows = min(args.e2e_rows, 16384)
        args.lm_seq = min(args.lm_seq, 256)
        args.lm_dim = min(args.lm_dim, 512)
        args.lm_depth = min(args.lm_depth, 4)
        args.chain = min(args.chain, 4)
        args.reps = min(args.reps, 2)

    if args.suite == "ps":
        # control-plane suite: loopback subprocesses pinned to the CPU,
        # no chip, no jax in this process
        _emit({"ps": bench_ps(args)}, False, "cpu-loopback(control-plane)",
              None, None)
        return 0

    if args.suite == "ps_tpu":
        # the PS wire + jitted worker compute row: rank 0 of the worker
        # job takes the chip; this parent never initializes jax
        _emit({"ps_tpu": bench_ps_tpu(args)}, False,
              ("cpu-loopback" if args.cpu
               else "mixed(rank0-tpu,peers-cpu)"), None, None)
        return 0

    if args.suite == "all":
        # each suite in a FRESH child process, the parent NEVER touching
        # JAX: (a) measured in-process interference — later suites read up
        # to 4x slow after earlier suites' compiled programs/buffers
        # accumulate (e2e isolated 727-872k vs 202-237k run last
        # in-process on the same chip); (b) a chip belongs to one process,
        # so a parent holding it would leave every child without one.
        return _run_all(args)

    import jax

    from minips_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    device = jax.devices()[0]
    on_tpu = device.platform == "tpu"
    if not on_tpu and not args.cpu:
        print(f"bench: no TPU (JAX found platform {device.platform!r}); "
              "nothing is measured off the chip — pass --cpu for the "
              "harness check", file=sys.stderr)
        return _NO_TPU_RC
    n_chips = len(jax.devices())
    peak = _peak_for(device) if on_tpu else None

    global _PROFILE_DIR
    _PROFILE_DIR = args.profile
    profile_t0 = time.time()

    suites = {}
    want = [args.suite]
    if "lrmlp" in want:
        suites["lrmlp"] = bench_lrmlp(args, n_chips, peak)
    if "lm" in want:
        suites["lm"] = bench_lm(args, n_chips, peak)
    if "wd" in want:
        suites["wd"] = bench_wd(args, n_chips, peak)
    if "mf" in want:
        suites["mf"] = bench_mf(args, n_chips, peak)
    if "w2v" in want:
        suites["w2v"] = bench_w2v(args, n_chips, peak)
    if "e2e" in want:
        suites["e2e"] = bench_e2e(args, n_chips)

    if _PROFILE_DIR and suites:
        import os

        from minips_tpu.utils.trace_analysis import (latest_xplane,
                                                     summarize)
        # one suite per invocation when profiling; the table lands on it.
        # Freshness-gate: a pre-existing trace in a reused dir must not
        # be misattributed to this run as its profile.
        newest = latest_xplane(_PROFILE_DIR)
        if newest is not None and os.path.getmtime(newest) >= profile_t0:
            prof = summarize(_PROFILE_DIR, top=12)
        else:
            prof = {"error": "no trace captured during this run "
                             "(profiler unavailable on this backend?)"}
        suites[next(iter(suites))]["profile"] = prof

    # only the lrmlp suite measures the BASELINE metric; a run that skipped
    # it must not label another suite's rate as LR+MLP or ratio it against
    # the samples/sec north-star (that would be weak-#7 all over again);
    # off-TPU numbers are not comparable to the TPU target: vs stays null
    _emit(suites, on_tpu, device.platform, device.device_kind,
          (peak / 1e12) if peak else None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
