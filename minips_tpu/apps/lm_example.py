"""lm_example — decoder-only LM across the framework's parallel layouts.

Beyond-parity app (the reference has no attention models, SURVEY.md §2.2):
demonstrates the long-context/model-parallel paths end-to-end. Layouts:

- ``--layout dp``  (default): batch sharded over the mesh ``data`` axis,
  full attention per shard — ordinary data parallelism through the
  DenseTable fused PS step.
- ``--layout sp``: BATCH REPLICATED, SEQUENCE sharded over the same axis —
  causal ring attention (K/V rotate over ppermute), positional embeddings
  offset per shard. Identical numerics to dp (tests prove grad parity);
  per-device activation memory scales as T/N, so sequences that cannot fit
  one device train anyway. Also a DenseTable fused step.
- ``--layout tp``: 2D mesh (data x model) — batch over ``data``, block
  weights Megatron-sharded over ``model`` (``--tp`` ranks); optimizer
  state sharded like the weights (weight-update sharding, the PS server
  role distributed per-tensor instead of per-key-range).
- ``--layout pp``: 2D mesh — batch over ``data``, layers GPipe-pipelined
  over ``model`` (``--tp`` stages, ``--microbatches`` in flight).
- ``--layout ep``: MoE-LM — every block's FFN is a top-k-routed expert
  layer with the expert stacks sharded over the mesh; tokens reach their
  experts via two all_to_alls per block (``--experts``, ``--k_top``,
  ``--capacity``).

``--model_config FILE`` (dp layout) takes the model from a configuration
file of published keys instead of the flags, by the file's ``model_type``
(``CONFIG_MODELS``): the ZAYA1-shaped decoder of ``models/zaya.py``
(compressed convolutional attention, a dropless top-1 expert layer that
is told which experts it holds), the latent-attention expert decoder of
``models/mla_moe.py`` (MLA, sigmoid top-k experts beside a shared one, a
leading dense layer, an untied head, a multi-token-prediction module)
or the hybrid of gated delta-rule linear-attention and full-attention
layers of ``models/olmo_hybrid.py``, trained through the same
``DenseTable.make_step``;
``bench/configs/zaya1-8b.json``, ``bench/configs/joyai-llm-flash.json``
and ``bench/configs/olmo-hybrid-7b.json`` are such files, and the benchmark's adapters call :func:`model_dp_step`
as ``run`` does.

Usage: python -m minips_tpu.apps.lm_example --num_iters 200 --layout sp
       python -m minips_tpu.apps.lm_example --layout tp --tp 2
       python -m minips_tpu.apps.lm_example --seq_len 8192 --batch_size 4 \
           --model_config bench/configs/zaya1-8b.json
       python -m minips_tpu.apps.lm_example --seq_len 8192 --batch_size 2 \
           --model_config bench/configs/joyai-llm-flash.json
       python -m minips_tpu.apps.lm_example --seq_len 8192 --batch_size 4 \
           --model_config bench/configs/olmo-hybrid-7b.json     # 4 chips
"""

from __future__ import annotations

import functools
import inspect
import json

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.apps.common import app_main, log_tables_built
from minips_tpu.core.config import Config, TableConfig, TrainConfig
from minips_tpu.data import synthetic
from minips_tpu.data.loader import BatchIterator
from minips_tpu.models import mla_moe, olmo_hybrid
from minips_tpu.models import transformer as tfm
from minips_tpu.models import zaya
from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh
from minips_tpu.tables.dense import DenseTable
from minips_tpu.train.loop import TrainLoop
from minips_tpu.utils import profiling as prof

DEFAULT = Config(
    table=TableConfig(name="lm", kind="dense", updater="adam", lr=3e-3),
    train=TrainConfig(batch_size=32, num_iters=200),
)

MODEL = dict(vocab=256, dim=64, heads=4, depth=2, max_len=1024)
# --model_config: the file's ``model_type`` names the module, each with
# ``from_config``, ``init``, ``grad_fn`` and an observer: ``routing_stats``
# with ``centred_bias`` where the model has routers and carries their
# balancing bias from step to step, ``observe`` where it carries nothing;
# a file without the key is ZAYA's, as before the key was read
CONFIG_MODELS = {"zaya": zaya, mla_moe.MODEL_TYPE: mla_moe,
                 olmo_hybrid.MODEL_TYPE: olmo_hybrid}


def _flags(parser):
    parser.add_argument("--layout", default="dp",
                        choices=["dp", "sp", "tp", "pp", "ep"],
                        help="dp: batch sharded; sp: sequence sharded "
                             "(ring attention); tp: Megatron tensor "
                             "parallel; pp: GPipe pipeline; ep: MoE-LM "
                             "with experts sharded over the mesh")
    parser.add_argument("--experts", type=int, default=8,
                        help="ep layout: number of experts (must divide "
                             "by the device count)")
    parser.add_argument("--k_top", type=int, default=1,
                        help="ep layout: experts per token (1=Switch, "
                             "2=GShard)")
    parser.add_argument("--capacity", type=int, default=0,
                        help="ep layout: slots per expert per source "
                             "device (0 = 2x the even share)")
    parser.add_argument("--seq_len", type=int, default=128)
    parser.add_argument("--model_config", default=None,
                        help="dp layout: a configuration file of published "
                             "keys (model_type, hidden_size, ...) names "
                             "the model, its attention, head chunk and "
                             "worker precision, in place of "
                             "--dim/--depth/--heads/--attn/--dtype "
                             "(models/zaya.py, models/mla_moe.py)")
    parser.add_argument("--tp", type=int, default=2,
                        help="model-axis size for tp/pp layouts")
    parser.add_argument("--microbatches", type=int, default=4,
                        help="pp layout: microbatches in flight")
    parser.add_argument("--data_file", default=None,
                        help="train on this file's bytes (byte-level LM, "
                             "vocab 256) instead of synthetic data")
    # --checkpoint_dir / --checkpoint_every come from add_config_flags
    parser.add_argument("--resume", action="store_true",
                        help="dp/sp: restore newest checkpoint before "
                             "training")
    parser.add_argument("--head_chunk", type=int, default=0,
                        help="sequence-chunked tied head + cross-entropy "
                             "(the [B,T,vocab] logits never materialize; "
                             "each chunk's gradients are formed while its "
                             "logits are live, so no product runs twice); "
                             "0 = plain head. dp layout only")
    parser.add_argument("--remat_mode", default="full",
                        choices=["full", "attn", "dots", "hybrid",
                                 "hybrid_qkv"],
                        help="with --remat: full = recompute whole "
                             "blocks; attn = save attention outputs; "
                             "dots = save matmul outputs; every mode but "
                             "full keeps the flash forward kernel's out "
                             "and lse (see transformer._remat_policy)")
    parser.add_argument("--remat", action="store_true",
                        help="recompute block activations in backward "
                             "(jax.checkpoint): depth stops driving peak "
                             "HBM — fits larger --dim/--depth (dp layout)")
    parser.add_argument("--attn", default="reference",
                        choices=["reference", "flash", "a2a",
                                 "a2a_flash"],
                        help="dp/sp layout attention: full-scores XLA or "
                             "the fused O(T)-memory flash kernels "
                             "(ops/flash_attention.py; on sp this is ring "
                             "flash attention) — the win is at long "
                             "--seq_len, where full scores thrash or OOM "
                             "HBM. a2a / a2a_flash (sp only): all-to-all "
                             "sequence parallelism (Ulysses-style, "
                             "parallel/a2a_attention.py) — two "
                             "collectives per attention and a fully "
                             "LOCAL kernel; needs heads %% devices == 0")
    parser.add_argument("--accum", type=int, default=1,
                        help="dp/sp: gradient-accumulation microbatches "
                             "per step (effective batch = batch_size, "
                             "activation memory = batch_size/accum)")
    parser.add_argument("--dim", type=int, default=None,
                        help=f"model width (default {MODEL['dim']})")
    parser.add_argument("--depth", type=int, default=None,
                        help=f"transformer blocks (default {MODEL['depth']})")
    parser.add_argument("--heads", type=int, default=None,
                        help=f"attention heads (default {MODEL['heads']})")
    parser.add_argument("--kv_heads", type=int, default=None,
                        help="grouped-query attention: KV heads shared by "
                             "groups of q-heads (1 = MQA; default = "
                             "--heads, classic MHA). Shrinks KV "
                             "projection + activations + sp ring wire by "
                             "heads/kv_heads")
    parser.add_argument("--rope", action="store_true",
                        help="rotary position embeddings instead of the "
                             "learned table: no pos_emb params, no "
                             "max_len sequence cap (--max_len ignored)")
    parser.add_argument("--clip_norm", type=float, default=0.0,
                        help="global-norm gradient clipping (0 = off); "
                             "any --updater")
    parser.add_argument("--weight_decay", type=float, default=None,
                        help="with --updater adamw (default 0.01 there): "
                             "decoupled weight decay on matrices only "
                             "(LN gains/biases never decay — "
                             "transformer.decay_mask); refused with "
                             "other updaters")
    parser.add_argument("--warmup_steps", type=int, default=0,
                        help="> 0: linear warmup then cosine decay to "
                             "10%% of --lr over --num_iters (an optax "
                             "schedule fed straight into the updater)")
    parser.add_argument("--generate", type=int, default=0,
                        help="after training, decode this many tokens "
                             "from a prompt of the training stream via "
                             "the KV cache (models/decode.py); greedy "
                             "unless --temperature")
    parser.add_argument("--temperature", type=float, default=0.0,
                        help="sampling temperature for --generate "
                             "(0 = greedy)")
    parser.add_argument("--dropout", type=float, default=0.0,
                        help="GPT-style embedding + residual dropout "
                             "(train-time; per-step keys ride the batch "
                             "into the pure fused step). --layout dp "
                             "only; incompatible with --accum")
    parser.add_argument("--dtype", default="float32",
                        choices=["float32", "bfloat16"],
                        help="dp/sp: worker-math precision (bfloat16 = "
                             "MXU-native mixed precision; master weights "
                             "and the optimizer stay float32)")
    parser.add_argument("--comm", default="float32",
                        choices=["float32", "bfloat16", "int8"],
                        help="dp/sp: wire format of the pull/push "
                             "collectives (EQuARX-style quantization, "
                             "2-4x fewer bytes, f32 accumulation)")
    parser.add_argument("--max_len", type=int, default=None,
                        help="positional-embedding capacity (default: "
                             f"{MODEL['max_len']}, auto-grown to "
                             "--seq_len)")


def _model_cfg(args, seq_len: int) -> dict:
    """MODEL with --dim/--depth/--heads overrides and positional capacity
    covering --max_len / --seq_len."""
    m = {**MODEL}
    for k in ("dim", "depth", "heads"):
        v = getattr(args, k, None)
        if v is not None:
            m[k] = v
    if m["heads"] < 1 or m["dim"] % m["heads"]:
        raise SystemExit(f"--dim {m['dim']} must divide by --heads "
                         f"{m['heads']} (>= 1)")
    kv = getattr(args, "kv_heads", None)
    if kv is not None:
        if kv < 1 or m["heads"] % kv:
            raise SystemExit(f"--kv_heads {kv} must divide --heads "
                             f"{m['heads']} (>= 1)")
        m["kv_heads"] = kv
    if getattr(args, "rope", False):
        if (m["dim"] // m["heads"]) % 2:
            raise SystemExit(f"--rope needs an even head dim "
                             f"(--dim {m['dim']} / --heads {m['heads']})")
        m["rope"] = True
    m["max_len"] = max(getattr(args, "max_len", None) or m["max_len"],
                       seq_len)
    return m


def _lr_schedule(cfg, args):
    """--warmup_steps > 0: linear warmup -> cosine decay to 10% of peak
    over the run; else the constant --lr. Returns what DenseTable's lr
    accepts (float or optax schedule)."""
    warmup = getattr(args, "warmup_steps", 0)
    if not warmup:
        return cfg.table.lr
    import optax

    total = max(cfg.train.num_iters, warmup + 1)
    return optax.warmup_cosine_decay_schedule(
        init_value=0.0, peak_value=cfg.table.lr, warmup_steps=warmup,
        decay_steps=total, end_value=0.1 * cfg.table.lr)


def _updater_kwargs(cfg, args, params):
    kw = {}
    clip = getattr(args, "clip_norm", 0.0)
    if clip:
        kw["clip_norm"] = clip
    wd = getattr(args, "weight_decay", None)
    if cfg.table.updater == "adamw":
        kw["weight_decay"] = 0.01 if wd is None else wd
        kw["decay_mask"] = tfm.decay_mask(params)
    elif wd is not None:
        # only adamw applies decoupled decay — dropping the flag quietly
        # would be the silent-downgrade bug again
        raise SystemExit("--weight_decay needs --updater adamw "
                         f"(got {cfg.table.updater})")
    return kw


def config_model(config: dict):
    """The module that builds a configuration file's model, by its
    ``model_type``."""
    kind = config.get("model_type", "zaya")
    if kind not in CONFIG_MODELS:
        raise SystemExit(f"--model_config: model_type {kind!r} is not "
                         f"built (have {sorted(CONFIG_MODELS)})")
    return CONFIG_MODELS[kind]


def model_dp_step(config: dict, mesh, params, first_batch, *, updater: str,
                  lr, name: str = "lm"):
    """The dp layout's table and fused step for a model taken from a
    configuration file, whichever module builds it (``config_model``):
    ``(model, table, step, stats)``. ``config`` holds the published keys
    (the module's ``from_config``) and how it is run: ``attn``,
    ``head_chunk``, ``compute_dtype``, ``router_bias_rate``. ``params`` is
    the caller's initial tree (the app draws the module's ``init``, the
    benchmark makes its own from its seed); the table owns the state from
    here on. A model with routers carries their balancing bias from step
    to step (``table.state``, started by the module's ``centred_bias``
    over ``first_batch``, as ``prep`` places it) and
    ``stats(table.pull(), batch, table.state)`` is its routing observer
    (the module's ``routing_stats``); a model without carries nothing
    (``table.state`` is None) and ``stats(table.pull(), batch)`` is the
    module's ``observe``, run on every worker's shard of the batch and
    reduced over them. Either is jitted apart from the step."""
    model = config_model(config)
    m = model.from_config(config)
    cd = jnp.dtype(config.get("compute_dtype", "float32"))
    how = dict(compute_dtype=cd, attn_impl=config.get("attn", "flash"),
               head_chunk=int(config.get("head_chunk", 0)),
               axis_name=DATA_AXIS)

    def run_as_the_step(fn):
        # a function is run as the step is in what it takes (ZAYA's
        # observer runs no head, so it takes no ``head_chunk``; the
        # routing observers do not reduce over the workers)
        takes = inspect.signature(fn).parameters
        return functools.partial(
            fn, m=m, **{k: v for k, v in how.items() if k in takes})

    routed = hasattr(model, "routing_stats")
    if routed:
        stats = jax.jit(run_as_the_step(model.routing_stats))
    else:
        # ``observe`` reduces over the workers itself: each reads its own
        # shard of the batch, as in the step (a kernel cannot be
        # partitioned by the compiler: it has to stand in a ``shard_map``)
        stats = jax.jit(jax.shard_map(
            run_as_the_step(model.observe), mesh=mesh,
            in_specs=(P(), P(DATA_AXIS)), out_specs=P()))
    bias = model.centred_bias(
        lambda b: stats(params, first_batch, b), m) if routed else None
    table = DenseTable(params, mesh, updater=updater, lr=lr, name=name)
    step = table.make_step(
        run_as_the_step(model.grad_fn), batch_spec=P(DATA_AXIS),
        compute_dtype=None if cd == jnp.float32 else cd, state=bias)
    return m, table, step, stats


zaya_dp_step = model_dp_step    # the name the benchmark's ZAYA adapter calls


def _run_model_config(cfg, args, mesh, layout, seq_len):
    """``--model_config``: (the data, table, step, prep, the routing
    observer as ``TrainLoop``'s ``extra_metrics``)."""
    if layout != "dp":
        raise SystemExit(f"--model_config is only wired into --layout dp "
                         f"(got {layout})")
    for flag in ("dim", "depth", "heads", "kv_heads"):
        if getattr(args, flag, None) is not None:
            raise SystemExit(f"--{flag} and --model_config both name the "
                             "model: the file decides")
    for flag, default in (("rope", False), ("dropout", 0.0), ("generate", 0),
                          ("attn", "reference"), ("remat", False),
                          ("head_chunk", 0), ("dtype", "float32"),
                          ("accum", 1), ("comm", "float32"),
                          ("clip_norm", 0.0), ("weight_decay", None)):
        if getattr(args, flag, default) != default:
            raise SystemExit(f"--{flag} is not wired into --model_config: "
                             "the file says how the model is run")
    with open(args.model_config) as f:
        config = json.load(f)
    data = _load_data(cfg, args, seq_len, int(config["vocab_size"]))
    batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
    last = {}

    @prof.span(prof.FEED)
    def prep(batch):
        last["batch"] = {"tokens": jax.device_put(
            jnp.asarray(batch["tokens"]), batch_sharding)}
        return last["batch"]

    model = config_model(config)
    params = model.init(jax.random.PRNGKey(cfg.train.seed),
                        model.from_config(config))
    first = {"tokens": data["tokens"][: cfg.train.batch_size]}
    _, table, step, stats = model_dp_step(
        config, mesh, params, prep(first), updater=cfg.table.updater,
        lr=_lr_schedule(cfg, args), name=cfg.table.name)

    def routing_metrics() -> dict:
        """The observer's counters of the last batch fed, at
        ``log_every``: the routing and, where the model has a prediction
        module beside its main head, the two losses apart."""
        with prof.span(prof.LOOP_READBACK):
            st = stats(table.pull(), last["batch"], table.state)
            st = jax.device_get({k: st[k] for k in (
                "tokens_held", "absent_share", "load_max_over_mean",
                "lm_nll", "mtp_nll") if k in st})
            prof.counter(prof.MOE_TOKENS_HELD,
                         int(st["tokens_held"].sum()))
            prof.counter(prof.MOE_LOAD_MAX_OVER_MEAN,
                         float(st["load_max_over_mean"].max()))
            losses = {}
            for key, name in (("lm_nll", prof.LM_NLL),
                              ("mtp_nll", prof.MTP_NLL)):
                if key in st:
                    losses[key] = float(st[key])
                    prof.counter(name, losses[key])
        return {"moe_tokens_held": st["tokens_held"].tolist(),
                "moe_absent_share": st["absent_share"].tolist(),
                "moe_load_max_over_mean":
                    st["load_max_over_mean"].tolist(), **losses}

    def linattn_metrics() -> dict:
        """The same for a model that carries no state (``observe``): the
        loss and, a linear-attention layer, the mean decay, the mean write
        strength and the state's largest entry; the counters take the
        worst layer of each."""
        with prof.span(prof.LOOP_READBACK):
            st = jax.device_get(stats(table.pull(), last["batch"]))
            prof.counter(prof.LM_NLL, float(st["lm_nll"]))
            prof.counter(prof.LINATTN_DECAY_MEAN,
                         float(st["decay_mean"].min()))
            prof.counter(prof.LINATTN_BETA_MEAN,
                         float(st["beta_mean"].max()))
            prof.counter(prof.LINATTN_STATE_ABSMAX,
                         float(st["state_absmax"].max()))
        return {"lm_nll": float(st["lm_nll"]),
                **{"linattn_" + k: st[k].tolist() for k in (
                    "decay_mean", "beta_mean", "state_absmax")}}

    return data, table, step, prep, (
        linattn_metrics if table.state is None else routing_metrics)


def run(cfg: Config, args, metrics) -> dict:
    seq_len = getattr(args, "seq_len", 128)
    layout = getattr(args, "layout", "dp")
    if (getattr(args, "attn", "reference") in ("a2a", "a2a_flash")
            and layout != "sp"):
        # a2a IS a sequence-parallel strategy; on dp there is no sequence
        # sharding to exchange
        raise SystemExit("--attn a2a/a2a_flash is sequence parallelism: "
                         f"use --layout sp (got {layout})")
    # These flags only thread through the dp/sp fused-step path; failing
    # loud beats silently training with different memory/perf/precision
    # than requested on tp/pp/ep.
    if layout not in ("dp", "sp"):
        for flag, default in (("attn", "reference"), ("accum", 1),
                              ("dtype", "float32"), ("comm", "float32"),
                              ("clip_norm", 0.0), ("warmup_steps", 0),
                              ("generate", 0)):
            if getattr(args, flag, default) != default:
                raise SystemExit(f"--{flag} is only wired into --layout "
                                 f"dp/sp (got {layout})")
        if cfg.table.updater == "adamw":
            # the tp/pp/ep tail hardcodes plain adam; silently dropping
            # the decay would be the r2 silent-downgrade bug again
            raise SystemExit("--updater adamw is only wired into "
                             f"--layout dp/sp (got {layout})")
    if layout != "dp" and getattr(args, "remat", False):
        # loss_sp's ring forward has its own memory story (T/N activations
        # per shard); silently ignoring the flag would misreport memory
        raise SystemExit(f"--remat is only wired into --layout dp "
                         f"(got {layout})")
    if layout != "dp" and getattr(args, "head_chunk", 0):
        raise SystemExit(f"--head_chunk is only wired into --layout dp "
                         f"(got {layout})")
    if layout != "dp" and getattr(args, "dropout", 0.0):
        # must precede the tp/pp/ep early returns below, or those layouts
        # would silently train without the requested regularization
        raise SystemExit(f"--dropout is only wired into --layout dp "
                         f"(got {layout})")
    if layout in ("tp", "pp"):
        return _run_model_parallel(cfg, args, metrics, layout, seq_len)
    if layout == "ep":
        return _run_ep(cfg, args, metrics, seq_len)
    mesh = make_mesh()
    n_shards = mesh.shape[DATA_AXIS]
    if seq_len % n_shards:
        raise SystemExit(f"--seq_len {seq_len} must divide by the "
                         f"{n_shards}-way mesh")
    model_file = getattr(args, "model_config", None)
    routing_metrics = None
    if model_file:
        data, table, step, prep, routing_metrics = _run_model_config(
            cfg, args, mesh, layout, seq_len)
        heads = None
    else:
        model = _model_cfg(args, seq_len)
        data = _load_data(cfg, args, seq_len)
        params = tfm.init(jax.random.PRNGKey(cfg.train.seed), **model)
        table = DenseTable(params, mesh, updater=cfg.table.updater,
                           lr=_lr_schedule(cfg, args), name=cfg.table.name,
                           updater_kwargs=_updater_kwargs(cfg, args, params))
        # the table owns the parameters from here on; the template would
        # sit on the default device for the whole run (1.6 GB at d=2048 x 8)
        del params
        heads = model["heads"]
    log_tables_built(metrics, (table.params, table.opt_state))

    ckpt, start_step = _maybe_checkpointer(cfg, args, table)

    accum = getattr(args, "accum", 1)
    comm = getattr(args, "comm", "float32")
    compute_dtype = (jnp.bfloat16
                     if getattr(args, "dtype", "float32") == "bfloat16"
                     else None)
    dropout = getattr(args, "dropout", 0.0)
    if dropout and accum > 1:
        # the accum fold reshapes every batch leaf into microbatches,
        # which a [2]-shaped key cannot survive
        raise SystemExit("--dropout is incompatible with --accum > 1")
    if model_file:
        pass        # step and prep came with the table, above
    elif layout == "dp":
        remat = getattr(args, "remat", False)
        if remat and getattr(args, "remat_mode", "full") != "full":
            remat = args.remat_mode
        step = table.make_step(
            functools.partial(tfm.grad_fn, heads=heads,
                              attn_impl=getattr(args, "attn", "reference"),
                              remat=remat,
                              head_chunk=getattr(args, "head_chunk", 0),
                              dropout=dropout),
            # per-WORKER keys shard with the data axis (distinct masks
            # per shard — a replicated key would correlate regularization
            # noise across workers); tokens shard over workers
            batch_spec=({"tokens": P(DATA_AXIS), "rng": P(DATA_AXIS)}
                        if dropout else P(DATA_AXIS)),
            accum=accum, compute_dtype=compute_dtype, comm=comm)
        batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
        drop_key = jax.random.PRNGKey(cfg.train.seed + 71)
        n_prepped = [start_step]

        @prof.span(prof.FEED)
        def prep(batch):
            out = {"tokens": jax.device_put(
                jnp.asarray(batch["tokens"]), batch_sharding)}
            if dropout:
                # fresh key per (resume-offset) step, then one key per
                # worker; loss() takes each shard's [1, 2] slice
                step_key = jax.random.fold_in(drop_key, n_prepped[0])
                n_prepped[0] += 1
                out["rng"] = jax.device_put(
                    jax.vmap(lambda i: jax.random.fold_in(step_key, i))(
                        jnp.arange(n_shards)), batch_sharding)
            return out
    else:
        # batch replicated, sequence sharded: inside shard_map each
        # device sees its token slice; ring attention stitches them.
        # make_step all-gathers params per shard and psum_scatters grads —
        # the same PS shape; only the batch specs change (sequence axis)
        sp_grad, sp_spec = tfm.sp_train_wiring(
            heads, seq_len // n_shards,
            attn_impl=getattr(args, "attn", "reference"))
        step = table.make_step(sp_grad, batch_spec=sp_spec, accum=accum,
                               compute_dtype=compute_dtype, comm=comm)
        seq_sharding = NamedSharding(mesh, P(None, DATA_AXIS))

        @prof.span(prof.FEED)
        def prep(batch):
            t = jnp.asarray(batch["tokens"])
            return {"inp": jax.device_put(t[:, :-1], seq_sharding),
                    "tgt": jax.device_put(t[:, 1:], seq_sharding)}

    # TrainLoop fast-forwards the iterator to step_offset, so the resumed
    # trajectory continues the stream instead of replaying it.
    batches = BatchIterator(data, cfg.train.batch_size, seed=cfg.train.seed)

    ckpt_every = _ckpt_every(cfg, args)
    loop = TrainLoop(lambda b: table.step_inplace(step, prep(b)), batches,
                     metrics=metrics, log_every=cfg.train.log_every,
                     batch_size=cfg.train.batch_size,
                     checkpointer=ckpt,
                     checkpoint_every=ckpt_every,
                     step_offset=start_step, extra_metrics=routing_metrics)
    # A completed run resumed again is a no-op, not an extra step.
    remaining = max(cfg.train.num_iters - start_step, 0)
    losses = loop.run(remaining)
    if ckpt is not None and remaining and not (
            ckpt_every and cfg.train.num_iters % ckpt_every == 0):
        ckpt.save(step=cfg.train.num_iters)  # not already saved by the loop
    if losses:
        metrics.log(final_loss=losses[-1], layout=layout, seq_len=seq_len,
                    tokens_per_sec=loop.timer.samples_per_sec * seq_len)
    gen = getattr(args, "generate", 0)
    out = {"losses": losses, "table": table, "layout": layout,
           "start_step": start_step,
           "samples_per_sec": loop.timer.samples_per_sec,
           # the jitted fused step and its batch placement, for callers
           # that inspect the program (chip_smoke.py, tests)
           "step": step, "prep": prep}
    if gen:
        # serving demo: pull the trained params and decode through the
        # KV cache (models/decode.py) — greedy unless --temperature
        from minips_tpu.models import decode as dec

        prompt = jnp.asarray(data["tokens"][:1, : min(8, seq_len)])
        temp = getattr(args, "temperature", 0.0)
        # decode at the TRAINING precision (f32 unless --dtype bfloat16)
        # so greedy decode stays pinned to the training forward
        dd = compute_dtype if compute_dtype is not None else jnp.float32
        toks = dec.generate(
            table.pull(), prompt, gen, heads=heads, temperature=temp,
            compute_dtype=dd, cache_dtype=dd,
            key=(jax.random.PRNGKey(cfg.train.seed) if temp else None))
        out["generated"] = toks[0].tolist()
        metrics.log(generated=out["generated"])
    return out


def _load_data(cfg, args, seq_len, vocab: int = MODEL["vocab"]):
    path = getattr(args, "data_file", None)
    if path:
        from minips_tpu.data.text import read_lm_file

        return read_lm_file(path, seq_len, max_windows=65536)
    return synthetic.lm_sequences(2048, seq_len, vocab,
                                  seed=cfg.train.seed)


def _ckpt_every(cfg, args) -> int:
    """Checkpoint cadence from the merged config, falling back to raw args
    (tests call run() with a bare Namespace, skipping config_from_args)."""
    return (getattr(cfg.train, "checkpoint_every", 0)
            or getattr(args, "checkpoint_every", 0) or 0)


def _maybe_checkpointer(cfg, args, table):
    """(Checkpointer | None, start_step) for the dp/sp table layouts.
    checkpoint_dir honors --config_file via cfg.train, like lr_example."""
    path = (getattr(cfg.train, "checkpoint_dir", None)
            or getattr(args, "checkpoint_dir", None))
    if not path:
        return None, 0
    from minips_tpu.ckpt.orbax_backend import make_checkpointer

    ckpt = make_checkpointer(path, {"lm": table})
    start = 0
    if getattr(args, "resume", False) and ckpt.list_steps():
        start = ckpt.restore()  # resume-if-present: first launch of an
    return ckpt, start          # always---resume wrapper starts at 0


def _optax_train(cfg, args, metrics, mesh, params, sharded_loss,
                 seq_len, layout, **log_fields) -> dict:
    """Shared tail of the non-PS layouts (tp/pp/ep): jitted
    value_and_grad + optax adam with donated buffers, data-parallel batch
    placement, TrainLoop, metrics."""
    import optax

    tx = optax.adam(cfg.table.lr)
    opt = tx.init(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, o, toks):
        loss, g = jax.value_and_grad(sharded_loss)(p, toks)
        updates, o = tx.update(g, o, p)
        return optax.apply_updates(p, updates), o, loss

    data = _load_data(cfg, args, seq_len)
    batch_sharding = NamedSharding(mesh, P(DATA_AXIS))
    state = {"p": params, "o": opt}

    def do_step(batch):
        toks = jax.device_put(jnp.asarray(batch["tokens"]), batch_sharding)
        state["p"], state["o"], loss = train_step(state["p"], state["o"],
                                                  toks)
        return loss

    batches = BatchIterator(data, cfg.train.batch_size, seed=cfg.train.seed)
    loop = TrainLoop(do_step, batches, metrics=metrics,
                     log_every=cfg.train.log_every,
                     batch_size=cfg.train.batch_size)
    losses = loop.run(cfg.train.num_iters)
    metrics.log(final_loss=losses[-1], layout=layout, seq_len=seq_len,
                tokens_per_sec=loop.timer.samples_per_sec * seq_len,
                **log_fields)
    return {"losses": losses, "params": state["p"], "layout": layout,
            "samples_per_sec": loop.timer.samples_per_sec}


def _run_model_parallel(cfg, args, metrics, layout, seq_len) -> dict:
    """tp/pp layouts: 2D (data x model) mesh, weights + optimizer state
    sharded over the model axis (per-tensor weight-update sharding),
    value_and_grad outside the shard_map, optax step under one jit."""
    from minips_tpu.parallel.mesh import MODEL_AXIS
    from minips_tpu.parallel.pipeline import stack_layers

    tp_size = getattr(args, "tp", 2)
    micro = getattr(args, "microbatches", 4)
    n_dev = len(jax.devices())
    if n_dev % tp_size:
        raise SystemExit(f"--tp {tp_size} must divide {n_dev} devices")
    mesh = make_mesh(n_dev // tp_size, model_size=tp_size)
    model = _model_cfg(args, seq_len)
    heads = model["heads"]
    if layout == "tp" and heads % tp_size:
        raise SystemExit(f"--tp {tp_size} must divide heads {heads}")
    if layout == "pp" and model["depth"] % tp_size:
        raise SystemExit(f"--tp {tp_size} must divide depth "
                         f"{model['depth']} (pipeline stages)")
    data_shards = n_dev // tp_size
    if cfg.train.batch_size % data_shards:
        raise SystemExit(f"--batch_size {cfg.train.batch_size} must divide "
                         f"by the {data_shards}-way data axis")
    local_b = cfg.train.batch_size // data_shards
    if layout == "pp" and local_b % micro:
        raise SystemExit(
            f"--microbatches {micro} must divide the per-device batch "
            f"{local_b} (= --batch_size {cfg.train.batch_size} / "
            f"{data_shards} data shards)")

    params = tfm.init(jax.random.PRNGKey(cfg.train.seed), **model)
    if layout == "pp":
        params = {**params, "blocks": stack_layers(params["blocks"])}
        specs = tfm.pp_specs(params, MODEL_AXIS)
    else:
        specs = tfm.tp_specs(params, MODEL_AXIS)
    shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(jax.device_put, params, shardings)

    def sharded_loss(p, toks):
        def shard_fn(p_, t_):
            if layout == "pp":
                logits = tfm.apply_pp(p_, t_[:, :-1], heads=heads,
                                      axis_name=MODEL_AXIS,
                                      num_microbatches=micro)
            else:
                logits = tfm.apply_tp(p_, t_[:, :-1], heads=heads,
                                      axis_name=MODEL_AXIS)
            return jax.lax.pmean(tfm.nll(logits, t_[:, 1:]), DATA_AXIS)
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(specs, P(DATA_AXIS)), out_specs=P())(p, toks)

    return _optax_train(cfg, args, metrics, mesh, params, sharded_loss,
                        seq_len, layout, tp=tp_size)


def _run_ep(cfg, args, metrics, seq_len) -> dict:
    """ep layout: MoE-LM, batch data-parallel, experts sharded over the
    same axis; dispatch/return ride two all_to_alls per block
    (parallel/moe.py). Optimizer state shards with the expert weights
    (weight-update sharding, PS-server-role per-expert)."""
    mesh = make_mesh()
    n_dev = mesh.shape[DATA_AXIS]
    model = _model_cfg(args, seq_len)
    heads = model["heads"]
    experts = getattr(args, "experts", 8)
    k_top = getattr(args, "k_top", 1)
    if not 1 <= k_top <= experts:
        raise SystemExit(f"--k_top {k_top} must be in [1, --experts "
                         f"{experts}] (0 would disable every MoE FFN)")
    if experts % n_dev:
        raise SystemExit(f"--experts {experts} must divide by the "
                         f"{n_dev}-way mesh")
    if cfg.train.batch_size % n_dev:
        raise SystemExit(f"--batch_size {cfg.train.batch_size} must "
                         f"divide by the {n_dev}-way mesh")
    local_tokens = (cfg.train.batch_size // n_dev) * seq_len
    capacity = getattr(args, "capacity", 0) or max(
        2 * k_top * local_tokens // experts, 4)

    params = tfm.init_moe_lm(
        jax.random.PRNGKey(cfg.train.seed), vocab=model["vocab"],
        dim=model["dim"], heads=heads, depth=model["depth"],
        max_len=model["max_len"], num_experts=experts,
        kv_heads=model.get("kv_heads"), rope=model.get("rope", False))
    specs = tfm.ep_lm_specs(params)
    shardings = jax.tree.map(lambda sp: NamedSharding(mesh, sp), specs,
                             is_leaf=lambda x: isinstance(x, P))
    params = jax.tree.map(jax.device_put, params, shardings)

    def sharded_loss(p, toks):
        def shard_fn(p_, t_):
            logits, aux = tfm.apply_ep(p_, t_[:, :-1], heads=heads,
                                       capacity=capacity, k_top=k_top)
            nll = jax.lax.pmean(tfm.nll(logits, t_[:, 1:]), DATA_AXIS)
            return nll + 0.01 * aux   # router load-balance pressure
        return jax.shard_map(
            shard_fn, mesh=mesh,
            in_specs=(specs, P(DATA_AXIS)), out_specs=P())(p, toks)

    return _optax_train(cfg, args, metrics, mesh, params, sharded_loss,
                        seq_len, "ep", experts=experts, k_top=k_top,
                        capacity=capacity)


def main(argv=None, metrics=None):
    return app_main("lm_example", DEFAULT, run, extra_flags=_flags,
                    argv=argv, metrics=metrics)


if __name__ == "__main__":
    main()
