"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no children, every local device through ``make_mesh()``. It
drives the two SPMD apps through their own ``main`` (what ``python -m
minips_tpu.apps.<app>`` runs) with the flags a user would type, at widths
the repo already supports, then checks what came out by the repo's own
means:

- ``deepfm``: ``wide_deep_example --model deepfm`` — both table kinds in
  one fused PS step; 2^22 slots x dim 8, Criteo shape, Adagrad, batch 16384.
- ``lm``: ``lm_example --layout dp --attn flash`` — the Pallas kernels'
  carrier; d=2048 x 8 layers, 32 heads, T=1024, B=16, bf16, remat=dots.
- ``kernels``: the flash kernels against ``reference_attention`` on a small
  input (GQA 32/4 forward and gradients; the sp ring path on the devices
  present) and the opt-in ``gather_rows`` kernel against XLA's gather.
- ``zaya``: one forward and backward of a one-layer ZAYA1 block
  (``models/zaya.py``: compressed convolutional attention, the dropless
  top-1 expert layer holding 8 of 16 experts) at published widths, T 1,024,
  against the benchmark's plain float32 reference.
- ``joyai``: one forward and backward of the latent-attention expert
  decoder (``models/mla_moe.py``: a dense layer, an expert layer holding 8
  of 256 experts at top-8 beside a shared one, the prediction module, the
  untied head; the flash kernels at 192/128) at published widths, T 1,024,
  against the benchmark's plain float32 reference.
- ``olmo``: one forward and backward of the hybrid decoder
  (``models/olmo_hybrid.py``) cut to one linear-attention layer (the
  chunked gated delta rule of ``ops/delta_rule.py`` at 30 heads of 96 / 192
  channels) and one full-attention layer (the flash kernels at 30 heads of
  128, no position signal) at published widths, T 1,024, against the
  benchmark's plain float32 reference, which is the token-by-token
  recurrence.
- ``laguna``: one forward and backward of the window/full hybrid
  (``models/laguna.py``) cut to a window layer (the windowed flash kernels
  at 36 heads over 4 key/value heads, window 512) and a full layer (24
  heads, YaRN on half the channels), each with 8 of 256 experts at top-10
  beside a shared one and the per-head gate, at published widths, T 4,096,
  against the benchmark's plain float32 reference.

It fails (non-zero exit, no result line) if JAX finds no TPU, if a loss is
non-finite or does not fall, if the LM step holds fewer than three compiled
Mosaic calls, if on several devices the LM step does not ask for an
all-gather and a reduce-scatter, the DeepFM step moves a table-sized
collective or table bytes are uneven, if a kernel disagrees with its
reference, or if anything raises: nothing is caught and downgraded. It
writes two JSON lines to stdout. The first is the facts of the run:
versions, the compile cache directory, and per leg its losses, compiles,
memory and the program's Mosaic calls and collectives. Times in it are
host clock around work that ends in a device read; they are facts of this
run, not a benchmark. The LAST line is the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.

The script runs the checkout it sits in: a copy of it in a directory
without the ``minips_tpu`` package fails before it touches the chip.

``--rehearse-cpu`` is the explicit tiny-size rehearsal of the control flow
on 4 fake CPU devices (the blockwise scan stands in for the kernels
there); its output says ``cpu``. Without it, no TPU means failure.

Usage: python chip_smoke.py
"""

from __future__ import annotations

import argparse
import collections
import gc
import json
import math
import os
import statistics
import sys
import time

STEPS = 8  # the compiling step + a handful


def _argv(full: bool) -> dict[str, list[str]]:
    """The two command lines, at full width or rehearsal size."""
    if full:
        slots, batch = 1 << 22, 16384
        # lr: the app's default 3e-3 suits its 64-wide default model. At
        # d=2048 without warmup an Adam step of 3e-4 still overshoots (on
        # the chip the loss went 6.03 -> 6.67 before it fell); this script
        # checks that the loss FALLS, so it takes steps small enough for
        # the first-order term to win from the first one
        lm = ["--dim", "2048", "--depth", "8", "--heads", "32",
              "--seq_len", "1024", "--batch_size", "16",
              "--head_chunk", "128", "--lr", "3e-5"]
    else:
        # slots >> batch x 26 fields even here: below that ratio GSPMD
        # rightly all-gathers the tiny table and the traffic check misfires
        slots, batch = 1 << 18, 256
        lm = ["--dim", "64", "--depth", "2", "--heads", "4",
              "--seq_len", "128", "--batch_size", "8", "--head_chunk", "32",
              "--lr", "3e-3"]
    common = ["--num_iters", str(STEPS), "--log_every", "1"]
    return {
        "deepfm": ["--model", "deepfm", "--exec", "spmd", "--num_slots",
                   str(slots), "--updater", "adagrad", "--batch_size",
                   str(batch), *common],
        "lm": ["--layout", "dp", "--attn", "flash", *lm, "--dtype",
               "bfloat16", "--remat", "--remat_mode", "dots", "--updater",
               "adam", *common],
    }


class _Recorder:
    """The ``metrics`` sink handed to an app: keeps every record with the
    host clock, and reads each device's memory at ``tables_built``."""

    def __init__(self, devices):
        self.devices = devices
        self.records: list[tuple[float, dict]] = []
        self.memory_after_tables = None

    def log(self, **record):
        if record.get("event") == "tables_built":
            self.memory_after_tables = _memory(self.devices, "bytes_in_use")
        self.records.append((time.perf_counter(), record))
        return record


def _memory(devices, key: str) -> list:
    """Per-device ``memory_stats()[key]``; None where the backend has no
    allocator statistics (the CPU)."""
    return [(d.memory_stats() or {}).get(key) for d in devices]


class _CompileLog:
    """JAX's own account of every backend compile in this process: its
    function name, seconds (compile, or retrieval on a persistent-cache
    hit) and whether the persistent cache answered it."""

    def __init__(self):
        import jax

        self.compiles: list[dict] = []
        self._hit = False
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(self._on_secs)

    def _on_event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self._hit = True  # precedes its compile's duration event

    def _on_secs(self, event, secs, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append({"fun": kw.get("fun_name"),
                                  "secs": secs, "cache_hit": self._hit})
            self._hit = False


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"chip_smoke: FAILED — {what}")


def _run_leg(name, main, argv, devices, compile_log) -> tuple[dict, dict]:
    """Drive one app through its ``main``; returns (facts, app result)."""
    rec = _Recorder(devices)
    first_compile = len(compile_log.compiles)
    result = main(argv, metrics=rec)
    compiles = compile_log.compiles[first_compile:]

    at = [i for i, (_, r) in enumerate(rec.records)
          if "step" in r and "loss" in r]
    losses = [rec.records[i][1]["loss"] for i in at]
    _check(len(losses) == STEPS, f"{name}: {len(losses)} of {STEPS} steps")
    _check(all(math.isfinite(x) for x in losses),
           f"{name}: non-finite loss in {losses}")
    _check(losses[-1] < losses[0],
           f"{name}: loss did not fall: {losses}")
    # host clock from the record before to each step's own record, which
    # follows a float(loss): every interval ends in a device read
    step_s = [rec.records[i][0] - rec.records[i - 1][0] for i in at]
    steady = statistics.median(step_s[1:])
    step_compile = max(compiles, key=lambda c: c["secs"])
    built = next(r for _, r in rec.records
                 if r.get("event") == "tables_built")
    table_bytes = built["table_bytes_per_device"]
    _check(len(table_bytes) == len(devices)
           and max(table_bytes.values()) <= 1.25 * min(table_bytes.values()),
           f"{name}: table bytes uneven over devices: {table_bytes}")
    facts = {
        "argv": " ".join(argv),
        "steps": len(losses),
        "loss_first": losses[0], "loss_last": losses[-1],
        "losses": losses,
        # first step minus a steady one: trace + lower + compile (or the
        # persistent-cache read)
        "compile_s": round(step_s[0] - steady, 3),
        "step_ms": [round(1e3 * s, 2) for s in step_s[1:]],
        "step_compile": {**step_compile,
                         "secs": round(step_compile["secs"], 3)},
        "cache_hits": sum(c["cache_hit"] for c in compiles),
        "cache_misses": sum(not c["cache_hit"] for c in compiles),
        "table_bytes_per_device": table_bytes,
        "memory_after_tables": rec.memory_after_tables,
        "memory_peak": _memory(devices, "peak_bytes_in_use"),
    }
    return facts, result


def _kinds(ops) -> dict[str, int]:
    return dict(collections.Counter(op.kind for op in ops))


def _program_facts(lowered) -> tuple[dict, list]:
    """What the step holds. ``collectives_asked``: the program as lowered,
    before the backend's passes — what the step asks the device for.
    The rest is read from the optimized HLO — what the backend made of it
    (a persistent-cache read: the app compiled this same program a moment
    ago): Mosaic calls, and the collectives with their payload bytes."""
    from minips_tpu.utils.comm_analysis import collective_ops

    hlo = lowered.compile().as_text()
    ops = collective_ops(hlo)
    return {"mosaic_calls": hlo.count('custom_call_target="tpu_custom_call"'),
            "collectives_asked": _kinds(
                collective_ops(lowered.as_text(dialect="hlo"))),
            "collectives": _kinds(ops),
            "collective_bytes": sum(op.bytes for op in ops),
            "largest_collectives": [
                {"kind": op.kind, "shape": op.shape, "bytes": op.bytes}
                for op in sorted(ops, key=lambda o: -o.bytes)[:4]]}, ops


def _leg_deepfm(argv, devices, compile_log) -> dict:
    from minips_tpu.apps import wide_deep_example as app
    from minips_tpu.data import synthetic

    facts, result = _run_leg("deepfm", app.main, argv, devices, compile_log)
    ps = result["step"]
    batch = int(argv[argv.index("--batch_size") + 1])
    slots = int(argv[argv.index("--num_slots") + 1])
    program, ops = _program_facts(
        ps.lower(ps.shard_batch(synthetic.criteo_like(batch, seed=1))))
    # pull/push of embedding rows must move the batch's rows, never a
    # table (tests/test_sharded_traffic.py pins the same on the raw ops)
    table_sized = [op.shape for op in ops
                   if op.has_dim(slots) or op.has_dim(slots // len(devices))]
    _check(not table_sized,
           f"deepfm: table-sized collectives in the step: {table_sized}")
    return {**facts, **program}


def _leg_lm(argv, devices, compile_log, on_tpu) -> dict:
    import numpy as np

    from minips_tpu.apps import lm_example as app

    facts, result = _run_leg("lm", app.main, argv, devices, compile_log)
    table = result["table"]
    batch, seq = (int(argv[argv.index(f) + 1])
                  for f in ("--batch_size", "--seq_len"))
    tokens = np.zeros((batch, seq + 1), np.int32)
    program, _ = _program_facts(
        result["step"].lower(*table.step_state,
                             result["prep"]({"tokens": tokens})))
    if on_tpu:  # forward, dQ, dK/dV — the kernels, not their scan twin
        _check(program["mosaic_calls"] >= 3,
               f"lm: {program['mosaic_calls']} Mosaic calls in the step")
    if len(devices) > 1:  # pull ≡ all-gather, push ≡ reduce-scatter
        _check({"all-gather", "reduce-scatter"}
               <= set(program["collectives_asked"]),
               f"lm: the step asks for {program['collectives_asked']}")
        _check(program["collectives"],
               "lm: no collective left in the compiled step")
    return {**facts, **program}


def _rel_err(a, b) -> float:
    import jax.numpy as jnp

    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _leg_kernels(on_tpu: bool) -> dict:
    """The kernels against their references on a small input (off TPU, in
    the rehearsal, their scan twins at a toy shape)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from minips_tpu.ops import pallas_kernels
    from minips_tpu.ops.flash_attention import (flash_attention,
                                                ring_flash_attention_local)
    from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh
    from minips_tpu.parallel.ring_attention import reference_attention

    B, T, H, Hk, D = (2, 1024, 32, 4, 64) if on_tpu else (2, 64, 4, 2, 16)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, Hk, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, T, Hk, D), jnp.bfloat16)
    tol = 3e-2  # bf16 inputs and outputs against an f32-softmax oracle

    def loss(attn):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True).astype(jnp.float32) ** 2)

    with jax.default_matmul_precision("highest"):
        ref = reference_attention(q, k, v, causal=True)
        g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(
            q, k, v)
    out = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))(
        q, k, v)
    g = jax.jit(jax.grad(loss(flash_attention), argnums=(0, 1, 2)))(q, k, v)
    errs = {"gqa_fwd": _rel_err(out, ref),
            **{f"gqa_d{n}": _rel_err(a, b)
               for n, a, b in zip("qkv", g, g_ref)}}

    # the sp ring: sequence sharded over the devices present, the same
    # kernels offset-masked per ring step
    mesh = make_mesh()
    spec = P(None, DATA_AXIS)
    ring = jax.jit(jax.shard_map(
        lambda q, k, v: ring_flash_attention_local(
            q, k, v, axis_name=DATA_AXIS, causal=True),
        mesh=mesh, in_specs=(spec,) * 3, out_specs=spec))
    errs["ring_fwd"] = _rel_err(ring(q, k, v), ref)
    for name, err in errs.items():
        _check(math.isfinite(err) and err < tol,
               f"kernels: {name} differs from reference_attention by "
               f"{err:.4f} (tolerance {tol})")
    facts = {"shape": {"B": B, "T": T, "H": H, "kv_heads": Hk, "D": D},
             "rel_err": {k: round(v, 5) for k, v in errs.items()},
             "tolerance": tol}
    if on_tpu:  # opt-in and off the default path; does it still compile?
        emb = jax.random.normal(kq, (1 << 18, 128), jnp.float32)
        slots = jax.random.randint(kk, (65536,), 0, 1 << 18)
        rows = pallas_kernels.gather_rows(emb, slots)
        _check(bool(jnp.array_equal(rows, emb[slots])),
               "kernels: gather_rows differs from emb[slots]")
        facts["gather_rows"] = "equal to emb[slots] at S=2^18 D=128 N=65536"
    return facts


def _grad_norm_gap(grads, want_sums, n: int) -> float:
    """The worst leaf's gap between the norm of the program's gradient
    and the reference's (a sum over ``n`` tokens, so divided by it), over
    the reference's norm of that leaf or of the median leaf, whichever is
    larger."""
    import jax
    import jax.numpy as jnp
    norm = lambda x: float(jnp.linalg.norm(x.astype(jnp.float32)))  # noqa: E731,E501
    want = [norm(x) / n for x in jax.tree.leaves(want_sums)]
    med = sorted(want)[len(want) // 2]
    return max(abs(norm(g) - w) / max(w, med)
               for g, w in zip(jax.tree.leaves(grads), want))


def _leg_zaya(on_tpu: bool, here: str) -> dict:
    """One forward and backward of a one-layer ZAYA block, bfloat16 worker
    math as the cell runs it, against the plain float32 reference the
    benchmark keeps (published widths on the chip, toy widths off it)."""
    import jax
    import jax.numpy as jnp

    from minips_tpu.models import zaya
    from minips_tpu.tables.dense import cast_floating

    bench = os.path.join(here, "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    from benchlib.reference import zaya_ref

    with open(os.path.join(bench, "configs", "zaya1-8b.json")) as f:
        config = dict(json.load(f), num_hidden_layers=1)
    B, T = 2, 1024
    if not on_tpu:
        config.update(hidden_size=32, head_dim=8, moe_intermediate_size=16,
                      router_hidden_size=8, vocab_size=128, num_experts=2,
                      held_experts=[0, 2], head_chunk=16,
                      published=dict(config["published"], num_experts=4))
        T = 64
    m = zaya.from_config(config)
    params = zaya.init(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, m.vocab)
    cd = jnp.bfloat16
    z = zaya_ref._sizes(config)
    # the balancing bias both start from: the reference's own centring
    bias = jnp.asarray(zaya_ref.centred_bias(params, toks, z, B))
    loss, grads, _ = jax.jit(lambda p, t: zaya.grad_fn(
        cast_floating(p, cd), {"tokens": t}, bias, m, compute_dtype=cd,
        attn_impl="flash", head_chunk=int(config["head_chunk"])))(
        params, toks)
    (want, chosen), want_g = jax.jit(jax.value_and_grad(
        lambda p, t: zaya_ref.loss_sum(p, t, bias, z, False),
        has_aux=True))(params, toks)
    want = float(want) / (B * T)
    mine = jax.jit(lambda p, t: zaya.routing_stats(
        p, {"tokens": t}, bias, m, compute_dtype=cd))(params, toks)
    flips = int(jnp.sum(mine["expert"] != chosen))
    _check(math.isfinite(float(loss)) and abs(float(loss) - want)
           < 2e-3 * want, f"zaya: loss {float(loss)} against the "
           f"reference's {want}")
    # the norm of each leaf's gradient; a token whose top-1 choice flips
    # between bfloat16 and float32 moves its expert's leaves, so the
    # tolerance is bfloat16's plus the share of tokens that flipped
    tol = 0.05 + 4.0 * flips / (B * T)
    worst = _grad_norm_gap(grads, want_g, B * T)
    _check(worst < tol, f"zaya: a leaf's gradient norm is {worst:.4f} off "
           f"the reference's (tolerance {tol:.4f}, {flips} tokens flipped)")
    return {"shape": {"B": B, "T": T, "dim": m.dim, "heads": m.heads,
                      "kv_heads": m.kv_heads, "head_dim": m.head_dim,
                      "experts_held": list(m.held), "experts": m.experts},
            "loss": float(loss), "reference_loss": want,
            "tokens_flipped": flips, "grad_norm_worst_gap": round(worst, 5),
            "tokens_held": mine["tokens_held"].tolist()}


def _leg_joyai(on_tpu: bool, here: str) -> dict:
    """One forward and backward of the latent-attention expert decoder cut
    to a dense layer, an expert layer and the prediction module, bfloat16
    worker math as the cell runs it, against the plain float32 reference
    the benchmark keeps (published widths on the chip, toy widths off
    it)."""
    import jax
    import jax.numpy as jnp

    from minips_tpu.models import mla_moe
    from minips_tpu.tables.dense import cast_floating

    bench = os.path.join(here, "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    from benchlib.reference import joyai_ref

    with open(os.path.join(bench, "configs", "joyai-llm-flash.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2)
    B, T = 2, 1024
    if not on_tpu:
        config.update(
            hidden_size=32, num_attention_heads=2, q_lora_rank=24,
            kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, intermediate_size=48, moe_intermediate_size=16,
            n_routed_experts=4, held_experts=[0, 4], num_experts_per_tok=2,
            vocab_size=128, head_chunk=16,
            published=dict(config["published"], n_routed_experts=8))
        T = 64
    m = mla_moe.from_config(config)
    params = mla_moe.init(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, m.vocab)
    cd = jnp.bfloat16
    z = joyai_ref._sizes(config)
    # the balancing bias both start from: the reference's own centring
    bias = jnp.asarray(joyai_ref.centred_bias(params, toks, z, B))
    how = dict(compute_dtype=cd, attn_impl="flash",
               head_chunk=int(config["head_chunk"]))
    loss, grads, _ = jax.jit(lambda p, t: mla_moe.grad_fn(
        cast_floating(p, cd), {"tokens": t}, bias, m, **how))(params, toks)
    (want, aux), want_g = jax.jit(jax.value_and_grad(
        lambda p, t: joyai_ref.loss_sums(p, t, bias, z, False),
        has_aux=True))(params, toks)
    want = float(want) / (B * T)
    mine = jax.jit(lambda p, t: mla_moe.routing_stats(
        p, {"tokens": t}, bias, m, **how))(params, toks)
    held = aux[0][:, m.held[0]: m.held[1]]
    moved = int(jnp.sum(jnp.abs(mine["tokens_held"] - held)))
    _check(math.isfinite(float(loss)) and abs(float(loss) - want)
           < 2e-3 * want, f"joyai: loss {float(loss)} against the "
           f"reference's {want}")
    # the norm of each leaf's gradient; an assignment that bfloat16 sends
    # to another expert than float32 moves that expert's leaves, so the
    # tolerance is bfloat16's plus the share of held assignments that moved
    tol = 0.05 + 4.0 * moved / max(int(jnp.sum(held)), 1)
    worst = _grad_norm_gap(grads, want_g, B * T)
    _check(worst < tol, f"joyai: a leaf's gradient norm is {worst:.4f} off "
           f"the reference's (tolerance {tol:.4f}, {moved} held assignments "
           "moved)")
    return {"shape": {"B": B, "T": T, "dim": m.dim, "heads": m.heads,
                      "qk_head": m.nope + m.rope, "v_head": m.v_dim,
                      "experts_held": list(m.held), "experts": m.experts,
                      "top_k": m.top_k, "blocks": m.depth + m.mtp},
            "loss": float(loss), "reference_loss": want,
            "lm_nll": float(mine["lm_nll"]),
            "mtp_nll": float(mine["mtp_nll"]),
            "held_assignments_moved": moved,
            "grad_norm_worst_gap": round(worst, 5),
            "tokens_held": mine["tokens_held"].tolist()}


def _leg_olmo(on_tpu: bool, here: str) -> dict:
    """One forward and backward of the hybrid decoder cut to a
    linear-attention layer and a full-attention layer, bfloat16 worker math
    as the cell runs it, against the plain float32 reference the benchmark
    keeps (published widths on the chip, toy widths off it), and the
    observer's reading of the linear layer."""
    import jax
    import jax.numpy as jnp

    from minips_tpu.models import olmo_hybrid
    from minips_tpu.tables.dense import cast_floating

    bench = os.path.join(here, "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    from benchlib.reference import olmo_hybrid_ref

    with open(os.path.join(bench, "configs", "olmo-hybrid-7b.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2, layer_types=[
            olmo_hybrid.LINEAR, olmo_hybrid.FULL])
    B, T = 2, 1024
    if not on_tpu:
        config.update(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=48, linear_num_key_heads=2,
            linear_num_value_heads=2, linear_key_head_dim=8,
            linear_value_head_dim=16, vocab_size=128, head_chunk=16)
        T = 128
    m = olmo_hybrid.from_config(config)
    params = olmo_hybrid.init(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, m.vocab)
    cd = jnp.bfloat16
    how = dict(compute_dtype=cd, attn_impl="flash",
               head_chunk=int(config["head_chunk"]))
    # the step's loss and gradients and the observer's reading: one program
    (loss, grads), seen = jax.jit(lambda p, t: (
        olmo_hybrid.grad_fn(cast_floating(p, cd), {"tokens": t}, m, **how),
        olmo_hybrid.observe(p, {"tokens": t}, m, **how)))(params, toks)
    seen = jax.device_get(seen)
    z = olmo_hybrid_ref._sizes(config)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p, t: olmo_hybrid_ref.loss_sum(p, t, z, False)))(params, toks)
    want = float(want) / (B * T)
    _check(math.isfinite(float(loss)) and abs(float(loss) - want)
           < 2e-3 * want, f"olmo: loss {float(loss)} against the "
           f"reference's {want}")
    worst = _grad_norm_gap(grads, want_g, B * T)
    _check(worst < 0.05, f"olmo: a leaf's gradient norm is {worst:.4f} off "
           "the reference's (tolerance 0.05)")
    _check(0.0 < float(seen["beta_mean"][0]) < 2.0
           and 0.0 < float(seen["decay_mean"][0]) <= 1.0
           and math.isfinite(float(seen["state_absmax"][0])),
           f"olmo: the observer reads {seen}")
    return {"shape": {"B": B, "T": T, "dim": m.dim, "heads": m.heads,
                      "linear_heads": m.lin_heads, "key_head": m.lin_dk,
                      "value_head": m.lin_dv, "layers": list(m.layer_types)},
            "loss": float(loss), "reference_loss": want,
            "lm_nll": float(seen["lm_nll"]),
            "grad_norm_worst_gap": round(worst, 5),
            "decay_mean": seen["decay_mean"].tolist(),
            "beta_mean": seen["beta_mean"].tolist(),
            "state_absmax": seen["state_absmax"].tolist()}


def _leg_laguna(on_tpu: bool, here: str) -> dict:
    """One forward and backward of the window/full hybrid cut to a window
    layer and a full layer, both with experts, bfloat16 worker math as the
    cell runs it (the windowed kernels walk two majors of 2,048 rows at
    T 4,096), against the plain float32 reference the benchmark keeps
    (published widths and the cell's head share on the chip, toy widths
    off it), and the observer's reading of the routing and the gates."""
    import jax
    import jax.numpy as jnp

    from minips_tpu.models import laguna
    from minips_tpu.tables.dense import cast_floating

    bench = os.path.join(here, "bench")
    if bench not in sys.path:
        sys.path.append(bench)
    from benchlib.reference import laguna_ref

    with open(os.path.join(bench, "configs", "laguna-s-2.1.json")) as f:
        config = dict(json.load(f), num_hidden_layers=2,
                      layer_types=[laguna.WINDOW, laguna.FULL],
                      mlp_layer_types=["sparse", "sparse"],
                      mlp_only_layers=[], gating_types=["per_head"] * 2,
                      num_attention_heads_per_layer=[36, 24])
    B, T = 1, 4096
    if not on_tpu:
        config.update(
            hidden_size=32, head_dim=16, num_key_value_heads=2,
            num_attention_heads_per_layer=[6, 4], moe_intermediate_size=16,
            shared_expert_intermediate_size=16, sliding_window=8,
            vocab_size=128, head_chunk=16, reference_query_rows=16)
        T = 64
    m = laguna.from_config(config)
    params = laguna.init(jax.random.PRNGKey(0), m)
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T + 1), 0, m.vocab)
    cd = jnp.bfloat16
    how = dict(compute_dtype=cd, attn_impl="flash",
               head_chunk=int(config["head_chunk"]))
    bias = jnp.zeros((m.routers, m.experts), jnp.float32)
    (loss, grads, _), seen = jax.jit(lambda p, t: (
        laguna.grad_fn(cast_floating(p, cd), {"tokens": t}, bias, m, **how),
        laguna.routing_stats(p, {"tokens": t}, bias, m, **how)))(params,
                                                                 toks)
    seen = jax.device_get(seen)
    z = laguna_ref._sizes(config)
    want, want_g = jax.jit(jax.value_and_grad(
        lambda p, t: laguna_ref.loss_sum(p, t, bias, z, False)[0]))(params,
                                                                    toks)
    want = float(want) / (B * T)
    _check(math.isfinite(float(loss)) and abs(float(loss) - want)
           < 2e-3 * want, f"laguna: loss {float(loss)} against the "
           f"reference's {want}")
    worst = _grad_norm_gap(grads, want_g, B * T)
    _check(worst < 0.05, f"laguna: a leaf's gradient norm is {worst:.4f} "
           "off the reference's (tolerance 0.05)")
    gates = seen["gate_mean"]
    _check(all(0.0 < float(g) < 1.0 for g in gates)
           and int(seen["tokens_held"].sum()) > 0,
           f"laguna: the observer reads {seen}")
    return {"shape": {"B": B, "T": T, "dim": m.dim, "heads": list(m.heads),
                      "kv_heads": m.kv_heads, "window": m.window,
                      "layers": list(m.layer_types)},
            "loss": float(loss), "reference_loss": want,
            "lm_nll": float(seen["lm_nll"]),
            "grad_norm_worst_gap": round(worst, 5),
            "tokens_held": seen["tokens_held"].tolist(),
            "gate_mean": gates.tolist()}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny-size rehearsal of the control flow on 4 fake "
                         "CPU devices; the output says cpu")
    args = ap.parse_args()
    here = os.path.dirname(os.path.abspath(__file__))
    if not os.path.isdir(os.path.join(here, "minips_tpu")):
        raise SystemExit(
            f"chip_smoke: no minips_tpu package beside {__file__}: this "
            "script proves the checkout it sits in and nothing else")
    if args.rehearse_cpu:  # stated before jax is imported
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count=4")
    t0 = time.perf_counter()
    import jax

    devices = jax.devices()
    on_tpu = devices[0].platform == "tpu"
    if not on_tpu and not args.rehearse_cpu:
        raise SystemExit(
            f"chip_smoke: no TPU — JAX found platform "
            f"{devices[0].platform!r}. This script proves the program on "
            "the chip and has no fallback (--rehearse-cpu is the explicit "
            "tiny-size rehearsal)")

    import jaxlib

    from minips_tpu.utils import native_lib
    from minips_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    compile_log = _CompileLog()
    argv = _argv(full=on_tpu)

    legs = {"deepfm": _leg_deepfm(argv["deepfm"], devices, compile_log)}
    gc.collect()  # the first leg's tables go before the LM fills the chip
    legs["lm"] = _leg_lm(argv["lm"], devices, compile_log, on_tpu)
    gc.collect()
    legs["kernels"] = _leg_kernels(on_tpu)
    gc.collect()
    legs["zaya"] = _leg_zaya(on_tpu, here)
    gc.collect()
    legs["joyai"] = _leg_joyai(on_tpu, here)
    gc.collect()
    legs["olmo"] = _leg_olmo(on_tpu, here)
    gc.collect()
    legs["laguna"] = _leg_laguna(on_tpu, here)
    _check(not native_lib.loaded_libs(),
           f"a native library was loaded: {native_lib.loaded_libs()}")

    from importlib import metadata
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    print(json.dumps({
        "facts": "chip_smoke",
        "device": device,
        "rehearsal": "cpu" if args.rehearse_cpu else None,
        "versions": {"jax": jax.__version__, "jaxlib": jaxlib.__version__,
                     "libtpu": libtpu},
        "compile_cache_dir": cache_dir,
        "native_libs_loaded": native_lib.loaded_libs(),
        "wall_s": round(time.perf_counter() - t0, 1),
        "legs": legs,
    }))
    # the result, last on stdout, in the shape the chip check reads
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
