"""A JoyAI-LLM-Flash-shaped decoder, plain: the reference of the ``joyai``
system. It imports nothing of the program. Weights arrive in the layout
the benchmark made them in (``tok_emb``, ``head``, ``ln_f``, ``blocks`` of
``ln1 ln2 attn{wq_a q_ln wq_b wkv_a kv_ln wkv_b wo}`` and ``mlp`` or
``router shared experts``, ``mtp{ln_h ln_e w_eh block ln_f}``).

The model, as ``bench/configs/joyai-llm-flash.json`` states it ([c]: pinned
by the published config.json; [r]: from the DeepSeek-V2/V3 reports,
arXiv:2405.04434 and arXiv:2412.19437, whose key names these are). With
residual x [b, T, d]:

- attention (MLA, training form, nothing absorbed): u = RMSNorm(x); c_q =
  RMSNorm(u W_qa) [c: q_lora_rank]; q = c_q W_qb, per head [q_nope |
  q_rope]; [c_kv | k_rope] = u W_kva, c_kv = RMSNorm(c_kv) [c:
  kv_lora_rank]; c_kv W_kvb gives per head [k_nope | v]; k_rope is one
  vector a token, shared by all heads; rotary positions on q_rope and
  k_rope over the interleaved pairs (2i, 2i + 1) [c: rope_interleave],
  angle t * theta^(-2i / rope_dim); per head softmax(q k^T / sqrt(nope +
  rope)), causal, by full scores; x <- x + concat_heads W_o.
- the first ``first_k_dense_replace`` layers: x <- x + MLP(RMSNorm(x)),
  MLP(u) = (silu(u W_g) * (u W_u)) W_d [c].
- the others: u = RMSNorm(x); s = sigmoid(u W_r) over ALL experts,
  float32 [c: scoring_func]; the choice is the top-k of s + b [c:
  topk_method noaux_tc; n_group = topk_group = 1, no group limit]; gates g
  = s[chosen] / sum(s[chosen]) * routed_scaling_factor [c]; y = sum_k g_k
  E_k(u) over the chosen experts HELD here (0 of one that is not) +
  E_shared(u); x <- x + y. No sort and no groups: every token goes
  through every held expert (one batched product over the stack) and a
  mask picks its own. b is no parameter
  [r: V3 section 2.1.2]: it starts at minus each router's mean score over
  the first batch (``centred_bias``; a departure, the file says why) and
  after every step rises by ``router_bias_rate`` where the expert got less
  than the mean load and falls by it where more, outside the gradient.
- multi-token prediction [r: V3 section 2.2, depth 1]: h'_i = W_eh
  [RMSNorm(h_i) ; RMSNorm(Emb(t_(i+1)))], h_i the last block's output
  before the final norm; one more block of the expert kind; a final norm
  of its own; the SHARED head and embedding; it predicts t_(i+2), so a
  row of T + 1 tokens gives T - 1 such targets, and the module runs over
  those T - 1 positions. loss = nll_main + mtp_loss_weight * nll_mtp.
- ends: token embedding, final RMSNorm, an untied head, mean next-token
  cross-entropy over the rows held. Adam, no decay.

float32 at ``highest`` matmul precision, no kernels. The batch is walked in
blocks of rows, each layer is recomputed in the backward pass and the
scores are made one head at a time (all its query rows: [T, T] float32 is
268 MB at T 8,192; [32, T, T] never exists), so that it fits beside its
own Adam state. ``low=True`` is the control: bfloat16 activations and
matmul inputs rounded to fp8 (e4m3) after scaling each to the format's
range, the step below the bfloat16 the configuration states (gradients
pass the rounding unchanged; the router stays float32 in it, as the
configuration states for every precision). ``fault`` plants one of
``FAULTS``: a piece of the mathematics left out or done otherwise. Which
one is an ARGUMENT of the compiled program (its number in ``FAULTS``, 0 for
none), so that the sound reference and every fault run one program: both
forms of the piece are computed and a ``where`` picks one.
"""

from __future__ import annotations

import functools
import math

FAULTS = ("no_mtp", "no_shared", "no_bias", "raw_gates", "k_rope_per_head",
          "top_k_less_one")


def _planted(fault, name: str):
    """Whether ``fault`` (None, or a traced number: 0 none, i + 1 for
    ``FAULTS[i]``) is the fault ``name``."""
    return False if fault is None else fault == FAULTS.index(name) + 1


def _sizes(config: dict) -> dict:
    n_held = int(config["n_routed_experts"])
    lo, hi = config.get("held_experts", (0, n_held))
    return {"heads": int(config["num_attention_heads"]),
            "q_rank": int(config["q_lora_rank"]),
            "kv_rank": int(config["kv_lora_rank"]),
            "nope": int(config["qk_nope_head_dim"]),
            "rope": int(config["qk_rope_head_dim"]),
            "v": int(config["v_head_dim"]),
            "theta": float(config["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "experts": int(config.get("published", {}).get(
                "n_routed_experts", n_held)),
            "lo": int(lo), "hi": int(hi),
            "k": int(config["num_experts_per_tok"]),
            "scale": float(config["routed_scaling_factor"]),
            "norm": bool(config["norm_topk_prob"]),
            "rate": float(config.get("router_bias_rate", 0.0)),
            "mtp_weight": float(config.get("mtp_loss_weight", 0.0))}


def _fp8(x):
    """``x`` (bfloat16) with fp8-e4m3's three bits of mantissa: scaled so
    that its largest entry is the format's largest (448), rounded, scaled
    back. The gradient passes as if nothing was rounded."""
    import jax
    import jax.numpy as jnp
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                                1e-30)
    rounded = ((x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
               .astype(jnp.float32) / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(eq, a, b, low):
    import jax
    import jax.numpy as jnp
    if low:
        return jnp.einsum(eq, _fp8(a), _fp8(b))
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rotate(x, theta):
    """Rotary positions on x [b, T, h, n] over the pairs (2i, 2i + 1),
    angle t * theta^(-2i / n), each pair rotated where it stands."""
    import jax.numpy as jnp
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(x.shape)


def _head_attention(q, k, v, scale, low):
    """One head: q, k [b, T, nope + rope], v [b, T, v_dim]."""
    import jax
    import jax.numpy as jnp
    T = q.shape[1]
    s = _mm("bqd,bkd->bqk", q, k, low).astype(jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                 -jnp.inf), axis=-1)
    return _mm("bqk,bkd->bqd", p.astype(v.dtype), v, low)


def attention(a, x, g1, z, low, fault=None):
    """The attention sublayer's addition to the residual."""
    import jax
    import jax.numpy as jnp
    b, T, _ = x.shape
    H, nope, rope = z["heads"], z["nope"], z["rope"]
    u = _rms(x, g1, z["eps"]).astype(x.dtype)
    c_q = _rms(_mm("btd,de->bte", u, a["wq_a"], low), a["q_ln"]["g"],
               z["eps"]).astype(x.dtype)
    q = _mm("bte,ef->btf", c_q, a["wq_b"], low).reshape(b, T, H, nope + rope)
    kv_a = _mm("btd,de->bte", u, a["wkv_a"], low)
    c_kv = _rms(kv_a[..., : z["kv_rank"]], a["kv_ln"]["g"],
                z["eps"]).astype(x.dtype)
    kv = _mm("bte,ef->btf", c_kv, a["wkv_b"], low).reshape(
        b, T, H, nope + z["v"])
    k_rope = jnp.broadcast_to(kv_a[..., None, z["kv_rank"]:],
                              (b, T, H, rope))
    if fault is not None:       # the fault: every head another vector
        k_rope = jnp.where(
            _planted(fault, "k_rope_per_head"),
            jnp.stack([jnp.roll(k_rope[:, :, h], h, axis=-1)
                       for h in range(H)], axis=2), k_rope)
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    q = jnp.concatenate([f32(q[..., :nope]),
                         _rotate(f32(q[..., nope:]), z["theta"])], -1)
    k = jnp.concatenate([f32(kv[..., :nope]),
                         _rotate(f32(k_rope), z["theta"])], -1)
    q, k, v = q.astype(x.dtype), k.astype(x.dtype), kv[..., nope:]
    # one head at a time, each recomputed in the backward pass
    one = jax.checkpoint(functools.partial(
        _head_attention, scale=1.0 / math.sqrt(nope + rope), low=low))
    o = jax.lax.map(lambda h: one(q[:, :, h], k[:, :, h], v[:, :, h]),
                    jnp.arange(H))
    o = jnp.moveaxis(o, 0, 2).reshape(b, T, H * z["v"])
    return _mm("bte,ed->btd", o, a["wo"], low).astype(x.dtype)


def _swiglu(w_gate, w_up, w_down, u, low):
    import jax
    import jax.numpy as jnp
    h = jax.nn.silu(_mm("nd,df->nf", u, w_gate, low).astype(jnp.float32)) \
        * _mm("nd,df->nf", u, w_up, low).astype(jnp.float32)
    return _mm("nf,fd->nd", h.astype(u.dtype), w_down, low).astype(
        jnp.float32)


def experts(blk, x, bias, z, low, fault=None):
    """(the expert sublayer's addition to the residual, the assignments of
    every expert [E], the sum of the tokens' scores [E]); ``bias`` [E]
    moves the choice alone."""
    import jax
    import jax.numpy as jnp
    b, T, d = x.shape
    u = _rms(x, blk["ln2"]["g"], z["eps"]).reshape(b * T, d)
    s = jax.nn.sigmoid(jnp.dot(u, blk["router"]["w"].astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    on = functools.partial(_planted, fault)
    ranked = s + jnp.where(on("no_bias"), 0.0, 1.0) \
        * jax.lax.stop_gradient(bias)
    chosen = jax.lax.top_k(ranked, z["k"])[1]
    # the fault of one choice too few: the last gets no gate and no count
    taken = jnp.where(on("top_k_less_one"),
                      jnp.arange(z["k"]) < z["k"] - 1, True)
    gate = jnp.take_along_axis(s, chosen, axis=1) * taken
    if z["norm"]:
        gate = jnp.where(on("raw_gates"), gate,
                         gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20))
    gate = gate * z["scale"]
    u = u.astype(x.dtype)
    # no sort and no groups: every token through every held expert, all of
    # them in one batched product, and a mask picks each token's own
    ex = blk["experts"]
    held = jnp.arange(z["lo"], z["hi"])
    mine = jnp.sum(jnp.where(chosen[None] == held[:, None, None], gate, 0.0),
                   -1)                                          # [held, n]
    h = jax.nn.silu(_mm("nd,edf->enf", u, ex["w_gate"], low).astype(
        jnp.float32)) * _mm("nd,edf->enf", u, ex["w_up"], low).astype(
        jnp.float32)
    y = jnp.sum(mine[:, :, None] * _mm(
        "enf,efd->end", h.astype(u.dtype), ex["w_down"], low).astype(
        jnp.float32), axis=0)
    sh = blk["shared"]
    y = y + jnp.where(on("no_shared"), 0.0, 1.0) * _swiglu(
        sh["w_gate"], sh["w_up"], sh["w_down"], u, low)
    loads = jnp.sum(jax.nn.one_hot(chosen, z["experts"], dtype=jnp.int32)
                    * taken[:, None].astype(jnp.int32), axis=(0, 1))
    return (y.astype(x.dtype).reshape(b, T, d), loads,
            jax.lax.stop_gradient(jnp.sum(s, axis=0)))


def _layer(x, blk, bias, z, low, fault):
    import jax.numpy as jnp
    x = x + attention(blk["attn"], x, blk["ln1"]["g"], z, low, fault)
    if "mlp" in blk:
        b, T, d = x.shape
        u = _rms(x, blk["ln2"]["g"], z["eps"]).astype(x.dtype)
        m = blk["mlp"]
        y = _swiglu(m["w_gate"], m["w_up"], m["w_down"],
                    u.reshape(b * T, d), low)
        none = jnp.zeros(z["experts"])
        return (x + y.astype(x.dtype).reshape(b, T, d),
                none.astype(jnp.int32), none)
    y, loads, total = experts(blk, x, bias, z, low, fault)
    return x + y, loads, total


def loss_sums(params, tokens, bias, z, low, fault=None):
    """Over the rows of ``tokens`` [b, T+1]: (main nll sum + mtp_weight *
    T / (T - 1) * mtp nll sum, so that dividing by the main targets' count
    gives the step's loss; (every router's loads [routers, E], sums of
    scores [routers, E], the main nll sum, the module's))."""
    import jax
    import jax.numpy as jnp
    if low:
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
    T = tokens.shape[1] - 1
    layer = jax.checkpoint(functools.partial(_layer, z=z, low=low,
                                             fault=fault))

    def nll_sum(h, g, targets):
        h = _rms(h, g, z["eps"]).astype(h.dtype)
        logits = _mm("btd,vd->btv", h, params["head"], low).astype(
            jnp.float32)
        logp = jax.nn.log_softmax(logits)
        return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))

    x = params["tok_emb"][tokens[:, :-1]]
    loads, totals, r = [], [], 0
    for blk in params["blocks"]:
        x, load, total = layer(x, blk, bias[r])
        if "mlp" not in blk:
            loads.append(load)
            totals.append(total)
            r += 1
    main = nll_sum(x, params["ln_f"]["g"], tokens[:, 1:])
    mtp = jnp.zeros(())
    if "mtp" in params:
        p = params["mtp"]
        e = params["tok_emb"][tokens[:, 1:T]]       # t_1 .. t_(T-1)
        both = jnp.concatenate(
            [_rms(x[:, : T - 1], p["ln_h"]["g"], z["eps"]),
             _rms(e, p["ln_e"]["g"], z["eps"])], -1).astype(x.dtype)
        xm = _mm("bte,ed->btd", both, p["w_eh"], low).astype(x.dtype)
        xm, load, total = layer(xm, p["block"], bias[r])
        loads.append(load)
        totals.append(total)
        mtp = jnp.where(_planted(fault, "no_mtp"), 0.0, 1.0) \
            * nll_sum(xm, p["ln_f"]["g"], tokens[:, 2:])
    return (main + z["mtp_weight"] * mtp * T / (T - 1),
            (jnp.stack(loads), jnp.stack(totals), main, mtp))


def _leaf_norms(tree, names) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {n: float(v) for n, v in zip(names, norms)}


@functools.lru_cache(maxsize=1)
def _compiled(sizes: tuple, low: bool):
    import jax
    z = dict(sizes)
    return jax.jit(jax.value_and_grad(
        lambda p, t, b, f: loss_sums(p, t, b, z, low, f), has_aux=True))


def _program(z, low: bool):
    """The one compiled program of a run: ``(params, tokens, bias, fault
    number) -> ((loss sum, aux), gradients)``. One is kept at a time: a
    process that follows the sound reference and its faults and then the
    control holds one program's code on the device, not two (434 MB each
    at the cell's sizes, beside 8 GB of state)."""
    return _compiled(tuple(sorted(z.items())), bool(low))


def centred_bias(params, tokens, z, rows_per_block: int, program=None):
    """The balancing bias a run starts from, [routers, E] as a host
    array: minus each router's mean score over the first batch ``tokens``
    [rows, T+1], layer by layer, since a layer's routing moves the next
    layer's scores. (The module's router sees T - 1 positions a row.) It
    reads the scores off ``program``, the run's own (``_program``), and
    lets the gradients go: a second, forward-only program would cost more
    to compile than these passes cost to run."""
    import numpy as np
    program = program or _program(z, False)
    routers = sum("mlp" not in blk for blk in params["blocks"]) \
        + ("mtp" in params)
    bias = np.zeros((routers, z["experts"]), np.float32)
    for layer in range(routers):
        loads, totals = 0, 0
        for r in range(0, tokens.shape[0], rows_per_block):
            (_, (lo, tot, _, _)), _ = program(
                params, tokens[r: r + rows_per_block], bias, 0)
            loads, totals = loads + np.asarray(lo), totals + np.asarray(tot)
        bias[layer] = -totals[layer] / (loads[layer].sum() / z["k"])
    return bias


def run(config: dict, batches: list, make_params, leaf_names, *,
        low: bool = False, keep: float = 1.0, fault=None,
        rows_per_block: int = 1, bias=None) -> dict:
    """Follow ``len(batches)`` steps; returns ``loss`` per step, ``grad``
    (norm of the first gradient per leaf), ``delta`` (norm of each leaf's
    change after the last step), ``loads`` (the first step's assignments,
    [routers, E]), ``nll`` (the first step's two losses) and ``bias`` (the
    balancing bias the first step ran under: ``centred_bias`` of the first
    batch unless it is given). ``make_params()`` gives the benchmark's own
    initial weights (a pytree of device arrays); it is called again at the
    end, so that the start need not be kept beside the Adam state.
    ``keep`` < 1 plants the fault of a step that leaves part of its batch
    out and takes the mean over the rest; ``fault`` one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"joyai_ref: no fault {fault!r} (have {FAULTS})")
    z = _sizes(config)
    lr, b1, b2, eps = float(config["lr"]), 0.9, 0.999, 1e-8
    # a copy: the steps below update it in place (donation)
    params = jax.tree.map(lambda x: jnp.array(x, jnp.float32),
                          make_params())
    # Adam's moments wait on the host between updates: beside the
    # parameters, two sets of gradients and the program's 5.5 GB of
    # temporaries they would fill the chip's 16 GB to the last 40 MB
    mu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    nu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    vg = _program(z, low)
    planted = 0 if fault is None else FAULTS.index(fault) + 1
    if bias is None:
        bias = centred_bias(params, jnp.asarray(batches[0]["tokens"]), z,
                            rows_per_block, vg)
    bias = np.array(bias, np.float32)

    # donated: parameters and moments are updated in place
    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def adam(params, mu, nu, g, t, denom):
        g = jax.tree.map(lambda x: x.astype(jnp.float32) / denom, g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
        return params, mu, nu

    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    out = {"loss": [], "grad": {}, "delta": {}, "bias": bias.copy()}
    for t, b in enumerate(batches, 1):
        toks = jnp.asarray(b["tokens"])
        if keep < 1.0:
            toks = toks[: int(toks.shape[0] * keep)]
        n_rows, T = toks.shape[0], toks.shape[1] - 1
        total, grads, loads, main, mtp = 0.0, None, 0, 0.0, 0.0
        for r in range(0, n_rows, rows_per_block):
            (l, (lo, _, m_, p_)), g = vg(params, toks[r: r + rows_per_block],
                                         bias, planted)
            total, main, mtp = total + l, main + m_, mtp + p_
            loads = loads + np.asarray(lo)
            grads = g if grads is None else add(grads, g)
        denom = float(n_rows * T)       # the sums become means
        out["loss"].append(float(total) / denom)
        if t == 1:
            out["grad"] = {k: v / denom for k, v in
                           _leaf_norms(grads, leaf_names).items()}
            out["loads"] = loads
            out["nll"] = {"lm_nll": float(main) / denom,
                          "mtp_nll": float(mtp) / (n_rows * (T - 1))}
        # the balancing bias of the next step, from this step's loads
        bias = (bias + z["rate"] * np.sign(
            loads.mean(-1, keepdims=True) - loads)).astype(np.float32)
        params, on_mu, on_nu = adam(
            params, jax.tree.map(jnp.asarray, mu),
            jax.tree.map(jnp.asarray, nu), grads, float(t), denom)
        del grads
        if t < len(batches):
            mu, nu = jax.device_get((on_mu, on_nu))
        del on_mu, on_nu
    del mu, nu
    out["delta"] = _leaf_norms(
        jax.tree.map(jnp.subtract, params, make_params()), leaf_names)
    return out
