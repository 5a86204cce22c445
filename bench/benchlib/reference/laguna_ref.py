"""A Laguna-S-2.1-shaped decoder, plain: the reference of the ``laguna``
system. It imports nothing of the program. Weights arrive in the layout
the benchmark made them in (``tok_emb``, ``head``, ``ln_f``, ``blocks`` of
``ln1 ln2 attn{wq wk wv wg wo}`` and ``mlp`` or ``router shared
experts``).

The model, as ``bench/configs/laguna-s-2.1.json`` states it ([c]: pinned
by the published config.json; [a]: assumed, the file says why). With
residual x [b, T, d] and the layer's kind from ``layer_types`` [c]:

- attention: u = RMSNorm(x); q = u W_q (the layer's held heads,
  ``num_attention_heads_per_layer`` [c]), k = u W_k, v = u W_v (the
  ``num_key_value_heads`` held [c]), no biases [c]; q-head h reads kv head
  h // (heads / kv heads). Positions: a window layer rotates all
  ``head_dim`` channels at its own theta [c: sliding_attention]; a full
  layer rotates the first ``partial_rotary_factor`` of them by YaRN [c:
  full_attention: rope_type yarn, factor, original positions, beta_fast,
  beta_slow, attention_factor], the inverse frequency of pair i
  ``(1 - r_i) theta^(-2i/n) + r_i theta^(-2i/n) / factor``, r_i =
  clip((i - lo) / (hi - lo), 0, 1), lo = floor and hi = ceil of the pairs
  ``n ln(orig / (beta 2 pi)) / (2 ln theta)`` for beta_fast and beta_slow,
  cos and sin times attention_factor; the rest pass through. Pairs are
  rotate-half (i, i + n/2) [a]. Scores q k / sqrt(head_dim) under an
  explicit mask: s <= t on a full layer, t - sliding_window < s <= t on a
  window layer [c], by query blocks (``rows`` a block: [T, T] float32 is
  1 GB a head at T 16,384). Per-head gate [c: gating per-head]: g =
  sigmoid(u W_g), one number a head and token, from u and with no bias
  [a]; x <- x + concat_h(g_h a_h) W_o.
- ``mlp_layer_types`` dense [c]: x <- x + (silu(u W_g) * (u W_u)) W_d.
- sparse [c]: u = RMSNorm(x); s = sigmoid(u W_r) over ALL experts, float32
  [a: the score function]; the top ``num_experts_per_tok`` of s + b [c],
  b the balancing bias, one an expert and layer and no parameter (a
  departure, the file says why): it starts at minus each router's mean
  score over the first batch (``centred_bias``) and after every step
  rises by ``router_bias_rate`` where the expert got less than its layer's
  mean load and falls by it where more; gates the chosen UNBIASED scores
  over their sum [c: norm_topk_prob] times
  ``moe_routed_scaling_factor`` [c], multiplying the experts' outputs [c:
  moe_apply_router_weight_on_input false]; y = sum of the gated outputs
  of the chosen experts HELD here (0 of one that is not) + the shared
  expert's, ungated [a]; x <- x + y. No sort and no groups: every token
  goes through every held expert and its gate, 0 where not chosen, picks.
- ends: token embedding, final RMSNorm, an untied head [c], mean
  next-token cross-entropy over the rows held. Adam, no decay.

float32 at ``highest`` matmul precision, no kernels. The batch is walked in
blocks of rows, each layer is recomputed in the backward pass and the
scores are made one head and one query block at a time. ``low=True`` is
the control: bfloat16 activations and matmul inputs rounded to fp8 (e4m3)
after scaling each to the format's range, the step below the bfloat16 the
configuration states (the router stays float32). ``fault`` plants one of
``FAULTS``, an ARGUMENT of the compiled program, so that the sound
reference and every fault run one program: both forms of the piece are
computed and a ``where`` picks one.
"""

from __future__ import annotations

import functools
import math

FAULTS = ("no_gate", "window_wide", "no_yarn", "no_shared", "raw_gates",
          "top_k_less_one")
WINDOW, FULL = "sliding_attention", "full_attention"


def _planted(fault, name: str):
    return False if fault is None else fault == FAULTS.index(name) + 1


def _yarn(n: int, p: dict) -> list:
    theta, factor = float(p["rope_theta"]), float(p["factor"])
    orig = float(p["original_max_position_embeddings"])
    at = [n * math.log(orig / (float(p[b]) * 2 * math.pi))
          / (2 * math.log(theta)) for b in ("beta_fast", "beta_slow")]
    lo, hi = max(math.floor(at[0]), 0), min(math.ceil(at[1]), n - 1)
    ramp = [min(max((i - lo) / (hi - lo), 0.0), 1.0) for i in range(n // 2)]
    return [(1 - r) * theta ** (-2 * i / n) + r * theta ** (-2 * i / n)
            / factor for i, r in enumerate(ramp)]


def _sizes(config: dict) -> dict:
    depth = int(config["num_hidden_layers"])
    n_held = int(config["num_experts"])
    lo, hi = config.get("held_experts", (0, n_held))
    hd = int(config["head_dim"])
    ropes = config["rope_parameters"]
    full, win = ropes[FULL], ropes[WINDOW]
    n_full = int(hd * float(full["partial_rotary_factor"]))
    n_win = int(hd * float(win.get("partial_rotary_factor", 1)))
    return {"kinds": tuple(config["layer_types"][:depth]),
            "kv": int(config["num_key_value_heads"]), "hd": hd,
            "window": int(config["sliding_window"]),
            "eps": float(config["rms_norm_eps"]),
            "experts": int(config.get("published", {}).get(
                "num_experts", n_held)),
            "lo": int(lo), "hi": int(hi),
            "k": int(config["num_experts_per_tok"]),
            "scale": float(config["moe_routed_scaling_factor"]),
            "rate": float(config.get("router_bias_rate", 0.0)),
            "norm": bool(config["norm_topk_prob"]),
            "rows": int(config.get("reference_query_rows", 2048)),
            # rotary: channels, inverse frequencies, the factor on cos/sin;
            # "plain" is the full layers' without YaRN (a planted fault)
            "rope_win": (n_win, tuple(float(win["rope_theta"]) ** (-2 * i /
                                                                  n_win)
                                      for i in range(n_win // 2)), 1.0),
            "rope_full": (n_full, tuple(_yarn(n_full, full)),
                          float(full["attention_factor"])),
            "rope_plain": (n_full, tuple(float(full["rope_theta"]) ** (
                -2 * i / n_full) for i in range(n_full // 2)), 1.0)}


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


def _rotate(x, rope):
    """x [b, T, h, hd] float32: the first n channels rotated over the
    pairs (i, i + n/2), angle t * inv_freq_i, cos and sin times the
    factor; the others as they are."""
    import jax.numpy as jnp
    n, inv, factor = rope
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * factor)[None, :, None]
    sin = (jnp.sin(ang) * factor)[None, :, None]
    a, b = x[..., : n // 2], x[..., n // 2: n]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., n:]], -1)


def _head_attention(q, k, v, window, rows, wide, low):
    """One head: q, k, v [b, T, hd] -> [b, T, hd], query block by query
    block. ``window`` None: every key s <= t; else t - window < s <= t, or,
    where ``wide`` (the planted fault), every earlier key of the block's
    span, window + rows of them."""
    import jax
    import jax.numpy as jnp
    b, T, hd = q.shape
    rows = min(rows, T)
    scale = 1.0 / math.sqrt(hd)
    # a full layer's block reads every key; a window layer's the ``pad``
    # keys before its first query and its own, from K padded in front
    span = T if window is None else min(T, rows + window)
    pad = 0 if window is None else span - rows
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0)))

    def block(i):
        q0 = i * rows
        qb = jax.lax.dynamic_slice_in_dim(q, q0, rows, 1)
        start = 0 if window is None else q0
        kb = jax.lax.dynamic_slice_in_dim(kp, start, span, 1)
        vb = jax.lax.dynamic_slice_in_dim(vp, start, span, 1)
        t = q0 + jnp.arange(rows)[:, None]
        s_pos = start - pad + jnp.arange(span)[None, :]
        keep = (s_pos <= t) & (s_pos >= 0)
        if window is not None:
            keep = keep & jnp.where(wide, True, s_pos > t - window)
        s = _mm("bqd,bkd->bqk", qb, kb, low).astype(jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return _mm("bqk,bkd->bqd", p.astype(vb.dtype), vb, low)

    o = jax.lax.map(block, jnp.arange(T // rows))     # [blocks, b, rows, hd]
    return jnp.moveaxis(o, 0, 1).reshape(b, T, hd)


def attention(a, x, g1, kind, z, low, fault=None):
    """The attention sublayer's addition to the residual."""
    import jax
    import jax.numpy as jnp
    b, T, d = x.shape
    hd, G = z["hd"], z["kv"]
    H = a["wq"].shape[1] // hd
    r = H // G
    u = _rms(x, g1, z["eps"]).astype(x.dtype)
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    q = f32(_mm("btd,de->bte", u, a["wq"], low)).reshape(b, T, H, hd)
    k = f32(_mm("btd,de->bte", u, a["wk"], low)).reshape(b, T, G, hd)
    v = _mm("btd,de->bte", u, a["wv"], low).reshape(b, T, G, hd)
    if kind == FULL:
        plain = _planted(fault, "no_yarn")
        q = jnp.where(plain, _rotate(q, z["rope_plain"]),
                      _rotate(q, z["rope_full"]))
        k = jnp.where(plain, _rotate(k, z["rope_plain"]),
                      _rotate(k, z["rope_full"]))
    else:
        q, k = _rotate(q, z["rope_win"]), _rotate(k, z["rope_win"])
    q, k = q.astype(x.dtype), k.astype(x.dtype)
    one = jax.checkpoint(functools.partial(
        _head_attention, window=z["window"] if kind == WINDOW else None,
        rows=z["rows"], wide=_planted(fault, "window_wide"), low=low))
    o = jax.lax.map(lambda h: one(q[:, :, h], k[:, :, h // r],
                                  v[:, :, h // r]), jnp.arange(H))
    o = jnp.moveaxis(o, 0, 2)                               # [b, T, H, hd]
    gate = jax.nn.sigmoid(f32(_mm("btd,dh->bth", u, a["wg"], low)))
    gate = jnp.where(_planted(fault, "no_gate"), 1.0, gate)
    o = (f32(o) * gate[..., None]).astype(x.dtype).reshape(b, T, H * hd)
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
    chosen = jax.lax.top_k(s + jax.lax.stop_gradient(bias), z["k"])[1]
    # the fault of one choice too few: the last gets no gate and no count
    taken = jnp.where(on("top_k_less_one"),
                      jnp.arange(z["k"]) < z["k"] - 1, True)
    gate = jnp.take_along_axis(s, chosen, axis=1) * taken
    if z["norm"]:
        gate = jnp.where(on("raw_gates"), gate,
                         gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20))
    gate = gate * z["scale"]
    u = u.astype(x.dtype)
    ex = blk["experts"]
    held = jnp.arange(z["lo"], z["hi"])
    mine = jnp.sum(jnp.where(chosen[None] == held[:, None, None], gate, 0.0),
                   -1)                                          # [held, n]
    h = jax.nn.silu(_mm("nd,edf->enf", u, ex["w_gate"], low).astype(
        jnp.float32)) * _mm("nd,edf->enf", u, ex["w_up"], low).astype(
        jnp.float32)
    # the gate scales each expert's hidden row before its W_d (a linear
    # map), and one product sums over the experts: no [E, n, d] exists
    y = _mm("enf,efd->nd", (h * mine[:, :, None]).astype(u.dtype),
            ex["w_down"], low).astype(jnp.float32)
    sh = blk["shared"]
    y = y + jnp.where(on("no_shared"), 0.0, 1.0) * _swiglu(
        sh["w_gate"], sh["w_up"], sh["w_down"], u, low)
    loads = jnp.sum(jax.nn.one_hot(chosen, z["experts"], dtype=jnp.int32)
                    * taken[:, None].astype(jnp.int32), axis=(0, 1))
    return (y.astype(x.dtype).reshape(b, T, d), loads,
            jax.lax.stop_gradient(jnp.sum(s, axis=0)))


def _layer(x, blk, bias, kind, z, low, fault):
    import jax.numpy as jnp
    x = x + attention(blk["attn"], x, blk["ln1"]["g"], kind, z, low, fault)
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


def loss_sum(params, tokens, bias, z, low, fault=None):
    """Over the rows of ``tokens`` [b, T+1] under the balancing ``bias``
    [layers, E]: (the nll sum; (every expert layer's loads [layers, E],
    its sums of scores [layers, E]))."""
    import jax
    import jax.numpy as jnp
    if low:
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
    layer = jax.checkpoint(functools.partial(_layer, z=z, low=low,
                                             fault=fault),
                           static_argnums=(3,))
    x = params["tok_emb"][tokens[:, :-1]]
    loads, totals = [], []
    for blk, kind in zip(params["blocks"], z["kinds"]):
        x, load, total = layer(x, blk, bias[len(loads)], kind)
        if "mlp" not in blk:
            loads.append(load)
            totals.append(total)
    h = _rms(x, params["ln_f"]["g"], z["eps"]).astype(x.dtype)
    logits = _mm("btd,vd->btv", h, params["head"], low).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    return nll, (jnp.stack(loads), jnp.stack(totals))


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
        lambda p, t, b, f: loss_sum(p, t, b, z, low, f), has_aux=True))


def _program(z, low: bool):
    """The one compiled program of a run: ``(params, tokens, bias, fault
    number) -> ((nll sum, (loads, sums of scores)), gradients)``; one is
    kept at a time."""
    return _compiled(tuple(sorted(z.items())), bool(low))


def centred_bias(params, tokens, z, rows_per_block: int, program):
    """The balancing bias a run starts from, [layers, E] as a host array:
    minus each router's mean score over the first batch ``tokens`` [rows,
    T+1], layer by layer, since a layer's routing moves the next layer's
    scores. It reads the scores off the run's own ``program`` and lets
    the gradients go."""
    import numpy as np
    routers = sum("mlp" not in blk for blk in params["blocks"])
    bias = np.zeros((routers, z["experts"]), np.float32)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    for layer in range(routers):
        totals = 0
        for r in range(0, tokens.shape[0], rows_per_block):
            (_, (_, tot)), _ = program(params, tokens[r: r + rows_per_block],
                                       bias, 0)
            totals = totals + np.asarray(tot)
        bias[layer] = -totals[layer] / n
    return bias


def run(config: dict, batches: list, make_params, leaf_names, *,
        low: bool = False, keep: float = 1.0, fault=None,
        rows_per_block: int = 1, bias=None) -> dict:
    """Follow ``len(batches)`` steps; returns ``loss`` per step, ``grad``
    (norm of the first gradient per leaf), ``delta`` (norm of each leaf's
    change after the last step), ``loads`` (the first step's assignments,
    [layers, E]) and ``bias`` (the balancing bias the first step ran
    under: ``centred_bias`` of the first batch unless it is given).
    ``make_params()`` gives the benchmark's own
    initial weights (a pytree of device arrays); it is called again at the
    end, so that the start need not be kept beside the Adam state.
    ``keep`` < 1 plants the fault of a step that leaves part of its batch
    out (of a batch of one row, part of its tokens) and takes the mean over
    the rest; ``fault`` one of ``FAULTS``."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"laguna_ref: no fault {fault!r} (have {FAULTS})")
    z = _sizes(config)
    lr, b1, b2, eps = float(config["lr"]), 0.9, 0.999, 1e-8
    params = jax.tree.map(lambda x: jnp.array(x, jnp.float32),
                          make_params())
    # Adam's moments wait on the host between updates
    mu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    nu = jax.tree.map(lambda x: np.zeros(x.shape, np.float32), params)
    vg = _program(z, low)
    planted = 0 if fault is None else FAULTS.index(fault) + 1
    if bias is None:
        bias = centred_bias(params, jnp.asarray(batches[0]["tokens"]), z,
                            rows_per_block, vg)
    bias = np.array(bias, np.float32)

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
        if keep < 1.0 and toks.shape[0] > 1:
            toks = toks[: int(toks.shape[0] * keep)]
        elif keep < 1.0:        # a batch of one row: part of its tokens
            toks = toks[:, : int((toks.shape[1] - 1) * keep) + 1]
        n_rows, T = toks.shape[0], toks.shape[1] - 1
        total, grads, loads = 0.0, None, 0
        for r in range(0, n_rows, rows_per_block):
            (l, (lo, _)), g = vg(params, toks[r: r + rows_per_block], bias,
                                 planted)
            total = total + l
            loads = loads + np.asarray(lo)
            grads = g if grads is None else add(grads, g)
        denom = float(n_rows * T)       # the sums become means
        out["loss"].append(float(total) / denom)
        if t == 1:
            out["grad"] = {k: v / denom for k, v in
                           _leaf_norms(grads, leaf_names).items()}
            out["loads"] = loads
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
