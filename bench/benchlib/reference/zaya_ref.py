"""A ZAYA1-shaped decoder, plain: the reference of the ``zaya`` system.
It imports nothing of the program. Weights arrive in the layout the
benchmark made them in (``tok_emb``, ``ln_f``, ``blocks`` of ``ln1 ln2 wq
wk wv1 wv2 conv_q_dw conv_k_dw conv_q_hd conv_k_hd k_temp wo router
experts``).

The layer, as ``bench/configs/zaya1-8b.json`` states it ([c]: pinned by the
published config.json; [r]: from the reports, CCA arXiv:2510.04476 and
ZAYA1 arXiv:2511.17127). With residual x [B, T, d]:

- attention: u = RMSNorm(x) [c]; latents q~ = u W_Q, k~ = u W_K [c]; value
  shift [r]: each key/value head's first half is u_t W_V1 and its second
  half u_(t-1) W_V2 (u_(-1) = 0); on q~ and k~ alike two causal
  convolutions [c: their taps; r: their form], depthwise s1_t = sum_j a_j *
  s_(t-j), then dense inside each head s2_t = sum_j s1_(t-j) A_j^(h); q-k
  mean [r]: q = s2(q~) + (q~ + rep(k~)) / 2, k = s2(k~) + (mean_group(q~)
  + k~) / 2; each head of q and k scaled to L2 norm sqrt(head_dim), k times
  a temperature, one a key/value head [r]; rotary positions on the first
  half of each head's channels [c]; causal grouped-query attention by full
  softmax scores, scale 1/sqrt(head_dim); x <- x + o W_O.
- experts: u = RMSNorm(x); r_l = u W_R + gamma_l * r_(l-1) (r_(-1) = 0)
  [r]; logits = gelu(gelu(RMSNorm(r_l) W_1) W_2) W_3, gelu in its tanh
  form; p = softmax over ALL experts; e = argmax(logits + bias) [r], the
  balancing bias a state that is no parameter: it starts at minus each
  router's mean logit over the first batch (``centred_bias``) and after
  every step each expert's bias rises by ``router_bias_rate`` times the
  share of the even load it fell short by, outside the gradient; y = p_e *
  (silu(u W_g^e) * (u W_u^e)) W_d^e where expert e is held here and 0
  where it is not; x <- x + y. No sort and no groups: every token goes
  through every held expert and a mask picks its own.
- ends: token embedding, final RMSNorm, the head tied to the embedding,
  mean next-token cross-entropy over the rows held. Adam, no decay.

float32 at ``highest`` matmul precision, no kernels. The batch is walked in
blocks of rows, each layer is recomputed in the backward pass and the
scores are made one head at a time, so that it fits beside its own Adam
state. ``low=True`` is the control: bfloat16 activations and matmul inputs
rounded to fp8 (e4m3) after scaling each to the format's range, the step
below the bfloat16 the configuration states (gradients pass the rounding
unchanged; the router stays float32 in it, as the configuration states
for every precision). ``capacity`` plants the fault of an expert layer
that drops: each expert takes its first ``capacity * tokens / experts``
tokens of a block of rows and the rest get nothing.
"""

from __future__ import annotations

import functools
import math


def _sizes(config: dict) -> dict:
    rope = config["rope_parameters"]["hybrid"]
    hd = int(config["head_dim"])
    n_held = int(config["num_experts"])
    lo, hi = config.get("held_experts", (0, n_held))
    return {"heads": int(config["num_attention_heads"]),
            "kv": int(config["num_key_value_heads"]), "hd": hd,
            "rotary": int(hd * float(rope["partial_rotary_factor"])),
            "theta": float(rope["rope_theta"]),
            "eps": float(config["rms_norm_eps"]),
            "experts": int(config.get("published", {}).get("num_experts",
                                                           n_held)),
            "lo": int(lo), "hi": int(hi),
            "rate": float(config.get("router_bias_rate", 0.0))}


def _mm(eq, a, b, low):
    import jax
    import jax.numpy as jnp
    if low:
        return jnp.einsum(eq, _fp8(a), _fp8(b))
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _fp8(x):
    """``x`` (bfloat16) with fp8-e4m3's three bits of mantissa: scaled so
    that its largest entry is the format's largest (448), rounded, scaled
    back. Unscaled, softmax weights of 8,192 keys fall under the format's
    smallest number and sums over them pass its largest. The gradient
    passes as if nothing was rounded."""
    import jax
    import jax.numpy as jnp
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                                1e-30)
    rounded = ((x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
               .astype(jnp.float32) / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


def _rms(x, g, eps):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _later(x, j):
    """x [b, T, ...] delayed by j steps of time, zeros first."""
    import jax.numpy as jnp
    if j == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:, :j]), x[:, :-j]], axis=1)


def _gelu(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _rotate(x, rotary, theta):
    """Rotary positions on the first ``rotary`` channels of x [b, T, h, hd],
    pairs (i, i + rotary / 2), angle t * theta^(-2i / rotary)."""
    import jax.numpy as jnp
    half = rotary // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    a, b = x[..., :half], x[..., half:rotary]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos,
                            x[..., rotary:]], -1)


def _convolve(s, dw, hd_w, heads, low):
    b, T, _ = s.shape
    s1 = sum(_later(s, j) * dw[j] for j in range(dw.shape[0]))
    s1 = s1.reshape(b, T, heads, -1)
    return sum(_mm("bthd,hde->bthe", _later(s1, j), hd_w[j], low)
               for j in range(hd_w.shape[0])).astype(s.dtype)


def _head_attention(q, k, v, scale, low):
    """One query head against its key/value head: [b, T, hd] each."""
    import jax
    import jax.numpy as jnp
    T = q.shape[1]
    s = _mm("bqd,bkd->bqk", q, k, low).astype(jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((T, T), bool)), s,
                                 -jnp.inf), axis=-1)
    return _mm("bqk,bkd->bqd", p.astype(v.dtype), v, low)


def attention(blk, x, z, low):
    """The attention sublayer's addition to the residual."""
    import jax
    import jax.numpy as jnp
    b, T, _ = x.shape
    H, K, hd = z["heads"], z["kv"], z["hd"]
    g = H // K
    u = _rms(x, blk["ln1"]["g"], z["eps"]).astype(x.dtype)
    q_lat = _mm("btd,de->bte", u, blk["wq"], low)
    k_lat = _mm("btd,de->bte", u, blk["wk"], low)
    v = jnp.concatenate(
        [_mm("btd,de->bte", u, blk["wv1"], low).reshape(b, T, K, -1),
         _mm("btd,de->bte", _later(u, 1), blk["wv2"], low
             ).reshape(b, T, K, -1)], -1)
    qh = q_lat.reshape(b, T, H, hd).astype(jnp.float32)
    kh = k_lat.reshape(b, T, K, hd).astype(jnp.float32)
    q = _convolve(q_lat, blk["conv_q_dw"], blk["conv_q_hd"], H, low) \
        + 0.5 * (qh + jnp.repeat(kh, g, axis=2))
    k = _convolve(k_lat, blk["conv_k_dw"], blk["conv_k_hd"], K, low) \
        + 0.5 * (jnp.mean(qh.reshape(b, T, K, g, hd), axis=3) + kh)
    unit = lambda y: y / jnp.sqrt(      # noqa: E731
        jnp.mean(y * y, -1, keepdims=True) + z["eps"])
    q = _rotate(unit(q.astype(jnp.float32)), z["rotary"], z["theta"])
    k = _rotate(unit(k.astype(jnp.float32)) * blk["k_temp"][:, None],
                z["rotary"], z["theta"])
    q, k = q.astype(x.dtype), k.astype(x.dtype)
    # one head at a time, each recomputed in the backward pass: a head's
    # scores at T 8,192 are 268 MB
    one = jax.checkpoint(functools.partial(
        _head_attention, scale=1.0 / math.sqrt(hd), low=low))
    o = jax.lax.map(lambda h: one(q[:, :, h], k[:, :, h // g],
                                  v[:, :, h // g]), jnp.arange(H))
    o = jnp.moveaxis(o, 0, 2).reshape(b, T, H * hd)
    return _mm("bte,ed->btd", o, blk["wo"], low).astype(x.dtype)


def router(rt, u, r_prev, eps):
    """(logits over all experts [n, E], the router's state), float32
    whatever the rest runs in."""
    import jax
    import jax.numpy as jnp
    mm = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    r = mm(f32(u), f32(rt["w_r"])) + f32(rt["gamma"]) * r_prev
    h = _rms(r, f32(rt["ln"]["g"]), eps)
    h = _gelu(mm(_gelu(mm(h, f32(rt["w1"]))), f32(rt["w2"])))
    return mm(h, f32(rt["w3"])), r


def experts(blk, x, r_prev, bias, z, low, capacity=None):
    """(the expert sublayer's addition to the residual, the router's
    state, each token's expert [n], the sum of the tokens' logits [E]);
    ``bias`` [E] moves the choice alone."""
    import jax
    import jax.numpy as jnp
    b, T, d = x.shape
    u = _rms(x, blk["ln2"]["g"], z["eps"]).reshape(b * T, d)
    logits, r = router(blk["router"], u, r_prev, z["eps"])
    e = jnp.argmax(logits + jax.lax.stop_gradient(bias), axis=-1)
    gate = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                               e[:, None], axis=1)[:, 0]
    if capacity is not None:        # the planted fault: overflow dropped
        onehot = jax.nn.one_hot(e, z["experts"], dtype=jnp.int32)
        place = jnp.sum((jnp.cumsum(onehot, axis=0) - 1) * onehot, -1)
        gate = jnp.where(place < int(capacity * b * T / z["experts"]),
                         gate, 0.0)
    u = u.astype(x.dtype)
    ex = blk["experts"]

    def one(w_gate, w_up, w_down, mine):
        h = jax.nn.silu(_mm("nd,df->nf", u, w_gate, low).astype(
            jnp.float32)) * _mm("nd,df->nf", u, w_up, low)
        return jnp.where(mine[:, None],
                         _mm("nf,fd->nd", h.astype(x.dtype), w_down, low),
                         0.0).astype(jnp.float32)

    y = jnp.zeros((b * T, d), jnp.float32)
    for i in range(z["hi"] - z["lo"]):
        y = y + one(ex["w_gate"][i], ex["w_up"][i], ex["w_down"][i],
                    e == z["lo"] + i)
    y = (y * gate[:, None]).astype(x.dtype)
    return (y.reshape(b, T, d), r, e,
            jax.lax.stop_gradient(jnp.sum(logits, axis=0)))


def hidden(params, tokens, bias, z, low, capacity=None):
    """(the final normed hidden state, each layer's expert choices, each
    layer's sum of logits over the tokens); ``bias`` [layers, E]."""
    import jax
    import jax.numpy as jnp
    if low:
        params = jax.tree.map(lambda t: t.astype(jnp.bfloat16), params)
    x = params["tok_emb"][tokens]
    r = jnp.zeros((tokens.size, params["blocks"][0]["router"]["w_r"]
                   .shape[1]), jnp.float32)

    @jax.checkpoint
    def layer(x, r, blk, b):
        x = x + attention(blk, x, z, low)
        y, r, e, total = experts(blk, x, r, b, z, low, capacity)
        return x + y, r, e, total

    chosen, totals = [], []
    for blk, b in zip(params["blocks"], bias):
        x, r, e, total = layer(x, r, blk, b)
        chosen.append(e)
        totals.append(total)
    return (_rms(x, params["ln_f"]["g"], z["eps"]).astype(x.dtype),
            jnp.stack(chosen), jnp.stack(totals))


def loss_sum(params, tokens, bias, z, low, capacity=None):
    """Sum (not mean) of next-token negative log-likelihoods over the rows
    of ``tokens`` [b, T+1], and each layer's expert choices."""
    import jax
    import jax.numpy as jnp
    x, chosen, _ = hidden(params, tokens[:, :-1], bias, z, low, capacity)
    emb = params["tok_emb"]
    if low:
        emb = emb.astype(jnp.bfloat16)
    logits = _mm("btd,vd->btv", x, emb, low).astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], -1))
    return nll, chosen


def _leaf_norms(tree, names) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {n: float(v) for n, v in zip(names, norms)}


def centred_bias(params, tokens, z, rows_per_block: int):
    """The balancing bias a run starts from, [layers, E] as a host array:
    minus each router's mean logit over the first batch ``tokens``
    [rows, T+1], layer by layer, since a layer's routing moves the next
    layer's logits."""
    import jax
    import numpy as np
    totals = jax.jit(lambda p, t, b: hidden(p, t[:, :-1], b, z, False)[2])
    layers = len(params["blocks"])
    bias = np.zeros((layers, z["experts"]), np.float32)
    n = tokens.shape[0] * (tokens.shape[1] - 1)
    for layer in range(layers):
        total = sum(np.asarray(totals(params, tokens[r: r + rows_per_block],
                                      bias))[layer]
                    for r in range(0, tokens.shape[0], rows_per_block))
        bias[layer] = -total / n
    return bias


def run(config: dict, batches: list, make_params, leaf_names, *,
        low: bool = False, keep: float = 1.0, capacity=None,
        rows_per_block: int = 1, bias=None) -> dict:
    """Follow ``len(batches)`` steps; returns ``loss`` per step, ``grad``
    (norm of the first gradient per leaf), ``delta`` (norm of each leaf's
    change after the last step), ``expert`` (the first step's choices,
    [layers, tokens] as a host array) and ``bias`` (the balancing bias the
    first step ran under: ``centred_bias`` of the first batch unless it
    is given). ``make_params()`` gives the benchmark's own initial weights
    (a pytree of device arrays); it is called again at the end, so that
    the start need not be kept beside the Adam state. ``keep`` < 1 plants
    the fault of a step that leaves part of its batch out and takes the
    mean over the rest."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    z = _sizes(config)
    lr, b1, b2, eps = float(config["lr"]), 0.9, 0.999, 1e-8
    # a copy: the steps below update it in place (donation)
    params = jax.tree.map(lambda x: jnp.array(x, jnp.float32),
                          make_params())
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(
        lambda p, t, b: loss_sum(p, t, b, z, low, capacity), has_aux=True))
    if bias is None:
        bias = centred_bias(params, jnp.asarray(batches[0]["tokens"]), z,
                            rows_per_block)
    bias = np.array(bias, np.float32)

    # donated: parameters and moments are updated in place; at 4 bytes a
    # parameter a copy is 2 GB, and five are alive at once
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
        total, grads, chosen = 0.0, None, []
        for r in range(0, n_rows, rows_per_block):
            (l, e), g = vg(params, toks[r: r + rows_per_block], bias)
            total = total + l
            grads = g if grads is None else add(grads, g)
            chosen.append(np.asarray(e))
        denom = float(n_rows * T)       # the sums become means
        out["loss"].append(float(total) / denom)
        if t == 1:
            out["grad"] = {k: v / denom for k, v in
                           _leaf_norms(grads, leaf_names).items()}
            out["expert"] = np.concatenate(chosen, axis=1)
        # the balancing bias of the next step, from this step's loads
        loads = np.stack([np.bincount(row, minlength=z["experts"])
                          for row in np.concatenate(chosen, axis=1)])
        bias = (bias + z["rate"] * (1.0 - loads * z["experts"] / denom)
                ).astype(np.float32)
        params, mu, nu = adam(params, mu, nu, grads, float(t), denom)
        del grads
    del mu, nu
    out["delta"] = _leaf_norms(
        jax.tree.map(jnp.subtract, params, make_params()), leaf_names)
    return out
