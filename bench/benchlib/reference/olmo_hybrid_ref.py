"""An Olmo-Hybrid-shaped decoder, plain: the reference of the
``olmo_hybrid`` system. It imports nothing of the program. Weights arrive
in the layout the benchmark made them in (``tok_emb``, ``head``, ``ln_f``,
``blocks`` of ``ln1 ln2 mlp{w_gate w_up w_down}`` and ``linattn{wq wk wv wz
wa wb conv_q conv_k conv_v A_log dt_bias o_ln wo}`` or ``attn{wq wk wv q_ln
k_ln wo}``).

The model, as ``bench/configs/olmo-hybrid-7b.json`` states it ([c]: pinned
by the published config.json; [r]: from Gated Delta Networks,
arXiv:2412.06464, and its public implementation, whose ``linear_*`` key
names these are, and from the OLMo 2 report, arXiv:2501.00656). With the
residual x [b, T, d]:

- a block [r: OLMo 2's reordered norm]: h = x + RMSNorm(Mixer(x)); out =
  h + RMSNorm(MLP(h)), MLP(h) = (silu(h W_g) * (h W_u)) W_d [c]; the mixer
  sees the residual itself, the norm is on its OUTPUT.
- a ``linear_attention`` mixer [c: 30 heads, 96 key and 192 value
  channels, 4 taps, ``linear_allow_neg_eigval``]: q, k, v, z, a, b = x
  W_q, .., x W_b; q, k, v each through a causal depthwise convolution over
  time (no bias) and a SiLU [r]; per head q, k <- q/|q|, k/|k|, q <- q /
  sqrt(96) [r]; beta = 2 sigmoid(b) [c: doubled]; g = -exp(A_log)
  softplus(a + dt_bias) [r]. The state S [96, 192] a head starts at zero
  for every sequence and moves TOKEN BY TOKEN, which is all this file
  knows of the rule (no chunks, no triangular system)::

      S~ = exp(g_t) S_(t-1);  u_t = beta_t (v_t - S~^T k_t)
      S_t = S~ + k_t u_t^T;   o_t = S_t^T q_t

  y = (RMSNorm_192(o) * silu(z)) W_o, one gain of 192 [r].
- a ``full_attention`` mixer [c: 30 heads of 128, no bias, ``rope_theta``
  null]: q, k, v = x W_q, x W_k, x W_v; an RMSNorm with gain over the whole
  of q and of k before the split into heads [r: OLMo 2]; NO position
  signal; causal softmax(q k^T / sqrt(128)) by full scores; W_o.
- ends: token embedding, a final RMSNorm, an untied head, mean next-token
  cross-entropy over the rows held. Adam, no decay.

Departures from the paper and the report: none in the mathematics. The
paper trains with the chunked form and states the recurrence as its
meaning; this file is the recurrence. The report's model has rotary
positions in its attention; the published config of THIS model has
``rope_theta`` null, and the config wins.

float32 at ``highest`` matmul precision, no kernels. One sequence a
device at a time; each layer is recomputed in the backward pass; the recurrence is a
``lax.scan`` over tokens, rematerialised every 64 tokens ONLY so that its
backward pass fits (8,192 kept states of [30, 96, 192] float32 would be
18 GB; 128 are 283 MB): the 64 is no chunk of the mathematics; the scores
are made one head and one block of 2,048 query rows at a time ([30, T, T]
never exists).

Placement: at the cell's sizes the weights, the gradients and Adam's two
moments are 14.9 GB in float32, more than one chip holds. Every leaf of
those four trees is therefore laid over the cell's chips by a sharding
(``NamedSharding`` along the leaf's first axis that divides), a layer's
weights are gathered whole (``with_sharding_constraint`` to replicated)
where the layer is computed, and a step's sequences are laid over the
chips too, ``reference_rows`` a chip (where their number divides): every
chip runs whole sequences through whole weights, so no product, norm,
softmax or scan is split, and the placement decides the order of ONE sum,
that of the gradients (and losses) over the step's sequences. On one
device (the CPU tests) both shardings are the trivial one.

``low=True`` is the control, the step below each precision the
configuration states: bfloat16 weights and activations with matmul inputs
rounded to fp8 (e4m3) after scaling each to the format's range, where the
configuration states bfloat16 (gradients pass the rounding unchanged), AND
the state S and the decay exp(g) in bfloat16, where it states float32.
``low="state"`` is the second of these alone, everything else as the sound
reference has it: what a program that kept S in bfloat16 would read
(PERF.md section 6 has the reading at the cell's size). ``fault`` plants one of
``FAULTS``: a piece of the mathematics left out or done otherwise. Which
one is an ARGUMENT of the compiled program (its number in ``FAULTS``, 0 for
none), so that the sound reference and every fault run one program: both
forms of the piece are computed and a ``where`` picks one.
"""

from __future__ import annotations

import functools
import math

FAULTS = ("beta_not_doubled", "no_decay", "state_reset_every_chunk",
          "no_conv", "qk_not_normalised", "no_gate", "full_as_linear")
REMAT_TOKENS = 64       # the recurrence's backward keeps a state this often
QUERY_ROWS = 2048


def _planted(fault, name: str):
    """Whether ``fault`` (None, or a traced number: 0 none, i + 1 for
    ``FAULTS[i]``) is the fault ``name``."""
    return False if fault is None else fault == FAULTS.index(name) + 1


def _sizes(config: dict) -> dict:
    depth = int(config["num_hidden_layers"])
    return {"heads": int(config["num_attention_heads"]),
            "kinds": tuple(config["layer_types"][:depth]),
            "lin_heads": int(config["linear_num_key_heads"]),
            "dk": int(config["linear_key_head_dim"]),
            "dv": int(config["linear_value_head_dim"]),
            "neg": bool(config["linear_allow_neg_eigval"]),
            "eps": float(config["rms_norm_eps"])}


def _fp8(x):
    """``x`` (bfloat16) with fp8-e4m3's three bits of mantissa: scaled so
    that its largest entry is the format's largest (448), rounded, scaled
    back. The gradient passes as if nothing was rounded."""
    import jax
    import jax.numpy as jnp
    x = x.astype(jnp.bfloat16)
    scale = 448.0 / jnp.maximum(jnp.max(jnp.abs(x.astype(jnp.float32))),
                                1e-30)
    rounded = ((x.astype(jnp.float32) * scale).astype(jnp.float8_e4m3fn)
               .astype(jnp.float32) / scale).astype(x.dtype)
    return x + jax.lax.stop_gradient(rounded - x)


def _mm(eq, a, b, low):
    import jax
    import jax.numpy as jnp
    if low is True:
        return jnp.einsum(eq, _fp8(a), _fp8(b),
                          preferred_element_type=jnp.float32)
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms(x, g, eps):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _conv_silu(x, w):
    """y_t = sum_j w_j x_(t - 3 + j) over the taps w [taps, C], zero before
    the sequence's start; then SiLU."""
    import jax
    import jax.numpy as jnp
    taps, T = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j: j + T] * w[j].astype(jnp.float32)
                           for j in range(taps)))


def recurrence(q, k, v, g, beta, low=False, reset=False):
    """The gated delta rule token by token: q, k [b, T, H, Dk], v [b, T,
    H, Dv], g and beta [b, T, H] -> o [b, T, H, Dv], float32 (``low``: the
    state and the decay in bfloat16). ``reset`` (a traced bool) plants the
    fault of a state that starts again every ``REMAT_TOKENS`` tokens."""
    import jax
    import jax.numpy as jnp
    hi = jax.lax.Precision.HIGHEST
    b, T, H, Dk = q.shape
    keep = jnp.bfloat16 if low else jnp.float32

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t, first = x
        S = S * jnp.where(reset & first, 0.0, 1.0)
        decay = jnp.exp(g_t).astype(keep).astype(jnp.float32)
        S = S * decay[..., None, None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", S, k_t,
                                               precision=hi))
        S = (S + k_t[..., :, None] * u[..., None, :]).astype(keep).astype(
            jnp.float32)
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision=hi)

    @jax.checkpoint
    def stretch(S, xs):
        return jax.lax.scan(token, S, xs)

    n = math.gcd(T, REMAT_TOKENS)
    first = (jnp.arange(T) % REMAT_TOKENS) == 0
    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0).reshape(
        (T // n, n) + x.shape[:1] + x.shape[2:]) for x in (q, k, v, g, beta))
    xs += (first.reshape(T // n, n),)
    S0 = jnp.zeros((b, H, Dk, v.shape[-1]), jnp.float32)
    _, o = jax.lax.scan(stretch, S0, xs)
    return jnp.moveaxis(o.reshape((T,) + o.shape[2:]), 0, 1)


def linear_mixer(p, x, z, low, fault=None):
    import jax
    import jax.numpy as jnp
    b, T, _ = x.shape
    H, Dk, Dv = z["lin_heads"], z["dk"], z["dv"]
    on = functools.partial(_planted, fault)
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    proj = lambda w: f32(_mm("btd,de->bte", x, p[w], low))      # noqa: E731

    def mixed(raw, taps):       # the fault: no convolution, its SiLU kept
        return jnp.where(on("no_conv"), jax.nn.silu(raw),
                         _conv_silu(raw, taps))

    def unit(t):                # the fault: q and k as they come
        return jnp.where(on("qk_not_normalised"), t,
                         t / jnp.sqrt(jnp.sum(t * t, -1, keepdims=True)
                                      + 1e-6))

    q = unit(mixed(proj("wq"), p["conv_q"]).reshape(b, T, H, Dk)) \
        / math.sqrt(Dk)
    k = unit(mixed(proj("wk"), p["conv_k"]).reshape(b, T, H, Dk))
    v = mixed(proj("wv"), p["conv_v"]).reshape(b, T, H, Dv)
    beta = jax.nn.sigmoid(proj("wb")) * jnp.where(
        on("beta_not_doubled") | (not z["neg"]), 1.0, 2.0)
    g = jnp.where(on("no_decay"), 0.0, 1.0) * -jnp.exp(f32(p["A_log"])) \
        * jax.nn.softplus(proj("wa") + f32(p["dt_bias"]))
    if low is True:
        q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    o = recurrence(q, k, v, g, beta, bool(low),
                   reset=jnp.asarray(on("state_reset_every_chunk")))
    gate = jnp.where(on("no_gate"), 1.0,
                     jax.nn.silu(proj("wz")).reshape(b, T, H, Dv))
    y = (_rms(o, p["o_ln"]["g"], z["eps"]) * gate).reshape(b, T, H * Dv)
    return f32(_mm("bte,ed->btd", y.astype(x.dtype), p["wo"], low))


def _scores_block(q, k, v, rows, scale, low):
    """One head, one block of query rows: q [b, n, D] at the positions
    ``rows``, k and v [b, T, D]."""
    import jax
    import jax.numpy as jnp
    s = _mm("bqd,bkd->bqk", q, k, low).astype(jnp.float32) * scale
    s = jnp.where(rows[:, None] >= jnp.arange(k.shape[1])[None, :], s,
                  -jnp.inf)
    return _mm("bqk,bkd->bqd", jax.nn.softmax(s, axis=-1).astype(v.dtype), v,
               low)


def full_mixer(p, x, z, low, fault=None):
    import jax
    import jax.numpy as jnp
    b, T, d = x.shape
    H = z["heads"]
    D = d // H
    f32 = lambda t: t.astype(jnp.float32)   # noqa: E731
    q = _rms(_mm("btd,de->bte", x, p["wq"], low), p["q_ln"]["g"], z["eps"])
    k = _rms(_mm("btd,de->bte", x, p["wk"], low), p["k_ln"]["g"], z["eps"])
    v = f32(_mm("btd,de->bte", x, p["wv"], low))
    q, k, v = (t.astype(x.dtype).reshape(b, T, H, D) for t in (q, k, v))
    n = math.gcd(T, QUERY_ROWS)
    one = jax.checkpoint(functools.partial(
        _scores_block, scale=1.0 / math.sqrt(D), low=low))

    def head_block(i):
        h, r = i // (T // n), i % (T // n)
        rows = r * n + jnp.arange(n)
        take = lambda t: jnp.take(t, h, axis=2)     # noqa: E731
        return one(jax.lax.dynamic_slice_in_dim(take(q), r * n, n, axis=1),
                   take(k), take(v), rows)

    o = jax.lax.map(head_block, jnp.arange(H * (T // n)))  # [H T/n, b, n, D]
    o = jnp.moveaxis(f32(o).reshape(H, T // n, b, n, D), (0, 1), (3, 1)
                     ).reshape(b, T, d)
    if fault is not None:
        # the fault: the layer run as a fourth linear one (the delta rule
        # over its own q, k, v at unit length, beta 1, no decay)
        unit = lambda t: f32(t) / jnp.sqrt(jnp.sum(         # noqa: E731
            f32(t) ** 2, -1, keepdims=True) + 1e-6)
        ones = jnp.ones((b, T, H), jnp.float32)
        lin = recurrence(unit(q) / math.sqrt(D), unit(k), f32(v),
                         0.0 * ones, ones).reshape(b, T, d)
        o = jnp.where(_planted(fault, "full_as_linear"), lin, o)
    return f32(_mm("bte,ed->btd", o.astype(x.dtype), p["wo"], low))


def _layer(x, blk, gather, z, low, fault):
    import jax
    import jax.numpy as jnp
    blk = gather(blk)           # the layer's weights, whole on every chip
    if "attn" in blk:
        y = full_mixer(blk["attn"], x, z, low, fault)
    else:
        y = linear_mixer(blk["linattn"], x, z, low, fault)
    h = x + _rms(y, blk["ln1"]["g"], z["eps"]).astype(x.dtype)
    m = blk["mlp"]
    act = jax.nn.silu(_mm("btd,df->btf", h, m["w_gate"], low).astype(
        jnp.float32)) * _mm("btd,df->btf", h, m["w_up"], low).astype(
        jnp.float32)
    y = _mm("btf,fd->btd", act.astype(h.dtype), m["w_down"], low)
    return h + _rms(y, blk["ln2"]["g"], z["eps"]).astype(h.dtype)


def loss_sum(params, tokens, z, low, fault=None, gather=lambda t: t,
             weights=None):
    """The summed next-token NLL over the rows of ``tokens`` [b, T+1];
    ``weights`` [b] (ones and zeros) leaves rows out of the sum."""
    import jax
    import jax.numpy as jnp
    if low is True:
        params = jax.tree.map(
            lambda t: t.astype(jnp.bfloat16) if t.ndim > 1 else t, params)
    layer = jax.checkpoint(functools.partial(
        _layer, gather=gather, z=z, low=low, fault=fault))
    x = gather(params["tok_emb"])[tokens[:, :-1]]
    for blk in params["blocks"]:
        x = layer(x, blk)
    h = _rms(x, gather(params["ln_f"])["g"], z["eps"]).astype(x.dtype)
    logits = _mm("btd,vd->btv", h, gather(params["head"]), low).astype(
        jnp.float32)
    logp = jax.nn.log_softmax(logits)
    rows = -jnp.sum(jnp.take_along_axis(logp, tokens[:, 1:, None], -1),
                    axis=(1, 2))
    return jnp.sum(rows if weights is None else rows * weights)


def _leaf_norms(tree, names) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {n: float(v) for n, v in zip(names, norms)}


def placement(devices):
    """(``spread(leaf shape) -> sharding`` over ``devices`` along the first
    axis that divides, replicated where none does; the replicated
    sharding; ``rows(n) -> sharding`` of a step's n sequences, one share a
    device where n divides, replicated where not)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P
    mesh = Mesh(np.asarray(devices), ("ref",))
    n = len(devices)

    def spread(shape):
        for axis, size in enumerate(shape):
            if size % n == 0:
                return NamedSharding(mesh, P(*([None] * axis + ["ref"])))
        return NamedSharding(mesh, P())
    whole = NamedSharding(mesh, P())
    return spread, whole, lambda rows: (
        whole if rows % n else NamedSharding(mesh, P("ref")))


@functools.lru_cache(maxsize=1)
def _compiled(sizes: tuple, low, devices: tuple):
    """The one compiled program of a run: ``(params, tokens, the rows'
    weights, fault number) -> (loss sum, gradients)``, the gradients laid over the devices as the
    parameters are (left to the compiler they come out whole on every
    chip: 3.7 GB a chip at the cell's sizes)."""
    import jax
    z = dict(sizes)
    spread, whole, _ = placement(devices)
    gather = lambda t: jax.lax.with_sharding_constraint(   # noqa: E731
        t, jax.tree.map(lambda _: whole, t))

    def value_and_grad(p, t, w, f):
        total, g = jax.value_and_grad(
            lambda q: loss_sum(q, t, z, low, f, gather, w))(p)
        return total, jax.tree.map(
            lambda x: jax.lax.with_sharding_constraint(x, spread(x.shape)),
            g)
    return jax.jit(value_and_grad)


def run(config: dict, batches: list, make_params, leaf_names, *,
        low=False, keep: float = 1.0, fault=None,
        rows_per_block: int = 1, devices=None) -> dict:
    """Follow ``len(batches)`` steps; returns ``loss`` per step, ``grad``
    (norm of the first gradient per leaf) and ``delta`` (norm of each
    leaf's change after the last step). ``make_params()`` gives the
    benchmark's own initial weights (a pytree of arrays); it is called
    again at the end, so that the start need not be kept beside the Adam
    state. ``keep`` < 1 plants the fault of a step that leaves part of its
    batch out and takes the mean over the rest (a quarter: what chip 0
    alone computes when the exchange between four chips is left out); the
    rows left out weigh zero in the sound run's own program;
    ``fault`` one of ``FAULTS``; ``low`` True or ``"state"`` (the module's
    docstring). ``devices``: the chips the state is laid
    over (the module's docstring; default: the first device)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"olmo_hybrid_ref: no fault {fault!r} "
                         f"(have {FAULTS})")
    z = _sizes(config)
    lr, b1, b2, eps = float(config["lr"]), 0.9, 0.999, 1e-8
    devices = tuple(devices or jax.devices()[:1])
    spread, whole, rows = placement(devices)
    lay = jax.jit(lambda t: jax.tree.map(lambda x: x.astype(jnp.float32), t),
                  out_shardings=jax.tree.map(
                      lambda x: spread(x.shape), jax.eval_shape(make_params)))
    params = lay(make_params())     # a copy: the steps update it in place
    mu, nu = (jax.tree.map(jnp.zeros_like, params) for _ in range(2))
    low = low if low == "state" else bool(low)
    vg = _compiled(tuple(sorted(z.items())), low, devices)
    planted = 0 if fault is None else FAULTS.index(fault) + 1

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
    out = {"loss": [], "grad": {}, "delta": {}}
    block = rows_per_block * len(devices)   # rows_per_block a device
    for t, b in enumerate(batches, 1):
        toks = b["tokens"]
        n_rows, T = toks.shape[0], toks.shape[1] - 1
        # the fault of rows left out: they weigh nothing, in one program
        kept = n_rows if keep >= 1.0 else max(1, int(n_rows * keep))
        weights = (np.arange(n_rows) < kept).astype(np.float32)
        total, grads = 0.0, None
        for r in range(0, n_rows, block):
            part = rows(len(toks[r: r + block]))
            l, g = vg(params, jax.device_put(toks[r: r + block], part),
                      jax.device_put(weights[r: r + block], part), planted)
            total = total + float(l)
            grads = g if grads is None else add(grads, g)
        n_rows = kept
        denom = float(n_rows * T)       # the sums become means
        out["loss"].append(total / denom)
        if t == 1:
            out["grad"] = {k: v / denom for k, v in
                           _leaf_norms(grads, leaf_names).items()}
        params, mu, nu = adam(params, mu, nu, grads, float(t), denom)
        del grads
    del mu, nu
    out["delta"] = _leaf_norms(
        jax.jit(lambda p, p0: jax.tree.map(jnp.subtract, p, p0))(
            params, lay(make_params())), leaf_names)
    return out
