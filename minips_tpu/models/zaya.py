"""ZAYA1-shaped decoder: a block that is a function of its configuration.

The model of Zyphra's ZAYA1 reports (CCA: arXiv:2510.04476; ZAYA1:
arXiv:2511.17127) as ``config.json`` pins it and the reports describe it
(``bench/configs/zaya1-8b.json`` lists which is which). Every layer is an
attention sublayer and an expert sublayer on an RMSNorm'd float32 residual:

- compressed convolutional attention: q and k are projected into a latent
  (``heads x head_dim`` and ``kv_heads x head_dim``), mixed by two causal
  convolutions over time (depthwise, then dense inside each head), joined
  by the q-k mean, scaled to a fixed norm (k times a learned temperature),
  rotated on the first ``rotary_dim`` channels of each head, and attended
  causally with grouped queries; v is half this token's projection and
  half the previous token's (the value shift);
- a top-1 expert layer of gated-SiLU experts behind a small MLP router
  whose state passes from layer to layer; the choice is balanced by a
  bias that is no parameter: the step counts each expert's tokens and
  moves the bias for the next step, outside the gradient
  (``update_bias``; the table carries it, ``DenseTable.make_step``'s
  ``state``). The layer is told which experts of all it holds
  (``parallel/moe.moe_apply_dropless``) and computes their part of the
  result.

Plain-dict parameters like the other models, so the whole LM lives in one
``DenseTable`` and trains through ``DenseTable.make_step``; attention goes
through ``transformer._attn_fn`` and the tied head through
``transformer.nll_chunked``, the code the dense LM runs.
"""

from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.moe import moe_apply_dropless
from minips_tpu.tables.dense import cast_floating
from minips_tpu.utils import profiling as prof

_HIGHEST = jax.lax.Precision.HIGHEST


class Zaya(NamedTuple):
    """The sizes of a configuration file, static under jit."""
    vocab: int
    dim: int
    depth: int
    heads: int
    kv_heads: int
    head_dim: int
    taps0: int          # depthwise convolution over time
    taps1: int          # convolution dense inside each head
    rotary_dim: int
    rope_theta: float
    eps: float
    expert_width: int
    router_width: int
    experts: int        # the router's outputs
    held: tuple         # (lo, hi): the experts held here
    bias_rate: float    # the balancing bias's step, per unit of load error


def from_config(c: dict) -> Zaya:
    """The model of a configuration file with the published keys.
    ``num_experts`` counts the experts HELD here; where the file cuts it,
    ``published.num_experts`` is what the router knows and
    ``held_experts`` = [lo, hi) which of them these are."""
    for key, want in (("num_experts_per_tok", 1), ("hidden_act", "silu"),
                      ("tie_word_embeddings", True),
                      ("attention_bias", False)):
        if c.get(key, want) != want:
            raise ValueError(f"zaya: {key} = {c[key]!r} is not built "
                             f"(only {want!r})")
    depth = int(c["num_hidden_layers"])
    kinds = set(c.get("layer_types", ["hybrid"])[:depth])
    if kinds != {"hybrid"}:
        raise ValueError(f"zaya: layer types {sorted(kinds)}: only 'hybrid' "
                         "layers are built (no sliding window)")
    n_held = int(c["num_experts"])
    total = int(c.get("published", {}).get("num_experts", n_held))
    lo, hi = c.get("held_experts", (0, n_held))
    if hi - lo != n_held or not 0 <= lo < hi <= total:
        raise ValueError(f"zaya: held_experts [{lo}, {hi}) does not name "
                         f"{n_held} of {total} experts")
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd = int(c["head_dim"])
    rope = c["rope_parameters"]["hybrid"]
    rotary = int(hd * float(rope["partial_rotary_factor"]))
    if heads % kv or hd % 2 or rotary % 2:
        raise ValueError(f"zaya: heads {heads}/{kv} of {hd}, rotary "
                         f"{rotary}: kv must divide q, sizes must be even")
    return Zaya(int(c["vocab_size"]), int(c["hidden_size"]), depth,
                heads, kv, hd,
                int(c["cca_time0"]), int(c["cca_time1"]), rotary,
                float(rope["rope_theta"]), float(c["rms_norm_eps"]),
                int(c["moe_intermediate_size"]),
                int(c["router_hidden_size"]), total, (int(lo), int(hi)),
                float(c.get("router_bias_rate", 0.0)))


def init(key, m: Zaya, std: float = 0.02):
    """Normal weights of standard deviation ``std`` (the residual
    projections scaled down by sqrt(2 * depth)), gains one, the
    convolutions an identity tap plus noise, the router's depth-averaging
    ``gamma`` zero and the key temperature one."""
    d, hd, f, w = m.dim, m.head_dim, m.expert_width, m.router_width
    dq, dk, n_held = m.heads * hd, m.kv_heads * hd, m.held[1] - m.held[0]
    out_std = std / math.sqrt(2.0 * m.depth)
    norm = lambda k, shape, s: jax.random.normal(k, shape) * s  # noqa: E731
    k_emb, *k_blocks = jax.random.split(key, 1 + m.depth)

    def taps(k, n, shape, noise):
        first = jnp.zeros((n,) + shape).at[0].set(1.0)
        return first + norm(k, (n,) + shape, noise)

    def block(k):
        ks = iter(jax.random.split(k, 16))
        return {
            "ln1": {"g": jnp.ones(d)}, "ln2": {"g": jnp.ones(d)},
            "wq": norm(next(ks), (d, dq), std),
            "wk": norm(next(ks), (d, dk), std),
            "wv1": norm(next(ks), (d, dk // 2), std),
            "wv2": norm(next(ks), (d, dk // 2), std),
            "conv_q_dw": taps(next(ks), m.taps0, (dq,), 0.1),
            "conv_k_dw": taps(next(ks), m.taps0, (dk,), 0.1),
            "conv_q_hd": norm(next(ks), (m.taps1, m.heads, hd, hd),
                              hd ** -0.5),
            "conv_k_hd": norm(next(ks), (m.taps1, m.kv_heads, hd, hd),
                              hd ** -0.5),
            "k_temp": jnp.ones(m.kv_heads),
            "wo": norm(next(ks), (dq, d), out_std),
            "router": {
                "w_r": norm(next(ks), (d, w), std),
                "gamma": jnp.zeros(w), "ln": {"g": jnp.ones(w)},
                "w1": norm(next(ks), (w, w), w ** -0.5),
                "w2": norm(next(ks), (w, w), w ** -0.5),
                "w3": norm(next(ks), (w, m.experts), w ** -0.5)},
            "experts": {
                "w_gate": norm(next(ks), (n_held, d, f), std),
                "w_up": norm(next(ks), (n_held, d, f), std),
                "w_down": norm(next(ks), (n_held, f, d), out_std)},
        }

    return {"tok_emb": norm(k_emb, (m.vocab, d), std),
            "ln_f": {"g": jnp.ones(d)},
            "blocks": [block(k) for k in k_blocks]}


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _shift(x, j: int):
    """``x`` [B, T, ...] moved ``j`` steps later in time, zeros first."""
    if j == 0:
        return x
    pad = [(0, 0), (j, 0)] + [(0, 0)] * (x.ndim - 2)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def _convs(s, dw, hd_w, heads: int, compute_dtype):
    """The two causal convolutions on a latent ``s`` [B, T, heads * hd]:
    depthwise (``dw`` [taps, C]), then dense inside each head (``hd_w``
    [taps, heads, hd, hd]); returns float32 [B, T, heads, hd]."""
    B, T, _ = s.shape
    s1 = sum(_shift(s, j) * dw[j].astype(s.dtype)
             for j in range(dw.shape[0]))
    s1 = s1.reshape(B, T, heads, -1).astype(compute_dtype)
    return sum(jnp.einsum("bthd,hde->bthe", _shift(s1, j),
                          hd_w[j].astype(compute_dtype)).astype(jnp.float32)
               for j in range(hd_w.shape[0]))


def _unit(x, eps):
    """Each head scaled to L2 norm sqrt(head_dim)."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def _rope_part(x, pos, rotary: int, theta: float):
    return jnp.concatenate(
        [tfm.rope_rotate(x[..., :rotary], pos, theta), x[..., rotary:]], -1)


def cca_qkv(blk, u, pos, m: Zaya, compute_dtype):
    """q [B, T, heads, hd], k and v [B, T, kv_heads, hd] of the compressed
    convolutional attention, from the normed input ``u``."""
    B, T, _ = u.shape
    H, K, hd, g = m.heads, m.kv_heads, m.head_dim, m.heads // m.kv_heads
    u = u.astype(compute_dtype)
    q_lat = u @ blk["wq"].astype(compute_dtype)
    k_lat = u @ blk["wk"].astype(compute_dtype)
    with jax.named_scope(prof.LM_ATTN_CCA):
        v_now = (u @ blk["wv1"].astype(compute_dtype)).reshape(B, T, K, -1)
        v_prev = (_shift(u, 1) @ blk["wv2"].astype(compute_dtype)
                  ).reshape(B, T, K, -1)
        v = jnp.concatenate([v_now, v_prev], -1)
        qh = q_lat.reshape(B, T, H, hd).astype(jnp.float32)
        kh = k_lat.reshape(B, T, K, hd).astype(jnp.float32)
        q = _convs(q_lat, blk["conv_q_dw"], blk["conv_q_hd"], H,
                   compute_dtype) + 0.5 * (qh + jnp.repeat(kh, g, axis=2))
        k = _convs(k_lat, blk["conv_k_dw"], blk["conv_k_hd"], K,
                   compute_dtype) + 0.5 * (
            qh.reshape(B, T, K, g, hd).mean(3) + kh)
        q = _unit(q, m.eps)
        k = _unit(k, m.eps) * blk["k_temp"].astype(jnp.float32)[:, None]
        q = _rope_part(q, pos, m.rotary_dim, m.rope_theta)
        k = _rope_part(k, pos, m.rotary_dim, m.rope_theta)
    return q.astype(compute_dtype), k.astype(compute_dtype), v


def route(router, u, r_prev, m: Zaya):
    """The MLP router, float32 throughout: (logits [N, experts], the
    router's state [N, width] for the next layer)."""
    f32 = lambda x: x.astype(jnp.float32)   # noqa: E731
    mm = lambda a, b: jnp.dot(a, f32(b), precision=_HIGHEST)  # noqa: E731
    r = mm(f32(u), router["w_r"]) + f32(router["gamma"]) * r_prev
    z = _rms(r, router["ln"]["g"], m.eps)
    z = jax.nn.gelu(mm(jax.nn.gelu(mm(z, router["w1"])), router["w2"]))
    return mm(z, router["w3"]), r


def update_bias(bias, loads, rate: float):
    """The balancing bias after a step that sent ``loads`` [depth, experts]
    tokens to each expert: every expert's bias rises by ``rate`` times the
    share of the even load it fell short by (and falls by what it got too
    much). No gradient passes: the bias moves the choice only."""
    even = jnp.sum(loads, -1, keepdims=True) / loads.shape[-1]
    return bias + rate * (1.0 - loads / even)


def _block(h, r_prev, blk, bias, pos, m: Zaya, attn_fn, compute_dtype):
    """(residual, router state) -> (residual, router state, the experts
    chosen [B * T], the router's mean logits [experts]); ``bias``
    [experts] is added to the logits for the choice alone."""
    B, T, D = h.shape
    with jax.named_scope(prof.LM_ATTN):
        q, k, v = cca_qkv(blk, _rms(h, blk["ln1"]["g"], m.eps), pos, m,
                          compute_dtype)
        a = attn_fn(q, k, v).reshape(B, T, -1)
        h = h + (a @ blk["wo"].astype(compute_dtype)).astype(jnp.float32)
    with jax.named_scope(prof.LM_MOE):
        u = _rms(h, blk["ln2"]["g"], m.eps).reshape(B * T, D)
        with jax.named_scope(prof.LM_MOE_ROUTER):
            logits, r = route(blk["router"], u, r_prev, m)
            expert = jnp.argmax(logits + bias, axis=-1).astype(jnp.int32)
            gate = jnp.take_along_axis(jax.nn.softmax(logits, axis=-1),
                                       expert[:, None], 1)[:, 0]
        y = moe_apply_dropless(blk["experts"], u, expert, gate,
                               held=m.held, compute_dtype=compute_dtype)
        return (h + y.reshape(B, T, D), r, expert,
                jax.lax.stop_gradient(jnp.mean(logits, 0)))


def forward(params, tokens, m: Zaya, bias=None, *,
            compute_dtype=jnp.bfloat16, attn_impl="flash"):
    """(final normed hidden state [B, T, dim] float32, experts chosen
    [depth, B * T], the routers' mean logits [depth, experts]). ``bias``
    [depth, experts] float32 is the balancing bias (none: zeros)."""
    B, T = tokens.shape
    pos = jnp.arange(T)
    attn_fn = tfm._attn_fn(attn_impl)
    if bias is None:
        bias = jnp.zeros((m.depth, m.experts), jnp.float32)
    with jax.named_scope(prof.LM_EMBED):
        h = params["tok_emb"][tokens].astype(jnp.float32)
    r = jnp.zeros((B * T, m.router_width), jnp.float32)
    chosen, means = [], []
    for blk, b in zip(params["blocks"], bias):
        h, r, expert, mean = _block(h, r, blk, jax.lax.stop_gradient(b),
                                    pos, m, attn_fn, compute_dtype)
        chosen.append(expert)
        means.append(mean)
    with jax.named_scope(prof.LM_HEAD):
        return (_rms(h, params["ln_f"]["g"], m.eps), jnp.stack(chosen),
                jnp.stack(means))


def _loss(params, batch, m: Zaya, bias, *, compute_dtype, attn_impl,
          head_chunk):
    toks = batch["tokens"]
    h, expert, _ = forward(params, toks[:, :-1], m, bias,
                           compute_dtype=compute_dtype, attn_impl=attn_impl)
    if head_chunk:
        return tfm.nll_chunked(h, params["tok_emb"], toks[:, 1:],
                               head_chunk, compute_dtype), expert
    with jax.named_scope(prof.LM_HEAD):
        logits = (h.astype(compute_dtype)
                  @ params["tok_emb"].T.astype(compute_dtype))
    return tfm.nll(logits.astype(jnp.float32), toks[:, 1:]), expert


def loss(params, batch, m: Zaya, bias=None, *, compute_dtype=jnp.bfloat16,
         attn_impl="flash", head_chunk=0):
    """Mean next-token cross-entropy over the vocabulary rows held;
    batch = {"tokens": [B, T+1] int32}."""
    return _loss(params, batch, m, bias, compute_dtype=compute_dtype,
                 attn_impl=attn_impl, head_chunk=head_chunk)[0]


def _loads(expert, m: Zaya):
    """Tokens of each expert by layer: [depth, N] -> [depth, experts]."""
    return jnp.sum(expert[:, :, None] == jnp.arange(m.experts), axis=1)


def grad_fn(params, batch, bias, m: Zaya, *, axis_name=None,
            compute_dtype=jnp.bfloat16, attn_impl="flash", head_chunk=0):
    """(loss, gradients, the balancing bias for the next step): the step's
    own routing, counted over every worker of ``axis_name``, moves the
    bias (``update_bias``), outside the gradient."""
    (nll, expert), grads = jax.value_and_grad(
        lambda p: _loss(p, batch, m, bias, compute_dtype=compute_dtype,
                        attn_impl=attn_impl, head_chunk=head_chunk),
        has_aux=True)(params)
    loads = _loads(expert, m)
    if axis_name is not None:
        loads = jax.lax.psum(loads, axis_name)
    return nll, grads, update_bias(bias, loads, m.bias_rate)


def routing_stats(params, batch, bias, m: Zaya, *,
                  compute_dtype=jnp.bfloat16, attn_impl="flash"):
    """The routing observer, jitted apart from the step: for the batch's
    tokens under the balancing ``bias``, per layer, how many each held
    expert gets (``tokens_held`` [depth, held]), the share sent to experts
    that live elsewhere (``absent_share`` [depth]), the fullest expert's
    load over the mean load of ALL experts (``load_max_over_mean``
    [depth]), each token's choice (``expert`` [depth, B * T]) and the
    routers' mean logits (``mean_logit`` [depth, experts]). ``params`` are
    cast as the step's pull casts them."""
    p = cast_floating(params, compute_dtype)
    _, expert, mean = forward(p, batch["tokens"][:, :-1], m, bias,
                              compute_dtype=compute_dtype,
                              attn_impl=attn_impl)
    loads = _loads(expert, m)
    held = loads[:, m.held[0]: m.held[1]]
    n = expert.shape[1]
    return {"tokens_held": held,
            "absent_share": 1.0 - jnp.sum(held, 1) / n,
            "load_max_over_mean": jnp.max(loads, 1) * m.experts / n,
            "expert": expert, "mean_logit": mean}


def centred_bias(stats, m: Zaya):
    """The balancing bias a run starts from: minus each router's mean
    logit over a first batch, layer by layer (a layer's routing moves the
    next layer's logits), so that the choice starts from what tells the
    tokens apart and not from an offset all of them share. ``stats(bias)``
    is the routing observer over that batch. From zero the step-by-step
    rule gets there too, ``offset / rate`` steps later."""
    bias = jnp.zeros((m.depth, m.experts), jnp.float32)
    for layer in range(m.depth):
        bias = bias.at[layer].set(-stats(bias)["mean_logit"][layer])
    return bias
