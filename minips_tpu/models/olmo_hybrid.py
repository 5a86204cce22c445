"""Hybrid decoder of linear-attention and full-attention layers: a model
that is a function of its configuration file (``model_type``
``olmo_hybrid``; the ``linear_*`` key names are the Gated Delta Networks
implementation's, arXiv:2412.06464, the block is OLMo 2's,
arXiv:2501.00656, and ``bench/configs/olmo-hybrid-7b.json`` lists which
form the config pins and which the reports give).

``layer_types`` says, layer by layer, which mixer a block has; every block
is ``h = x + RMSNorm(Mixer(x))``, ``out = h + RMSNorm(MLP(h))`` (the norm
on the sublayer's OUTPUT) on a float32 residual, the MLP a SwiGLU:

- ``linear_attention``: q, k (``linear_num_key_heads`` heads of
  ``linear_key_head_dim``), v and an output gate z
  (``linear_num_value_heads`` heads of ``linear_value_head_dim``) and two
  numbers a head, a and b, are projections of x; q, k and v each pass a
  causal depthwise convolution over ``linear_conv_kernel_dim`` tokens and
  a SiLU; q and k are scaled to unit length a head (q by ``Dk^-0.5``
  more); the log-decay is ``g = -exp(A_log) softplus(a + dt_bias)`` and
  the write strength ``beta = sigmoid(b)``, doubled where
  ``linear_allow_neg_eigval`` (so that the state's eigenvalue along k,
  ``exp(g) (1 - beta)``, reaches into (-1, 0)); the gated delta rule
  (``ops/delta_rule.py``) runs over them, every sequence from a zero
  state; its output is normed over a head's channels, gated by
  ``silu(z)`` and projected back.
- ``full_attention``: q, k, v projections, an RMSNorm with gain over the
  whole of q and of k before the split into heads, NO position signal
  (``rope_theta`` null: the linear layers before it carry order), causal
  softmax attention through ``transformer._attn_fn`` (the flash kernels).

Plain-dict parameters like the other models, so the whole LM lives in one
``DenseTable`` and trains through ``DenseTable.make_step``; the untied head
goes through ``transformer.nll_chunked``; every block is recomputed in the
backward pass but for what the flash forward kernel and the delta rule's
forward scan leave for their backward passes
(``transformer._remat_policy("attn")`` and ``profiling.GDN_RESIDUALS``):
the one mode the model has. The model carries nothing from step to step
beside its parameters and has no router: ``grad_fn`` takes and hands on no
state, and its observer (``observe``) reads the linear layers' decay, write
strength and state.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from minips_tpu.models import transformer as tfm
from minips_tpu.ops import delta_rule
from minips_tpu.tables.dense import cast_floating
from minips_tpu.utils import profiling as prof

MODEL_TYPE = "olmo_hybrid"
LINEAR, FULL = "linear_attention", "full_attention"


class OlmoHybrid(NamedTuple):
    """The sizes of a configuration file, static under jit."""
    vocab: int
    dim: int
    layer_types: tuple  # one of LINEAR, FULL a layer
    heads: int          # full attention: heads of dim / heads channels
    lin_heads: int
    lin_dk: int
    lin_dv: int
    taps: int           # the causal convolutions' kernel
    neg_eigval: bool    # beta doubled
    mlp_width: int
    eps: float

    @property
    def depth(self) -> int:
        return len(self.layer_types)


def from_config(c: dict) -> OlmoHybrid:
    """The model of a configuration file with the published keys. The first
    ``num_hidden_layers`` entries of ``layer_types`` are the layers built."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("attention_bias", False)):
        if c.get(key, want) != want:
            raise ValueError(f"olmo_hybrid: {key} = {c[key]!r} is not built "
                             f"(only {want!r})")
    if (c.get("rope_parameters") or {}).get("rope_theta") is not None:
        raise ValueError("olmo_hybrid: a rope_theta is not built: the full "
                         "layers carry no position signal")
    depth, heads = int(c["num_hidden_layers"]), int(c["num_attention_heads"])
    kinds = tuple(c["layer_types"][:depth])
    lin_heads = int(c["linear_num_key_heads"])
    if len(kinds) != depth or set(kinds) - {LINEAR, FULL} \
            or int(c["num_key_value_heads"]) != heads \
            or int(c["linear_num_value_heads"]) != lin_heads \
            or int(c["hidden_size"]) % heads:
        raise ValueError(
            f"olmo_hybrid: {depth} layers of kinds {sorted(set(kinds))} "
            f"({len(c['layer_types'])} named), {heads} heads over "
            f"{c['num_key_value_heads']} key/value heads, "
            f"{lin_heads} linear key heads over "
            f"{c['linear_num_value_heads']} value heads: every layer a "
            "named kind, one key/value head a query head")
    return OlmoHybrid(
        int(c["vocab_size"]), int(c["hidden_size"]), kinds, heads, lin_heads,
        int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"]),
        int(c["linear_conv_kernel_dim"]), bool(c["linear_allow_neg_eigval"]),
        int(c["intermediate_size"]), float(c["rms_norm_eps"]))


def init(key, m: OlmoHybrid, std: float = 0.02):
    """Normal weights of standard deviation ``std`` (the residual
    projections scaled down by sqrt(2 * layers)), the convolutions' taps
    ``taps^-0.5``, gains one; ``A_log`` the log of a uniform draw in
    (0, 16) and ``dt_bias`` the inverse softplus of a log-uniform draw in
    (1e-3, 1e-1), a head (the Gated Delta Networks implementation's)."""
    d, H, Dk, Dv = m.dim, m.lin_heads, m.lin_dk, m.lin_dv
    out_std = std / math.sqrt(2.0 * m.depth)
    norm = lambda k, shape, s: jax.random.normal(k, shape) * s  # noqa: E731
    gain = lambda n: {"g": jnp.ones(n)}                         # noqa: E731
    k_emb, k_head, *k_blocks = jax.random.split(key, 2 + m.depth)

    def block(k, kind):
        ks = iter(jax.random.split(k, 16))
        blk = {"ln1": gain(d), "ln2": gain(d), "mlp": {
            "w_gate": norm(next(ks), (d, m.mlp_width), std),
            "w_up": norm(next(ks), (d, m.mlp_width), std),
            "w_down": norm(next(ks), (m.mlp_width, d), out_std)}}
        if kind == FULL:
            blk["attn"] = {"wq": norm(next(ks), (d, d), std),
                           "wk": norm(next(ks), (d, d), std),
                           "wv": norm(next(ks), (d, d), std),
                           "q_ln": gain(d), "k_ln": gain(d),
                           "wo": norm(next(ks), (d, d), out_std)}
            return blk
        dt = jnp.exp(jax.random.uniform(next(ks), (H,), minval=math.log(1e-3),
                                        maxval=math.log(1e-1)))
        blk["linattn"] = {
            "wq": norm(next(ks), (d, H * Dk), std),
            "wk": norm(next(ks), (d, H * Dk), std),
            "wv": norm(next(ks), (d, H * Dv), std),
            "wz": norm(next(ks), (d, H * Dv), std),
            "wa": norm(next(ks), (d, H), std),
            "wb": norm(next(ks), (d, H), std),
            "conv_q": norm(next(ks), (m.taps, H * Dk), m.taps ** -0.5),
            "conv_k": norm(next(ks), (m.taps, H * Dk), m.taps ** -0.5),
            "conv_v": norm(next(ks), (m.taps, H * Dv), m.taps ** -0.5),
            "A_log": jnp.log(jax.random.uniform(next(ks), (H,), minval=1e-3,
                                                maxval=16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
            "o_ln": gain(Dv),
            "wo": norm(next(ks), (H * Dv, d), out_std)}
        return blk

    return {"tok_emb": norm(k_emb, (m.vocab, d), std),
            "head": norm(k_head, (m.vocab, d), std),
            "ln_f": gain(d),
            "blocks": [block(k, kind)
                       for k, kind in zip(k_blocks, m.layer_types)]}


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _unit(x):
    """x over its length along the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _causal_conv(x, w):
    """Depthwise convolution over time of x [B, T, C] (float32) by the
    taps w [taps, C]: y_t = sum_j w_j x_(t - taps + 1 + j), nothing from
    before the sequence's start, no bias; then SiLU."""
    taps, T = w.shape[0], x.shape[1]
    w = w.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[:, j: j + T] * w[j] for j in range(taps)))


def linear_mixer(p, x, m: OlmoHybrid, compute_dtype, observed=None):
    """The linear-attention sublayer's output [B, T, dim] (float32) from
    the residual ``x``. ``observed`` (a dict, the observer's) is filled
    with the layer's mean decay, mean write strength and the largest entry
    of its state at any chunk's start or at the sequence's end."""
    B, T, _ = x.shape
    H, Dk, Dv = m.lin_heads, m.lin_dk, m.lin_dv
    u = x.astype(compute_dtype)
    mm = lambda w: jnp.dot(u, w.astype(compute_dtype),          # noqa: E731
                           preferred_element_type=jnp.float32)
    with jax.named_scope(prof.LM_LINATTN_PROJ):
        q, k, v, z, a, b = (mm(p[w]) for w in
                            ("wq", "wk", "wv", "wz", "wa", "wb"))
    with jax.named_scope(prof.LM_LINATTN_CONV):
        q = _causal_conv(q, p["conv_q"]).reshape(B, T, H, Dk)
        k = _causal_conv(k, p["conv_k"]).reshape(B, T, H, Dk)
        v = _causal_conv(v, p["conv_v"]).reshape(B, T, H, Dv)
    with jax.named_scope(prof.LM_LINATTN_SCAN):
        q, k = _unit(q) * Dk ** -0.5, _unit(k)
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            a + p["dt_bias"].astype(jnp.float32))
        beta = jax.nn.sigmoid(b) * (2.0 if m.neg_eigval else 1.0)
        q, k, v = (t.astype(compute_dtype) for t in (q, k, v))
    if observed is None:
        o = delta_rule.gated_delta_rule(q, k, v, g, beta)
    else:
        o, states, last = delta_rule.chunk_states(q, k, v, g, beta)
        observed.update(
            decay_mean=jnp.mean(jnp.exp(g)), beta_mean=jnp.mean(beta),
            state_absmax=jnp.maximum(jnp.max(jnp.abs(states)),
                                     jnp.max(jnp.abs(last))))
    with jax.named_scope(prof.LM_LINATTN_GATE):
        y = _rms(o, p["o_ln"]["g"], m.eps) \
            * jax.nn.silu(z.reshape(B, T, H, Dv))
        y = y.reshape(B, T, H * Dv).astype(compute_dtype)
    with jax.named_scope(prof.LM_LINATTN_PROJ):
        return jnp.dot(y, p["wo"].astype(compute_dtype),
                       preferred_element_type=jnp.float32)


def full_mixer(p, x, m: OlmoHybrid, attn_fn, compute_dtype):
    """The full-attention sublayer's output [B, T, dim] (float32)."""
    B, T, D = x.shape
    u = x.astype(compute_dtype)
    mm = lambda w: jnp.dot(u, w.astype(compute_dtype),          # noqa: E731
                           preferred_element_type=jnp.float32)
    heads = lambda t: t.astype(compute_dtype).reshape(          # noqa: E731
        B, T, m.heads, D // m.heads)
    q = _rms(mm(p["wq"]), p["q_ln"]["g"], m.eps)
    k = _rms(mm(p["wk"]), p["k_ln"]["g"], m.eps)
    a = attn_fn(heads(q), heads(k), heads(mm(p["wv"]))).reshape(B, T, D)
    return jnp.dot(a, p["wo"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)


def _block(h, blk, m: OlmoHybrid, attn_fn, compute_dtype, observed=None):
    if "attn" in blk:
        with jax.named_scope(prof.LM_ATTN):
            y = full_mixer(blk["attn"], h, m, attn_fn, compute_dtype)
            h = h + _rms(y, blk["ln1"]["g"], m.eps)
    else:
        with jax.named_scope(prof.LM_LINATTN):
            y = linear_mixer(blk["linattn"], h, m, compute_dtype, observed)
            h = h + _rms(y, blk["ln1"]["g"], m.eps)
    with jax.named_scope(prof.LM_MLP):
        w, u = blk["mlp"], h.astype(compute_dtype)
        act = jax.nn.silu(jnp.dot(u, w["w_gate"].astype(compute_dtype),
                                  preferred_element_type=jnp.float32)) \
            * jnp.dot(u, w["w_up"].astype(compute_dtype),
                      preferred_element_type=jnp.float32)
        y = jnp.dot(act.astype(compute_dtype),
                    w["w_down"].astype(compute_dtype),
                    preferred_element_type=jnp.float32)
        return h + _rms(y, blk["ln2"]["g"], m.eps)


def _remat_policy():
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        tfm._remat_policy("attn"),
        policies.save_only_these_names(*prof.GDN_RESIDUALS))


def forward(params, tokens, m: OlmoHybrid, *, compute_dtype=jnp.bfloat16,
            attn_impl="flash", observed=None):
    """``tokens`` [B, T + 1] -> the final normed hidden state over the
    first T positions, float32. ``observed`` (a list, the observer's) gets
    one dict a linear layer and switches the block checkpoint off."""
    block = functools.partial(_block, m=m, attn_fn=tfm._attn_fn(attn_impl),
                              compute_dtype=compute_dtype)
    kept = jax.checkpoint(block, policy=_remat_policy())
    with jax.named_scope(prof.LM_EMBED):
        h = params["tok_emb"][tokens[:, :-1]].astype(jnp.float32)
    for blk in params["blocks"]:
        if observed is None:
            h = kept(h, blk)
        else:
            seen = {}
            h = block(h, blk, observed=seen)
            if seen:
                observed.append(seen)
    with jax.named_scope(prof.LM_HEAD):
        return _rms(h, params["ln_f"]["g"], m.eps)


def _nll(h, head, targets, head_chunk, compute_dtype):
    if head_chunk:
        return tfm.nll_chunked(h, head, targets, head_chunk, compute_dtype)
    with jax.named_scope(prof.LM_HEAD):
        logits = h.astype(compute_dtype) @ head.T.astype(compute_dtype)
    return tfm.nll(logits.astype(jnp.float32), targets)


def loss(params, batch, m: OlmoHybrid, *, compute_dtype=jnp.bfloat16,
         attn_impl="flash", head_chunk=0):
    """Mean next-token cross-entropy over the vocabulary rows held;
    batch = {"tokens": [B, T+1] int32}."""
    toks = batch["tokens"]
    h = forward(params, toks, m, compute_dtype=compute_dtype,
                attn_impl=attn_impl)
    return _nll(h, params["head"], toks[:, 1:], head_chunk, compute_dtype)


def grad_fn(params, batch, m: OlmoHybrid, *, compute_dtype=jnp.bfloat16,
            attn_impl="flash", head_chunk=0):
    """(loss, gradients): the model carries no state from step to step."""
    return jax.value_and_grad(functools.partial(
        loss, m=m, compute_dtype=compute_dtype, attn_impl=attn_impl,
        head_chunk=head_chunk))(params, batch)


def observe(params, batch, m: OlmoHybrid, *, axis_name=None,
            compute_dtype=jnp.bfloat16, attn_impl="flash", head_chunk=0):
    """The observer, jitted apart from the step: for the batch's tokens,
    the loss (``lm_nll``) and per linear layer the mean decay ``exp(g)``
    (``decay_mean``), the mean write strength (``beta_mean``, up to 2) and
    the largest entry of a state (``state_absmax``; with beta up to 2 a
    state that grows is the first sign of a wrong sign or a missing norm).
    ``params`` are cast as the step's pull casts them. Inside a
    ``shard_map`` over ``axis_name`` each worker reads its shard of the
    batch and the readings are reduced over the workers."""
    p = cast_floating(params, compute_dtype)
    toks, seen = batch["tokens"], []
    h = forward(p, toks, m, compute_dtype=compute_dtype, attn_impl=attn_impl,
                observed=seen)
    out = {"lm_nll": _nll(h, p["head"], toks[:, 1:], head_chunk,
                          compute_dtype)}
    for key in ("decay_mean", "beta_mean", "state_absmax"):
        out[key] = jnp.stack([s[key] for s in seen])
    if axis_name is None:
        return out
    worst = out.pop("state_absmax")
    return dict(jax.lax.pmean(out, axis_name),
                state_absmax=jax.lax.pmax(worst, axis_name))
