"""Window/full hybrid expert decoder: a model that is a function of its
configuration file (``model_type`` ``laguna``; ``bench/configs/
laguna-s-2.1.json`` lists which form the config pins and which is
assumed).

``layer_types`` says, layer by layer, which attention a block has, and
``num_attention_heads_per_layer`` how many query heads; every kind reads
the same ``num_key_value_heads`` heads of ``head_dim``. Every block is an
attention sublayer and a feed-forward sublayer on an RMSNorm'd float32
residual ``h``:

- ``u = RMSNorm(h)``; q, k and v are projections of u (no biases); their
  positions by rotary over rotate-half pairs, float32: ``sliding_attention``
  layers over all channels at their own theta, ``full_attention`` layers by
  YaRN over the first ``partial_rotary_factor`` of the channels (the
  frequencies ramped from the original to ``1 / factor`` of it between the
  pairs that ``beta_fast`` and ``beta_slow`` rotations name, over
  ``original_max_position_embeddings``; cos and sin times
  ``attention_factor``), the rest passing through.
- attention, grouped: a window layer's query at t sees the keys
  ``t - sliding_window < s <= t``, a full layer's ``s <= t``, both through
  the flash kernels (``ops/flash_attention.py``; the window's are their own
  kernels, which visit the band alone).
- the per-head output gate (``gating`` ``per-head``): ``sigmoid(u W_g)``,
  one number a head and token, scales the head's output before ``W_o``.
- ``mlp_layer_types`` ``dense``: a SwiGLU MLP; ``sparse``: a sigmoid
  router over ``num_experts`` experts, the top ``num_experts_per_tok``
  chosen, their scores normalised to sum 1 (``norm_topk_prob``) and scaled
  by ``moe_routed_scaling_factor`` as the gates of the experts' outputs,
  beside one shared SwiGLU expert every token passes, ungated; the choice
  is made under a balancing bias that is no parameter (``mla_moe``'s:
  started at minus each router's mean score over a first batch,
  ``centred_bias``, and moved after each step by the sign of each
  expert's load error, ``update_bias``; the table carries it, ``DenseTable
  .make_step(state=)``). The layer is told which experts it holds
  (``parallel/moe.moe_apply_dropless``) and computes their part; the
  attention holds the heads the file names, and its partial output goes
  on as the experts' does.

Plain-dict parameters like the other models, so the whole LM lives in one
``DenseTable`` and trains through ``DenseTable.make_step``; the untied head
goes through ``transformer.nll_chunked``; every block is recomputed in the
backward pass but for what the flash forward kernels leave for their
backward kernels (``transformer._remat_policy("attn")``): the one mode the
model has. ``grad_fn`` takes the balancing bias and hands on the next
step's; the observer (``routing_stats``) reads the routing and the gates.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from minips_tpu.models import transformer as tfm
from minips_tpu.models.mla_moe import _rms, centred_bias, route, update_bias
from minips_tpu.parallel.moe import moe_apply_dropless
from minips_tpu.tables.dense import cast_floating
from minips_tpu.utils import profiling as prof

_HIGHEST = jax.lax.Precision.HIGHEST
MODEL_TYPE = "laguna"
WINDOW, FULL = "sliding_attention", "full_attention"
DENSE, SPARSE = "dense", "sparse"


class Rope(NamedTuple):
    """A layer kind's rotary positions: over the first ``rotary`` channels
    of a head, inverse frequencies ``inv_freq`` (a tuple, static), cos and
    sin times ``scale``."""
    rotary: int
    inv_freq: tuple
    scale: float


class Laguna(NamedTuple):
    """The sizes of a configuration file, static under jit."""
    vocab: int
    dim: int
    layer_types: tuple  # one of WINDOW, FULL a layer
    mlp_types: tuple    # one of DENSE, SPARSE a layer
    heads: tuple        # query heads held, a layer
    kv_heads: int
    head_dim: int
    window: int
    ropes: tuple        # (the window layers' Rope, the full layers')
    eps: float
    dense_width: int
    expert_width: int
    shared_width: int
    experts: int        # the router's outputs
    top_k: int
    held: tuple         # (lo, hi): the experts held here
    gate_scale: float
    norm_gates: bool
    bias_rate: float    # the balancing bias's step

    @property
    def depth(self) -> int:
        return len(self.layer_types)

    @property
    def routers(self) -> int:
        """The expert layers: the rows of the balancing bias."""
        return self.mlp_types.count(SPARSE)

    def rope(self, kind: str) -> Rope:
        return self.ropes[kind == FULL]


def yarn_inv_freq(head_dim: int, p: dict) -> tuple:
    """The full layers' inverse frequencies under YaRN (the form of the
    ``transformers`` library's ``yarn`` rope type): for the pairs i of the
    rotated channels, ``(1 - r_i) theta^(-2i/n) + r_i theta^(-2i/n) /
    factor``, r_i ramping from 0 to 1 between the pairs whose wavelengths
    ``beta_fast`` and ``beta_slow`` rotations over the original positions
    name (floor and ceiling)."""
    n = int(head_dim * float(p["partial_rotary_factor"]))
    theta, factor = float(p["rope_theta"]), float(p["factor"])
    orig = float(p["original_max_position_embeddings"])

    def pair(rotations):
        return n * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    lo = max(math.floor(pair(float(p["beta_fast"]))), 0)
    hi = min(math.ceil(pair(float(p["beta_slow"]))), n - 1)
    hi = hi if hi != lo else hi + 0.001
    out = []
    for i in range(n // 2):
        base = theta ** (-2.0 * i / n)
        r = min(max((i - lo) / (hi - lo), 0.0), 1.0)
        out.append((1.0 - r) * base + r * base / factor)
    return tuple(out)


def _rope_of(head_dim: int, p: dict, kind: str) -> Rope:
    kinds = {WINDOW: "default", FULL: "yarn"}
    if p.get("rope_type") != kinds[kind]:
        raise ValueError(f"laguna: {kind} rope_type {p.get('rope_type')!r} "
                         f"is not built (only {kinds[kind]!r})")
    if kind == FULL:
        return Rope(int(head_dim * float(p["partial_rotary_factor"])),
                    yarn_inv_freq(head_dim, p),
                    float(p["attention_factor"]))
    n = int(head_dim * float(p.get("partial_rotary_factor", 1)))
    theta = float(p["rope_theta"])
    return Rope(n, tuple(theta ** (-2.0 * i / n) for i in range(n // 2)),
                1.0)


def from_config(c: dict) -> Laguna:
    """The model of a configuration file with the published keys. The
    first ``num_hidden_layers`` entries of the per-layer lists are the
    layers built. ``num_experts`` counts the experts HELD here; where the
    file cuts it, ``published.num_experts`` is what the router knows and
    ``held_experts`` = [lo, hi) which of them these are. The head counts
    are those held here."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("attention_bias", False), ("gating", "per-head"),
                      ("moe_apply_router_weight_on_input", False),
                      ("moe_router_logit_softcapping", 0),
                      ("decoder_sparse_step", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"laguna: {key} = {c[key]!r} is not built "
                             f"(only {want!r})")
    depth = int(c["num_hidden_layers"])
    kinds = tuple(c["layer_types"][:depth])
    mlps = tuple(c["mlp_layer_types"][:depth])
    heads = tuple(int(h) for h in c["num_attention_heads_per_layer"][:depth])
    gates = set(c.get("gating_types", ["per_head"] * depth)[:depth])
    only = sorted(i for i, t in enumerate(c["mlp_layer_types"]) if t == DENSE)
    G, hd = int(c["num_key_value_heads"]), int(c["head_dim"])
    n_held = int(c["num_experts"])
    total = int(c.get("published", {}).get("num_experts", n_held))
    lo, hi = c.get("held_experts", (0, n_held))
    top_k = int(c["num_experts_per_tok"])
    if len(kinds) != depth or set(kinds) - {WINDOW, FULL} \
            or len(mlps) != depth or set(mlps) - {DENSE, SPARSE} \
            or len(heads) != depth or any(h % G for h in heads) \
            or gates != {"per_head"} \
            or sorted(c.get("mlp_only_layers", only)) != only \
            or hi - lo != n_held or not 0 <= lo < hi <= total \
            or not 1 <= top_k <= total:
        raise ValueError(
            f"laguna: {depth} layers of kinds {sorted(set(kinds))} and "
            f"{sorted(set(mlps))}, heads {heads} over {G} key/value heads, "
            f"gating {sorted(gates)}, held experts [{lo}, {hi}) of {total} "
            f"as {n_held}, top-{top_k}: every layer a named kind, key/value "
            "heads dividing each layer's, per-head gates, mlp_only_layers "
            "the dense layers, k within the experts")
    ropes = c["rope_parameters"]
    return Laguna(
        int(c["vocab_size"]), int(c["hidden_size"]), kinds, mlps, heads, G,
        hd, int(c["sliding_window"]),
        (_rope_of(hd, ropes[WINDOW], WINDOW), _rope_of(hd, ropes[FULL], FULL)),
        float(c["rms_norm_eps"]), int(c["intermediate_size"]),
        int(c["moe_intermediate_size"]),
        int(c["shared_expert_intermediate_size"]), total, top_k,
        (int(lo), int(hi)), float(c["moe_routed_scaling_factor"]),
        bool(c["norm_topk_prob"]), float(c.get("router_bias_rate", 0.0)))


def init(key, m: Laguna, std: float = 0.02):
    """Normal weights of standard deviation ``std`` (the residual
    projections scaled down by sqrt(2 * layers)), gains one."""
    d, G, hd = m.dim, m.kv_heads, m.head_dim
    n_held = m.held[1] - m.held[0]
    out_std = std / math.sqrt(2.0 * m.depth)
    norm = lambda k, shape, s: jax.random.normal(k, shape) * s  # noqa: E731
    gain = lambda n: {"g": jnp.ones(n)}                         # noqa: E731
    k_emb, k_head, *k_blocks = jax.random.split(key, 2 + m.depth)

    def swiglu(ks, lead, f):
        return {"w_gate": norm(next(ks), lead + (d, f), std),
                "w_up": norm(next(ks), lead + (d, f), std),
                "w_down": norm(next(ks), lead + (f, d), out_std)}

    def block(k, H, mlp):
        ks = iter(jax.random.split(k, 16))
        blk = {"ln1": gain(d), "ln2": gain(d), "attn": {
            "wq": norm(next(ks), (d, H * hd), std),
            "wk": norm(next(ks), (d, G * hd), std),
            "wv": norm(next(ks), (d, G * hd), std),
            "wg": norm(next(ks), (d, H), std),
            "wo": norm(next(ks), (H * hd, d), out_std)}}
        if mlp == DENSE:
            blk["mlp"] = swiglu(ks, (), m.dense_width)
        else:
            blk["router"] = {"w": norm(next(ks), (d, m.experts), std)}
            blk["shared"] = swiglu(ks, (), m.shared_width)
            blk["experts"] = swiglu(ks, (n_held,), m.expert_width)
        return blk

    return {"tok_emb": norm(k_emb, (m.vocab, d), std),
            "head": norm(k_head, (m.vocab, d), std),
            "ln_f": gain(d),
            "blocks": [block(k, H, mlp) for k, H, mlp in
                       zip(k_blocks, m.heads, m.mlp_types)]}


def rotate(x, pos, rope: Rope):
    """Rotary positions on the first ``rope.rotary`` channels of ``x``
    [B, T, H, hd], float32, over the rotate-half pairs (i, i + rotary/2),
    angle ``pos * inv_freq_i``, cos and sin times ``rope.scale``; the
    other channels pass through."""
    x = x.astype(jnp.float32)
    n = rope.rotary
    ang = pos.astype(jnp.float32)[:, None] \
        * jnp.asarray(rope.inv_freq, jnp.float32)[None, :]
    cos = (jnp.cos(ang) * rope.scale)[None, :, None, :]
    sin = (jnp.sin(ang) * rope.scale)[None, :, None, :]
    x1, x2 = x[..., : n // 2], x[..., n // 2: n]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            x[..., n:]], -1)


def attention(a, u, pos, kind: str, m: Laguna, attn_fn, compute_dtype,
              observed=None):
    """The attention sublayer's output [B, T, dim] (float32) from the
    normed input ``u``: the held heads' part. ``observed`` (a dict, the
    observer's) gets the gate's mean."""
    B, T, _ = u.shape
    H, G, hd = a["wq"].shape[1] // m.head_dim, m.kv_heads, m.head_dim
    uc = u.astype(compute_dtype)
    mm = lambda w: jnp.dot(uc, a[w].astype(compute_dtype),      # noqa: E731
                           preferred_element_type=jnp.float32)
    rope = m.rope(kind)
    q = rotate(mm("wq").reshape(B, T, H, hd), pos, rope)
    k = rotate(mm("wk").reshape(B, T, G, hd), pos, rope)
    o = attn_fn(q.astype(compute_dtype), k.astype(compute_dtype),
                mm("wv").reshape(B, T, G, hd).astype(compute_dtype))
    with jax.named_scope(prof.LM_ATTN_GATE):
        gate = jax.nn.sigmoid(mm("wg"))                          # [B, T, H]
        if observed is not None:
            observed["gate_mean"] = jnp.mean(gate)
        y = (o.astype(jnp.float32) * gate[..., None]).reshape(B, T, H * hd)
    return jnp.dot(y.astype(compute_dtype), a["wo"].astype(compute_dtype),
                   preferred_element_type=jnp.float32)


def _swiglu(w, u, compute_dtype):
    u = u.astype(compute_dtype)
    mm = lambda x, k: jnp.dot(x, w[k].astype(compute_dtype),    # noqa: E731
                              preferred_element_type=jnp.float32)
    act = jax.nn.silu(mm(u, "w_gate")) * mm(u, "w_up")
    return mm(act.astype(compute_dtype), "w_down")


def experts(blk, u, bias, m: Laguna, compute_dtype):
    """The expert sublayer's output [N, dim] (float32) from the normed
    input ``u`` [N, dim], the assignments of each expert [experts] and the
    experts' mean score [experts]: the held experts' part beside the
    shared expert's whole. ``bias`` [experts] moves the choice alone."""
    with jax.named_scope(prof.LM_MOE_ROUTER):
        chosen, gate, s = route(blk["router"], u, bias, m)
        loads = jnp.sum(chosen[:, :, None] == jnp.arange(m.experts),
                        axis=(0, 1), dtype=jnp.int32)
    y = moe_apply_dropless(blk["experts"], u, chosen, gate, held=m.held,
                           compute_dtype=compute_dtype)
    with jax.named_scope(prof.LM_MOE_SHARED):
        y = y + _swiglu(blk["shared"], u, compute_dtype)
    return y, loads, jax.lax.stop_gradient(jnp.mean(s, 0))


def _block(h, blk, bias, kind, pos, m: Laguna, attn_fns, compute_dtype,
           observed=None):
    """residual -> (residual, assignments of each expert [experts], the
    experts' mean score [experts]); a dense block gives None for both and
    takes None for ``bias``."""
    B, T, D = h.shape
    with jax.named_scope(prof.LM_ATTN):
        u = _rms(h, blk["ln1"]["g"], m.eps)
        sub = functools.partial(attention, blk["attn"], u, pos, kind, m,
                                attn_fns[kind], compute_dtype, observed)
        if kind == WINDOW:
            with jax.named_scope(prof.LM_ATTN_WINDOW):
                h = h + sub()
        else:
            h = h + sub()
    if "mlp" in blk:
        with jax.named_scope(prof.LM_MLP):
            return h + _swiglu(blk["mlp"], _rms(h, blk["ln2"]["g"], m.eps),
                               compute_dtype), None, None
    with jax.named_scope(prof.LM_MOE):
        y, loads, mean = experts(blk, _rms(h, blk["ln2"]["g"], m.eps).reshape(
            B * T, D), bias, m, compute_dtype)
        return h + y.reshape(B, T, D), loads, mean


def forward(params, tokens, m: Laguna, bias=None, *,
            compute_dtype=jnp.bfloat16, attn_impl="flash", observed=None):
    """``tokens`` [B, T + 1] -> (the final normed hidden state over the
    first T positions, float32; the assignments of each expert [routers,
    experts]; the experts' mean scores [routers, experts]). ``bias``
    [routers, experts] float32 is the balancing bias (none: zeros).
    ``observed`` (a list, the observer's) gets one dict a layer and
    switches the block checkpoint off."""
    pos = jnp.arange(tokens.shape[1] - 1)
    if bias is None:
        bias = jnp.zeros((m.routers, m.experts), jnp.float32)
    bias = jax.lax.stop_gradient(bias)
    attn_fns = {WINDOW: tfm._attn_fn(attn_impl, m.window),
                FULL: tfm._attn_fn(attn_impl)}
    block = functools.partial(_block, pos=pos, m=m, attn_fns=attn_fns,
                              compute_dtype=compute_dtype)
    kept = jax.checkpoint(block, policy=tfm._remat_policy("attn"),
                          static_argnums=(3,))
    with jax.named_scope(prof.LM_EMBED):
        h = params["tok_emb"][tokens[:, :-1]].astype(jnp.float32)
    loads, means = [], []
    for blk, kind in zip(params["blocks"], m.layer_types):
        b = None if "mlp" in blk else bias[len(loads)]
        if observed is None:
            h, load, mean = kept(h, blk, b, kind)
        else:
            seen = {}
            h, load, mean = block(h, blk, b, kind, observed=seen)
            observed.append(seen)
        if load is not None:
            loads.append(load)
            means.append(mean)
    none = jnp.zeros((0, m.experts))    # a model of dense blocks alone
    with jax.named_scope(prof.LM_HEAD):
        return _rms(h, params["ln_f"]["g"], m.eps), (
            jnp.stack(loads) if loads else none.astype(jnp.int32)), (
            jnp.stack(means) if means else none)


def _nll(h, head, targets, head_chunk, compute_dtype):
    if head_chunk:
        return tfm.nll_chunked(h, head, targets, head_chunk, compute_dtype)
    with jax.named_scope(prof.LM_HEAD):
        logits = h.astype(compute_dtype) @ head.T.astype(compute_dtype)
    return tfm.nll(logits.astype(jnp.float32), targets)


def _loss(params, batch, m: Laguna, bias, *, compute_dtype, attn_impl,
          head_chunk, observed=None):
    toks = batch["tokens"]
    h, loads, means = forward(params, toks, m, bias,
                              compute_dtype=compute_dtype,
                              attn_impl=attn_impl, observed=observed)
    nll = _nll(h, params["head"], toks[:, 1:], head_chunk, compute_dtype)
    return nll, {"loads": loads, "mean_score": means, "lm_nll": nll}


def loss(params, batch, m: Laguna, bias=None, *, compute_dtype=jnp.bfloat16,
         attn_impl="flash", head_chunk=0):
    """Mean next-token cross-entropy over the vocabulary rows held;
    batch = {"tokens": [B, T+1] int32}."""
    return _loss(params, batch, m, bias, compute_dtype=compute_dtype,
                 attn_impl=attn_impl, head_chunk=head_chunk)[0]


def grad_fn(params, batch, bias, m: Laguna, *, axis_name=None,
            compute_dtype=jnp.bfloat16, attn_impl="flash", head_chunk=0):
    """(loss, gradients, the balancing bias for the next step): the step's
    own routing, counted over every worker of ``axis_name``, moves the
    bias (``update_bias``), outside the gradient."""
    (total, aux), grads = jax.value_and_grad(
        lambda p: _loss(p, batch, m, bias, compute_dtype=compute_dtype,
                        attn_impl=attn_impl, head_chunk=head_chunk),
        has_aux=True)(params)
    loads = aux["loads"]
    if axis_name is not None:
        loads = jax.lax.psum(loads, axis_name)
    return total, grads, update_bias(bias, loads, m.bias_rate)


def routing_stats(params, batch, bias, m: Laguna, *,
                  compute_dtype=jnp.bfloat16, attn_impl="flash",
                  head_chunk=0):
    """The observer, jitted apart from the step: for the batch's tokens
    under the balancing ``bias``, per expert layer the assignments of each
    held expert (``tokens_held`` [routers, held]), the share sent to
    experts that live elsewhere (``absent_share``), the fullest expert's
    load over the mean load of ALL experts (``load_max_over_mean``) and the
    experts' mean scores (``mean_score`` [routers, experts]); the loss
    (``lm_nll``); per layer the output gate's mean (``gate_mean``
    [layers]) and its mean over each kind's layers (``gate_mean_window``,
    ``gate_mean_full``). ``params`` are cast as the step's pull casts
    them."""
    p = cast_floating(params, compute_dtype)
    seen = []
    _, aux = _loss(p, batch, m, bias, compute_dtype=compute_dtype,
                   attn_impl=attn_impl, head_chunk=head_chunk, observed=seen)
    loads = aux.pop("loads")
    held = loads[:, m.held[0]: m.held[1]]
    n = jnp.sum(loads[0])
    gates = [s["gate_mean"] for s in seen]
    by_kind = {}
    for kind, name in ((WINDOW, "window"), (FULL, "full")):
        of = [g for g, k in zip(gates, m.layer_types) if k == kind]
        if of:
            by_kind[f"gate_mean_{name}"] = jnp.mean(jnp.stack(of))
    return {"tokens_held": held,
            "absent_share": 1.0 - jnp.sum(held, 1) / n,
            "load_max_over_mean": jnp.max(loads, 1) * m.experts / n,
            "gate_mean": jnp.stack(gates), **by_kind, **aux}
