"""Latent-attention expert decoder: a model that is a function of its
configuration file (``model_type`` ``joyai_llm_flash``; the key names are
DeepSeek-V2/V3's, arXiv:2405.04434 and arXiv:2412.19437, and
``bench/configs/joyai-llm-flash.json`` lists which form the config pins
and which the reports give).

Every layer is an attention sublayer and a feed-forward sublayer on an
RMSNorm'd float32 residual:

- multi-head latent attention (MLA), training form (nothing is absorbed
  and no latent cache exists): the queries come through a normed latent
  of ``q_lora_rank`` channels, keys and values through one of
  ``kv_lora_rank``; each head's q and k are ``qk_nope_head_dim``
  channels of their own and ``qk_rope_head_dim`` rotated ones, and the
  rotated part of k is ONE vector a token, shared by all heads; v has
  ``v_head_dim`` channels. q·k runs over 192 channels and p·v over 128:
  ``ops/flash_attention.py`` takes v's head size from v.
- the first ``first_k_dense_replace`` layers have a SwiGLU MLP; the others
  ``n_routed_experts`` SwiGLU experts, ``num_experts_per_tok`` a token,
  beside ``n_shared_experts`` shared ones that every token passes. The
  router is a sigmoid over all experts; the choice is the top-k of score
  plus a balancing bias that is no parameter (the step counts each
  expert's tokens and moves the bias for the next step, outside the
  gradient: ``update_bias``; the table carries it, ``DenseTable
  .make_step``'s ``state``); the gates are the chosen scores, normalised
  and scaled. The layer is told which experts of all it holds
  (``parallel/moe.moe_apply_dropless``) and computes their part.
- ``num_nextn_predict_layers`` = 1 multi-token-prediction module: the last
  block's output and the embedding of the next token, each normed, joined
  and projected, one more expert block, a final norm of its own and the
  model's head and embedding; it predicts the token after next, and its
  loss is added with weight ``mtp_loss_weight``.

Plain-dict parameters like the other models, so the whole LM lives in one
``DenseTable`` and trains through ``DenseTable.make_step``; attention goes
through ``transformer._attn_fn``, the untied head through
``transformer.nll_chunked`` and every block is recomputed in the backward
pass but for what the flash forward kernel leaves for its backward kernel
(``transformer._remat_policy("attn")``).
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.moe import moe_apply_dropless
from minips_tpu.tables.dense import cast_floating
from minips_tpu.utils import profiling as prof

_HIGHEST = jax.lax.Precision.HIGHEST
MODEL_TYPE = "joyai_llm_flash"


class MlaMoe(NamedTuple):
    """The sizes of a configuration file, static under jit."""
    vocab: int
    dim: int
    depth: int          # blocks of the main model
    dense_layers: int   # the leading ones with a plain MLP
    heads: int
    q_rank: int
    kv_rank: int
    nope: int           # a head's channels without position
    rope: int           # a head's rotated channels (k's: shared by heads)
    v_dim: int
    rope_theta: float
    eps: float
    dense_width: int
    expert_width: int
    shared: int         # shared experts (one stack of shared * width)
    experts: int        # the router's outputs
    top_k: int
    held: tuple         # (lo, hi): the experts held here
    gate_scale: float   # routed_scaling_factor
    norm_gates: bool
    bias_rate: float    # the balancing bias's step
    mtp: int            # prediction modules: 0 or 1
    mtp_weight: float

    @property
    def routers(self) -> int:
        """Expert layers, the prediction module's included: the rows of
        the balancing bias."""
        return self.depth - self.dense_layers + self.mtp


def from_config(c: dict) -> MlaMoe:
    """The model of a configuration file with the published keys.
    ``n_routed_experts`` counts the experts HELD here; where the file cuts
    it, ``published.n_routed_experts`` is what the router knows and
    ``held_experts`` = [lo, hi) which of them these are."""
    for key, want in (("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("attention_bias", False), ("rope_scaling", None),
                      ("rope_interleave", True), ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1)):
        if c.get(key, want) != want:
            raise ValueError(f"mla_moe: {key} = {c[key]!r} is not built "
                             f"(only {want!r})")
    n_held = int(c["n_routed_experts"])
    total = int(c.get("published", {}).get("n_routed_experts", n_held))
    lo, hi = c.get("held_experts", (0, n_held))
    if hi - lo != n_held or not 0 <= lo < hi <= total:
        raise ValueError(f"mla_moe: held_experts [{lo}, {hi}) does not name "
                         f"{n_held} of {total} experts")
    depth, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    mtp, top_k = int(c.get("num_nextn_predict_layers", 0)), \
        int(c["num_experts_per_tok"])
    rope = int(c["qk_rope_head_dim"])
    if not 0 <= dense <= depth or mtp not in (0, 1) or rope % 2 \
            or not 1 <= top_k <= total or depth - dense + mtp < 1:
        raise ValueError(
            f"mla_moe: {dense} dense of {depth} layers, {mtp} prediction "
            f"modules, {rope} rotated channels, top-{top_k} of {total}: "
            "at least one expert layer, at most one module, an even rotary "
            "size, k within the experts")
    return MlaMoe(
        int(c["vocab_size"]), int(c["hidden_size"]), depth, dense,
        int(c["num_attention_heads"]), int(c["q_lora_rank"]),
        int(c["kv_lora_rank"]), int(c["qk_nope_head_dim"]), rope,
        int(c["v_head_dim"]), float(c["rope_theta"]),
        float(c["rms_norm_eps"]), int(c["intermediate_size"]),
        int(c["moe_intermediate_size"]), int(c["n_shared_experts"]), total,
        top_k, (int(lo), int(hi)), float(c["routed_scaling_factor"]),
        bool(c["norm_topk_prob"]), float(c.get("router_bias_rate", 0.0)),
        mtp, float(c.get("mtp_loss_weight", 0.0)))


def init(key, m: MlaMoe, std: float = 0.02):
    """Normal weights of standard deviation ``std`` (the residual
    projections scaled down by sqrt(2 * blocks)), gains one."""
    d, H = m.dim, m.heads
    n_held = m.held[1] - m.held[0]
    out_std = std / math.sqrt(2.0 * (m.depth + m.mtp))
    norm = lambda k, shape, s: jax.random.normal(k, shape) * s  # noqa: E731
    gain = lambda n: {"g": jnp.ones(n)}                         # noqa: E731
    k_emb, k_head, k_eh, *k_blocks = jax.random.split(
        key, 3 + m.depth + m.mtp)

    def swiglu(ks, lead, f):
        return {"w_gate": norm(next(ks), lead + (d, f), std),
                "w_up": norm(next(ks), lead + (d, f), std),
                "w_down": norm(next(ks), lead + (f, d), out_std)}

    def block(k, dense: bool):
        ks = iter(jax.random.split(k, 16))
        blk = {"ln1": gain(d), "ln2": gain(d), "attn": {
            "wq_a": norm(next(ks), (d, m.q_rank), std),
            "q_ln": gain(m.q_rank),
            "wq_b": norm(next(ks), (m.q_rank, H * (m.nope + m.rope)), std),
            "wkv_a": norm(next(ks), (d, m.kv_rank + m.rope), std),
            "kv_ln": gain(m.kv_rank),
            "wkv_b": norm(next(ks), (m.kv_rank, H * (m.nope + m.v_dim)),
                          std),
            "wo": norm(next(ks), (H * m.v_dim, d), out_std)}}
        if dense:
            blk["mlp"] = swiglu(ks, (), m.dense_width)
        else:
            blk["router"] = {"w": norm(next(ks), (d, m.experts), std)}
            blk["shared"] = swiglu(ks, (), m.shared * m.expert_width)
            blk["experts"] = swiglu(ks, (n_held,), m.expert_width)
        return blk

    params = {"tok_emb": norm(k_emb, (m.vocab, d), std),
              "head": norm(k_head, (m.vocab, d), std),
              "ln_f": gain(d),
              "blocks": [block(k, i < m.dense_layers)
                         for i, k in enumerate(k_blocks[: m.depth])]}
    if m.mtp:
        params["mtp"] = {"ln_h": gain(d), "ln_e": gain(d),
                         "w_eh": norm(k_eh, (2 * d, d), std),
                         "block": block(k_blocks[-1], False),
                         "ln_f": gain(d)}
    return params


def _rms(x, g, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _rotate_pairs(x, pos, theta: float):
    """Rotary positions over the interleaved pairs (2i, 2i + 1) of ``x``
    [B, T, H, rope], float32. The pairs are parted into halves first and
    left so: q and k are parted alike, and a dot product does not see a
    permutation that both sides share."""
    x = x.astype(jnp.float32)
    x = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    return tfm.rope_rotate(jnp.concatenate([x[..., 0], x[..., 1]], -1), pos,
                           theta)


def mla_qkv(a, u, pos, m: MlaMoe, compute_dtype):
    """q and k [B, T, heads, nope + rope] and v [B, T, heads, v_dim] of
    the latent attention, from the normed input ``u``."""
    B, T, _ = u.shape
    H = m.heads
    u = u.astype(compute_dtype)
    mm = lambda x, w: x.astype(compute_dtype) @ w.astype(compute_dtype)  # noqa: E731,E501
    with jax.named_scope(prof.LM_ATTN_MLA):
        c_q = _rms(mm(u, a["wq_a"]), a["q_ln"]["g"], m.eps)
        q = mm(c_q, a["wq_b"]).reshape(B, T, H, m.nope + m.rope)
        kv_a = mm(u, a["wkv_a"])
        c_kv = _rms(kv_a[..., : m.kv_rank], a["kv_ln"]["g"], m.eps)
        kv = mm(c_kv, a["wkv_b"]).reshape(B, T, H, m.nope + m.v_dim)
        q_rope = _rotate_pairs(q[..., m.nope:], pos, m.rope_theta)
        # one rotated key vector a token, the same for every head
        k_rope = _rotate_pairs(kv_a[..., None, m.kv_rank:], pos,
                               m.rope_theta)
        q = jnp.concatenate([q[..., : m.nope],
                             q_rope.astype(compute_dtype)], -1)
        k = jnp.concatenate(
            [kv[..., : m.nope], jnp.broadcast_to(
                k_rope.astype(compute_dtype), (B, T, H, m.rope))], -1)
    return q, k, kv[..., m.nope:]


def _swiglu(w, u, compute_dtype):
    u = u.astype(compute_dtype)
    act = jax.nn.silu((u @ w["w_gate"].astype(compute_dtype)
                       ).astype(jnp.float32)) \
        * (u @ w["w_up"].astype(compute_dtype)).astype(jnp.float32)
    return (act.astype(compute_dtype)
            @ w["w_down"].astype(compute_dtype)).astype(jnp.float32)


def route(router, u, bias, m: MlaMoe):
    """The sigmoid router, float32 throughout: (the experts chosen [N, k],
    their gates [N, k], every expert's score [N, experts]). ``bias``
    [experts] moves the choice alone."""
    s = jax.nn.sigmoid(jnp.dot(u.astype(jnp.float32),
                               router["w"].astype(jnp.float32),
                               precision=_HIGHEST))
    _, chosen = jax.lax.top_k(s + bias, m.top_k)
    gate = jnp.take_along_axis(s, chosen, axis=1)
    if m.norm_gates:
        gate = gate / (jnp.sum(gate, -1, keepdims=True) + 1e-20)
    return chosen.astype(jnp.int32), gate * m.gate_scale, s


def update_bias(bias, loads, rate: float):
    """The balancing bias after a step that sent ``loads`` [routers,
    experts] assignments to each expert: an expert that got less than the
    mean load rises by ``rate``, one that got more falls by it. No
    gradient passes: the bias moves the choice only."""
    mean = jnp.mean(loads.astype(jnp.float32), -1, keepdims=True)
    return bias + rate * jnp.sign(mean - loads)


def _block(h, blk, bias, counted, pos, m: MlaMoe, attn_fn, compute_dtype):
    """residual -> (residual, assignments of each expert [experts], the
    experts' mean score [experts]); a dense block gives None for both and
    takes None for ``bias``. ``bias`` [experts] is added to the scores for
    the choice alone; ``counted`` [T] bool says which positions' routing
    counts (the prediction module's last position predicts nothing; None:
    all of them)."""
    B, T, D = h.shape
    with jax.named_scope(prof.LM_ATTN):
        q, k, v = mla_qkv(blk["attn"], _rms(h, blk["ln1"]["g"], m.eps), pos,
                          m, compute_dtype)
        a = attn_fn(q, k, v).reshape(B, T, -1)
        h = h + (a @ blk["attn"]["wo"].astype(compute_dtype)
                 ).astype(jnp.float32)
    if "mlp" in blk:
        with jax.named_scope(prof.LM_MLP):
            y = _swiglu(blk["mlp"], _rms(h, blk["ln2"]["g"], m.eps),
                        compute_dtype)
        return h + y, None, None
    with jax.named_scope(prof.LM_MOE):
        u = _rms(h, blk["ln2"]["g"], m.eps).reshape(B * T, D)
        with jax.named_scope(prof.LM_MOE_ROUTER):
            chosen, gate, s = route(blk["router"], u, bias, m)
            hit = chosen[:, :, None] == jnp.arange(m.experts)   # [N, k, E]
            if counted is None:
                mean = jnp.mean(s, 0)
            else:
                real = jnp.tile(counted, B)
                hit = hit & real[:, None, None]
                mean = jnp.sum(jnp.where(real[:, None], s, 0.0), 0) \
                    / jnp.sum(real)
            loads = jnp.sum(hit, axis=(0, 1), dtype=jnp.int32)
        y = moe_apply_dropless(blk["experts"], u, chosen, gate, held=m.held,
                               compute_dtype=compute_dtype)
        with jax.named_scope(prof.LM_MOE_SHARED):
            y = y + _swiglu(blk["shared"], u, compute_dtype)
        return h + y.reshape(B, T, D), loads, jax.lax.stop_gradient(mean)


def forward(params, tokens, m: MlaMoe, bias=None, *,
            compute_dtype=jnp.bfloat16, attn_impl="flash"):
    """``tokens`` [B, T + 1] -> (the main model's final normed hidden state
    over the first T positions; the prediction module's, its last position
    zero, or None; assignments of each expert [routers, experts]; the
    experts' mean scores [routers, experts]), the expert layers in order
    and the module's last. ``bias`` [routers, experts] float32 is the
    balancing bias (none: zeros)."""
    toks = tokens[:, :-1]
    B, T = toks.shape
    pos = jnp.arange(T)
    if bias is None:
        bias = jnp.zeros((m.routers, m.experts), jnp.float32)
    bias = jax.lax.stop_gradient(bias)
    block = jax.checkpoint(
        functools.partial(_block, pos=pos, m=m,
                          attn_fn=tfm._attn_fn(attn_impl),
                          compute_dtype=compute_dtype),
        policy=tfm._remat_policy("attn"))
    with jax.named_scope(prof.LM_EMBED):
        h = params["tok_emb"][toks].astype(jnp.float32)
    loads, means = [], []
    for blk in params["blocks"]:
        if "mlp" in blk:
            h, _, _ = block(h, blk, None, None)
            continue
        h, load, mean = block(h, blk, bias[len(loads)], None)
        loads.append(load)
        means.append(mean)
    with jax.named_scope(prof.LM_HEAD):
        h_main = _rms(h, params["ln_f"]["g"], m.eps)
    h_mtp = None
    if m.mtp:
        p = params["mtp"]
        with jax.named_scope(prof.LM_MTP):
            with jax.named_scope(prof.LM_EMBED):
                e = params["tok_emb"][tokens[:, 1:]].astype(jnp.float32)
            x = jnp.concatenate([_rms(h, p["ln_h"]["g"], m.eps),
                                 _rms(e, p["ln_e"]["g"], m.eps)], -1)
            x = (x.astype(compute_dtype) @ p["w_eh"].astype(compute_dtype)
                 ).astype(jnp.float32)
            x, load, mean = block(x, p["block"], bias[-1], pos < T - 1)
            loads.append(load)
            means.append(mean)
            with jax.named_scope(prof.LM_HEAD):
                # position T - 1 would predict a token the batch does not
                # hold: its row is zero, so it reads log(vocab) exactly
                # and passes no gradient (``_nll_mtp`` takes it out)
                h_mtp = _rms(x, p["ln_f"]["g"], m.eps) \
                    * (pos < T - 1)[None, :, None]
    return h_main, h_mtp, jnp.stack(loads), jnp.stack(means)


def _nll(h, head, targets, head_chunk, compute_dtype):
    if head_chunk:
        return tfm.nll_chunked(h, head, targets, head_chunk, compute_dtype)
    with jax.named_scope(prof.LM_HEAD):
        logits = h.astype(compute_dtype) @ head.T.astype(compute_dtype)
    return tfm.nll(logits.astype(jnp.float32), targets)


def _nll_mtp(h_mtp, head, tokens, head_chunk, compute_dtype):
    """Mean cross-entropy of the module's T - 1 predictions a sequence
    (position i predicts token i + 2). The head runs over all T positions,
    whose last row is zero and reads log(vocab), which is taken out."""
    B, T = h_mtp.shape[:2]
    targets = jnp.pad(tokens[:, 2:], ((0, 0), (0, 1)))
    with jax.named_scope(prof.LM_MTP):
        over_t = _nll(h_mtp, head, targets, head_chunk, compute_dtype)
    return (over_t * T - math.log(head.shape[0])) / (T - 1)


def _loss(params, batch, m: MlaMoe, bias, *, compute_dtype, attn_impl,
          head_chunk):
    toks = batch["tokens"]
    h, h_mtp, loads, means = forward(params, toks, m, bias,
                                     compute_dtype=compute_dtype,
                                     attn_impl=attn_impl)
    main = _nll(h, params["head"], toks[:, 1:], head_chunk, compute_dtype)
    aux = {"loads": loads, "mean_score": means, "lm_nll": main}
    if h_mtp is None:
        return main, aux
    aux["mtp_nll"] = _nll_mtp(h_mtp, params["head"], toks, head_chunk,
                              compute_dtype)
    return main + m.mtp_weight * aux["mtp_nll"], aux


def loss(params, batch, m: MlaMoe, bias=None, *, compute_dtype=jnp.bfloat16,
         attn_impl="flash", head_chunk=0):
    """Mean next-token cross-entropy over the vocabulary rows held, plus
    ``mtp_weight`` times the prediction module's; batch = {"tokens":
    [B, T+1] int32}."""
    return _loss(params, batch, m, bias, compute_dtype=compute_dtype,
                 attn_impl=attn_impl, head_chunk=head_chunk)[0]


def grad_fn(params, batch, bias, m: MlaMoe, *, axis_name=None,
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


def routing_stats(params, batch, bias, m: MlaMoe, *,
                  compute_dtype=jnp.bfloat16, attn_impl="flash",
                  head_chunk=0):
    """The observer, jitted apart from the step: for the batch's tokens
    under the balancing ``bias``, per expert layer (the module's last),
    how many assignments each held expert gets (``tokens_held`` [routers,
    held]), the share sent to experts that live elsewhere
    (``absent_share`` [routers]), the fullest expert's load over the mean
    load of ALL experts (``load_max_over_mean`` [routers]), the experts'
    mean scores (``mean_score`` [routers, experts]) and the two losses
    (``lm_nll``, ``mtp_nll``). ``params`` are cast as the step's pull
    casts them."""
    p = cast_floating(params, compute_dtype)
    _, aux = _loss(p, batch, m, bias, compute_dtype=compute_dtype,
                   attn_impl=attn_impl, head_chunk=head_chunk)
    loads = aux.pop("loads")
    held = loads[:, m.held[0]: m.held[1]]
    n = jnp.sum(loads[0])
    return {"tokens_held": held,
            "absent_share": 1.0 - jnp.sum(held, 1) / n,
            "load_max_over_mean": jnp.max(loads, 1) * m.experts / n, **aux}


def centred_bias(stats, m: MlaMoe):
    """The balancing bias a run starts from: minus each router's mean
    score over a first batch, layer by layer (a layer's routing moves the
    next layer's scores), so that the choice starts from what tells the
    tokens apart and not from an offset all of them share. ``stats(bias)``
    is the observer over that batch. From zero the step-by-step rule gets
    there too, ``offset / rate`` steps later. The bias is kept on the host
    between the passes, so that the observer is handed the same kind of
    array every time and compiles once."""
    bias = np.zeros((m.routers, m.experts), np.float32)
    for layer in range(m.routers):
        bias[layer] = -np.asarray(
            stats(jnp.asarray(bias))["mean_score"][layer])
    return jnp.asarray(bias)
