"""A GPT-2-shaped decoder, plain: the reference of the ``lm`` system.
It imports nothing of the program. Weights arrive in the layout the
benchmark made them in (``tok_emb``, ``pos_emb``, ``ln_f``, ``blocks`` of
``ln1 ln2 qkv proj mlp_in mlp_out``).

Architecture as the program trains it (Radford et al. 2019, GPT-2): pre-LN
blocks, learned positions, causal multi-head attention by full softmax
scores, GELU (tanh form) MLP of width 4d, tied output head, mean next-token
cross-entropy. Departure from the published model, shared with the
program: the linear layers carry no biases. Optimizer: Adam, no decay.

float32 at ``highest`` matmul precision, no kernels, no cache. The batch
is walked in blocks of rows and each block of layers is recomputed in the
backward pass, so that it fits beside its own Adam state. ``low=True`` is
the control: bfloat16 activations and fp8 (e4m3) matmul inputs, the step
below the bfloat16 the configuration states.
"""

from __future__ import annotations

import functools
import math


def _ln(x, p):
    import jax
    import jax.numpy as jnp
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]


def _mm(eq, a, b, low):
    import jax
    import jax.numpy as jnp
    if low:
        f8, bf = jnp.float8_e4m3fn, jnp.bfloat16
        return jnp.einsum(eq, a.astype(f8).astype(bf),
                          b.astype(f8).astype(bf))
    return jnp.einsum(eq, a, b, precision=jax.lax.Precision.HIGHEST)


def _gelu(x):
    import jax.numpy as jnp
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, heads, low):
    import jax
    import jax.numpy as jnp
    B, T, D = x.shape
    hd = D // heads
    h = _ln(x, blk["ln1"])
    qkv = _mm("btd,dce->cbte", h, blk["qkv"], low)
    q, k, v = (qkv[i].reshape(B, T, heads, hd) for i in range(3))
    s = _mm("bqhd,bkhd->bhqk", q, k, low).astype(jnp.float32) \
        / math.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    a = _mm("bhqk,bkhd->bqhd", p.astype(x.dtype), v, low).reshape(B, T, D)
    x = x + _mm("btd,de->bte", a, blk["proj"], low).astype(x.dtype)
    h = _ln(x, blk["ln2"])
    z = _gelu(_mm("btd,de->bte", h, blk["mlp_in"], low))
    return x + _mm("bte,ed->btd", z, blk["mlp_out"], low).astype(x.dtype)


def loss_sum(params, tokens, heads: int, low: bool):
    """Sum (not mean) of next-token negative log-likelihoods over the
    rows of ``tokens`` [b, T+1]."""
    import jax
    import jax.numpy as jnp
    if low:
        params = jax.tree.map(lambda x: x.astype(jnp.bfloat16), params)
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    T = inp.shape[1]
    x = params["tok_emb"][inp] + params["pos_emb"][:T]
    block = jax.checkpoint(functools.partial(_block, heads=heads, low=low))
    for blk in params["blocks"]:
        x = block(x, blk)
    x = _ln(x, params["ln_f"])
    logits = _mm("btd,vd->btv", x, params["tok_emb"], low) \
        .astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    return -jnp.sum(jnp.take_along_axis(logp, tgt[..., None], -1))


def _leaf_norms(tree, names) -> dict:
    import jax
    import jax.numpy as jnp
    norms = jax.jit(lambda t: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in jax.tree.leaves(t)])(tree)
    return {n: float(v) for n, v in zip(names, norms)}


def run(config: dict, batches: list, params0, leaf_names, *,
        low: bool = False, keep: float = 1.0,
        rows_per_block: int = 2) -> dict:
    """Follow ``len(batches)`` steps; returns ``loss`` per step, ``grad``
    (norm of the first gradient per leaf) and ``delta`` (norm of each
    leaf's change after the last step). ``params0`` is the benchmark's own
    initial weights (a pytree of device or host arrays). ``keep`` < 1
    plants the fault of a step that leaves part of its batch out and takes
    the mean over the rest (0.5: half; 1/chips: no exchange)."""
    import jax
    import jax.numpy as jnp
    heads = int(config["n_head"])
    lr, b1, b2, eps = float(config["lr"]), 0.9, 0.999, 1e-8
    params = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params0)
    start = params
    mu = jax.tree.map(jnp.zeros_like, params)
    nu = jax.tree.map(jnp.zeros_like, params)
    vg = jax.jit(jax.value_and_grad(
        lambda p, t: loss_sum(p, t, heads, low)))

    # donated: the moments, the gradient sum and (after step 1, whose
    # parameters are the start that the change is measured from) the
    # parameters are updated in place: at 4 bytes a parameter a copy is
    # 1.9 GB, and at most six are alive at once
    def adam(params, mu, nu, g, t, denom):
        g = jax.tree.map(lambda x: x.astype(jnp.float32) / denom, g)
        mu = jax.tree.map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
        nu = jax.tree.map(lambda v, x: b2 * v + (1 - b2) * x * x, nu, g)
        params = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t))
            / (jnp.sqrt(v / (1 - b2 ** t)) + eps), params, mu, nu)
        return params, mu, nu

    adam_first = jax.jit(adam, donate_argnums=(1, 2, 3))
    adam_later = jax.jit(adam, donate_argnums=(0, 1, 2))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    out = {"loss": [], "grad": {}, "delta": {}}
    for t, b in enumerate(batches, 1):
        toks = jnp.asarray(b["tokens"])
        if keep < 1.0:
            toks = toks[: int(toks.shape[0] * keep)]
        n_rows, T = toks.shape[0], toks.shape[1] - 1
        total, grads = 0.0, None
        for r in range(0, n_rows, rows_per_block):
            l, g = vg(params, toks[r: r + rows_per_block])
            total = total + l
            grads = g if grads is None else add(grads, g)
        denom = float(n_rows * T)       # the sums become means
        out["loss"].append(float(total) / denom)
        if t == 1:
            out["grad"] = {k: v / denom for k, v in
                           _leaf_norms(grads, leaf_names).items()}
        params, mu, nu = (adam_first if t == 1 else adam_later)(
            params, mu, nu, grads, float(t), denom)
        del grads
    out["delta"] = _leaf_norms(
        jax.tree.map(jnp.subtract, params, start), leaf_names)
    return out
