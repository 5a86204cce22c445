"""The one general traffic generator: reads a mix's parameters, makes the
run's batches on the device from ``--seed`` in one jitted call.

A mix is a data file (``bench/traffic/<name>.json``) with a ``kind`` and
that kind's parameters. Every seed gets the same sizes: the same batch,
the same pool, the same distribution; only the draws differ.

Kinds:
- ``criteo_zipf``: Criteo-shaped CTR rows, 13 numeric and 26 categorical
  fields; field f draws its ids from ``cardinalities[f]`` values,
  Zipf-distributed (a bounded power law by inverse CDF), and its ids start
  where field f-1's end; the label follows a hidden linear model of the
  numeric fields and an id effect, so the loss can fall.
- ``lm_tokens``: packed token sequences [batch, seq_len + 1], tokens
  Zipf-distributed over the vocabulary with a weak bigram structure (every
  other token is a function of its left neighbour).
"""

from __future__ import annotations

import numpy as np


def _key(seed: int):
    import jax
    # seeds run to a little over 2**31: fold the high bits in, PRNGKey
    # takes 32 signed bits on some paths
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def _zipf_ranks(u, n, alpha: float):
    """Ranks in [0, n) with P(rank r) ~ (r+1)^-alpha, from uniforms ``u``
    in [0, 1): the inverse CDF of the continuous bounded power law. ``n``
    is a number or an array that broadcasts against ``u``."""
    import jax.numpy as jnp
    a = 1.0 - alpha
    n = jnp.asarray(n, jnp.int32)
    x = (((n + 1).astype(jnp.float32) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return jnp.clip(jnp.floor(x).astype(jnp.int32) - 1, 0, n - 1)


def _criteo_zipf(key, mix: dict):
    import jax
    import jax.numpy as jnp
    P, B = int(mix["pool_batches"]), int(mix["batch"])
    nd, nc = int(mix["num_dense"]), int(mix["num_cat"])
    card = np.asarray(mix["cardinalities"], np.int64)
    if card.shape != (nc,) or card.min() < 1 or card.sum() >= 2 ** 31:
        raise ValueError(f"cardinalities: {nc} counts >= 1 that sum to "
                         "under 2**31 are needed")
    alpha = float(mix["zipf_alpha"])
    kd, kc, kw, kn = jax.random.split(key, 4)
    dense = jax.random.normal(kd, (P, B, nd), jnp.float32)
    u = jax.random.uniform(kc, (P, B, nc))
    if alpha > 0:
        ranks = _zipf_ranks(u, card.astype(np.int32), alpha)
    else:       # uniform keys: no hot rows, nothing for dedup to merge
        # (float32 uniforms: a field of over 2**24 values is drawn from
        # 2**24 of them, evenly spread)
        ranks = jnp.minimum((u * card.astype(np.float32)).astype(jnp.int32),
                            card.astype(np.int32) - 1)
    starts = np.concatenate([[0], np.cumsum(card)[:-1]]).astype(np.int32)
    cat = ranks + starts
    # the hidden model has the same strength under every seed, so that
    # the loss a run can reach does not depend on the seed
    w = jax.random.normal(kw, (nd,), jnp.float32)
    w = w * jnp.sqrt(nd / jnp.sum(w * w))
    effect = jnp.sum((cat % 97).astype(jnp.float32) / 97.0 - 0.5, -1)
    logit = (dense @ w) * 0.5 + 0.3 * effect \
        + 0.5 * jax.random.normal(kn, (P, B), jnp.float32)
    return {"dense": dense, "cat": cat,
            "y": (logit > 0).astype(jnp.float32)}


def _lm_tokens(key, mix: dict):
    import jax
    import jax.numpy as jnp
    P, B, T = int(mix["pool_batches"]), int(mix["batch"]), \
        int(mix["seq_len"])
    vocab, alpha = int(mix["vocab"]), float(mix["zipf_alpha"])
    ku, kj = jax.random.split(key)
    toks = _zipf_ranks(jax.random.uniform(ku, (P, B, T + 1)), vocab, alpha)
    # weak structure: an odd position repeats a function of its neighbour
    jump = jax.random.randint(kj, (P, B, T + 1), 0, 50, jnp.int32)
    left = jnp.roll(toks, 1, axis=-1)
    odd = (jnp.arange(T + 1) % 2) == 1
    toks = jnp.where(odd, (left + jump) % vocab, toks)
    return {"tokens": toks.astype(jnp.int32)}


KINDS = {"criteo_zipf": _criteo_zipf, "lm_tokens": _lm_tokens}


def make_pool(mix: dict, seed: int) -> dict:
    """The run's pool of batches as host arrays ``[pool, batch, ...]``:
    made on the device in one jitted call, fetched once. The window feeds
    ``pool[i % pool_batches]`` through the program's own feed."""
    import jax
    kind = mix["kind"]
    if kind not in KINDS:
        raise ValueError(f"unknown traffic kind {kind!r} "
                         f"(have {sorted(KINDS)})")
    made = jax.jit(lambda k: KINDS[kind](k, mix))(_key(seed))
    return {k: np.asarray(v) for k, v in made.items()}


def batch_of(pool: dict, i: int) -> dict:
    n = next(iter(pool.values())).shape[0]
    return {k: v[i % n] for k, v in pool.items()}


def samples_per_step(mix: dict) -> int:
    return int(mix["batch"])


def tokens_per_step(mix: dict) -> int:
    return int(mix["batch"]) * int(mix.get("seq_len", 0))
