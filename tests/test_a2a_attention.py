"""All-to-all (Ulysses-style) sequence parallelism vs oracles.

parallel/a2a_attention.py re-shards [B, T/N, H, D] sequence shards into
head groups with the full sequence local (two all_to_alls per attention),
so attention itself runs any single-device impl — including the flash
kernel — with no ring bookkeeping. These tests pin exact parity with the
full-sequence oracle across MHA/GQA/MQA, RoPE, both inner impls, the
training-grad path, and the loud head-divisibility refusal.
"""

import functools

import jax

import jax.flatten_util
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from jax import shard_map
from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.a2a_attention import a2a_attention_local
from minips_tpu.parallel.ring_attention import reference_attention

F32 = dict(compute_dtype=jnp.float32)


def _mesh(n):
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def _toks(B, T, vocab=61, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, size=(B, T)), jnp.int32)


# ------------------------------------------------------------- raw op
@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("kv_heads", [8, 4, 1])
def test_a2a_local_matches_reference(mesh8, causal, kv_heads):
    """Raw op parity on a 4-way mesh: kv=8 (MHA), kv=4 (GQA, divisible —
    the small-wire path), kv=1 (MQA, expand-before-exchange path)."""
    n = 4
    rng = np.random.default_rng(1)
    B, T, H, D = 2, 32, 8, 4
    q = jnp.asarray(rng.normal(size=(B, T, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, T, kv_heads, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, kv_heads, D)), jnp.float32)
    want = reference_attention(q, k, v, causal=causal)
    spec = P(None, "data")
    got = jax.jit(shard_map(
        functools.partial(a2a_attention_local, axis_name="data",
                          causal=causal),
        mesh=_mesh(n), in_specs=(spec, spec, spec), out_specs=spec,
    ))(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_a2a_rejects_indivisible_heads(mesh8):
    q = jnp.zeros((1, 8, 4, 4))  # 4 heads over an 8-way axis
    spec = P(None, "data")
    with pytest.raises(ValueError, match="divisible"):
        jax.jit(shard_map(
            functools.partial(a2a_attention_local, axis_name="data"),
            mesh=_mesh(8), in_specs=(spec, spec, spec), out_specs=spec,
        ))(q, q, q)


# ------------------------------------------------- through the model
def _sp_logits_n(n, params, tokens, heads, attn_impl):
    T_local = tokens.shape[1] // n

    def shard_fn(p, toks):
        shift = jax.lax.axis_index("data") * T_local
        return tfm.apply_sp(p, toks, shift, heads=heads,
                            attn_impl=attn_impl, **F32)

    return shard_map(shard_fn, mesh=_mesh(n),
                         in_specs=(P(), P(None, "data")),
                         out_specs=P(None, "data"))(params, tokens)


@pytest.mark.parametrize("attn_impl", ["a2a", "a2a_flash"])
def test_a2a_sp_forward_matches_full(mesh8, attn_impl):
    p = tfm.init(jax.random.PRNGKey(0), vocab=61, dim=32, heads=8,
                 depth=2, max_len=64)
    tokens = _toks(2, 64)
    want = tfm.apply(p, tokens, heads=8, **F32)
    got = _sp_logits_n(4, p, tokens, 8, attn_impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_a2a_rope_sp_forward_matches_full(mesh8):
    """RoPE rotates by GLOBAL position on the sequence-sharded side
    BEFORE the exchange — the reassembled sequence must equal the
    single-program oracle."""
    p = tfm.init(jax.random.PRNGKey(9), vocab=61, dim=32, heads=8,
                 depth=2, rope=True)
    tokens = _toks(2, 64, seed=9)
    want = tfm.apply(p, tokens, heads=8, **F32)
    got = _sp_logits_n(4, p, tokens, 8, "a2a")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_a2a_gqa_sp_forward_matches_full(mesh8):
    """GQA with kv_heads divisible by the axis (the small-wire case:
    the exchange carries only kv_heads/N heads of K/V per device)."""
    p = tfm.init(jax.random.PRNGKey(4), vocab=61, dim=32, heads=8,
                 depth=2, max_len=64, kv_heads=4)
    tokens = _toks(2, 64, seed=4)
    want = tfm.apply(p, tokens, heads=8, **F32)
    got = _sp_logits_n(4, p, tokens, 8, "a2a")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_a2a_grad_matches_full(mesh8):
    """Training equivalence: d(loss)/d(params) identical whether the
    sequence is a2a-sharded 4 ways or computed in one program (the same
    oracle the ring grad test uses, without the ring's heavy compile)."""
    B, T, n = 2, 32, 4
    toks = _toks(B, T + 1, seed=2)
    p = tfm.init(jax.random.PRNGKey(1), vocab=61, dim=32, heads=8,
                 depth=1, max_len=64)
    T_local = T // n

    def shard_fn(p_, i_, t_):
        shift = jax.lax.axis_index("data") * T_local
        return tfm.loss_sp(p_, i_, t_, shift, heads=8,
                           attn_impl="a2a", **F32)

    l_a2a, g_a2a = jax.value_and_grad(lambda q: shard_map(
        shard_fn, mesh=_mesh(n),
        in_specs=(P(), P(None, "data"), P(None, "data")),
        out_specs=P())(q, toks[:, :-1], toks[:, 1:]))(p)
    full = functools.partial(tfm.loss, heads=8, **F32)
    l_full, g_full = jax.value_and_grad(
        lambda q: full(q, {"tokens": toks}))(p)
    np.testing.assert_allclose(float(l_a2a), float(l_full), rtol=1e-6)
    fa, _ = jax.flatten_util.ravel_pytree(g_a2a)
    ff, _ = jax.flatten_util.ravel_pytree(g_full)
    np.testing.assert_allclose(np.asarray(fa), np.asarray(ff),
                               rtol=2e-4, atol=2e-5)
