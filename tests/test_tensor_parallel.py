"""Tensor parallelism (Megatron-style) on the reserved ``model`` mesh axis.

Beyond parity (reference has no TP, SURVEY.md §2.2): block weights shard
column-/row-parallel, activations replicate, two psums per block. Tests
prove logits and gradients match the unsharded oracle, and that TP composes
with data parallelism on a 2D (data=4, model=2) mesh.
"""

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.mesh import make_mesh

CFG = dict(vocab=31, dim=32, heads=4, depth=2, max_len=64)
F32 = dict(compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def mesh42():
    return make_mesh(4, model_size=2)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), **CFG)


def _toks(B, T, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG["vocab"], (B, T)), jnp.int32)


def test_tp_logits_match_full(mesh42, params):
    tokens = _toks(2, 16)
    want = tfm.apply(params, tokens, heads=CFG["heads"], **F32)

    specs = tfm.tp_specs(params)
    f = shard_map(
        lambda p, t: tfm.apply_tp(p, t, heads=CFG["heads"], **F32),
        mesh=mesh42, in_specs=(specs, P()), out_specs=P())
    got = f(params, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # fast tier keeps tp logits parity + dpxtp compose
def test_tp_grad_matches_full(mesh42, params):
    toks = _toks(2, 17, seed=1)

    def full_loss(p):
        return tfm.loss(p, {"tokens": toks}, heads=CFG["heads"], **F32)

    def tp_loss(p):
        specs = tfm.tp_specs(params)

        def shard_fn(p_, t_):
            logits = tfm.apply_tp(p_, t_[:, :-1], heads=CFG["heads"], **F32)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(logp, t_[:, 1:, None], axis=-1)[..., 0]
            return jnp.mean(nll)

        return shard_map(shard_fn, mesh=mesh42,
                             in_specs=(specs, P()), out_specs=P())(p, toks)

    l_f, g_f = jax.value_and_grad(full_loss)(params)
    l_t, g_t = jax.value_and_grad(tp_loss)(params)
    assert abs(float(l_f) - float(l_t)) < 1e-5
    f1, _ = jax.flatten_util.ravel_pytree(g_f)
    f2, _ = jax.flatten_util.ravel_pytree(g_t)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)


def test_tp_composes_with_dp(mesh42, params):
    """2D mesh: batch sharded over data (4), weights over model (2) — one
    optax SGD step matches the single-device step.

    The supported composition is value_and_grad OUTSIDE the shard_map (as
    in Megatron's conjugate f/g operators, which JAX's shard_map transpose
    implements automatically); taking raw local grads inside would miss
    the cross-rank reductions replicated params need."""
    import optax

    toks = _toks(8, 17, seed=2)
    specs = tfm.tp_specs(params)
    tx = optax.sgd(0.1)

    def tp_loss(p):
        def shard_fn(p_, t_):
            logits = tfm.apply_tp(p_, t_[:, :-1], heads=CFG["heads"], **F32)
            logp = jax.nn.log_softmax(logits)
            nll = -jnp.take_along_axis(
                logp, t_[:, 1:, None], axis=-1)[..., 0]
            return jax.lax.pmean(jnp.mean(nll), "data")
        return shard_map(shard_fn, mesh=mesh42,
                             in_specs=(specs, P("data")),
                             out_specs=P())(p, toks)

    @jax.jit
    def step_2d(p):
        loss, g = jax.value_and_grad(tp_loss)(p)
        updates, _ = tx.update(g, tx.init(p), p)
        return optax.apply_updates(p, updates), loss

    def full_step(p):
        def l(p_):
            return tfm.loss(p_, {"tokens": toks}, heads=CFG["heads"], **F32)
        loss, g = jax.value_and_grad(l)(p)
        updates, _ = tx.update(g, tx.init(p), p)
        return optax.apply_updates(p, updates), loss

    new_p, loss2d = step_2d(params)
    want_p, loss1 = full_step(params)
    assert abs(float(loss2d) - float(loss1)) < 1e-5
    f2, _ = jax.flatten_util.ravel_pytree(new_p)
    f1, _ = jax.flatten_util.ravel_pytree(want_p)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)


def test_tp_heads_not_divisible_raises(mesh42, params):
    specs = tfm.tp_specs(params)
    with pytest.raises(ValueError, match="divisible"):
        shard_map(
            lambda p, t: tfm.apply_tp(p, t, heads=3),
            mesh=mesh42, in_specs=(specs, P()), out_specs=P()
        )(params, _toks(1, 8))


def test_tp_gqa_logits_match_full(mesh42):
    """GQA under TP: wq/wkv shard column-parallel at head boundaries
    (each model shard computes 2 q-heads over 1 kv head here); logits
    must match the unsharded oracle."""
    p = tfm.init(jax.random.PRNGKey(7), **{**CFG, "kv_heads": 2})
    tokens = _toks(2, 16, seed=7)
    want = tfm.apply(p, tokens, heads=CFG["heads"], **F32)
    specs = tfm.tp_specs(p)
    f = shard_map(
        lambda q, t: tfm.apply_tp(q, t, heads=CFG["heads"], **F32),
        mesh=mesh42, in_specs=(specs, P()), out_specs=P())
    got = f(p, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_tp_gqa_kv_not_divisible_raises(mesh42):
    """kv_heads=1 cannot split across model=2 shards — apply_tp must
    refuse loudly instead of computing garbage."""
    p = tfm.init(jax.random.PRNGKey(7), **{**CFG, "kv_heads": 1})
    tokens = _toks(1, 8)
    specs = tfm.tp_specs(p)
    f = shard_map(
        lambda q, t: tfm.apply_tp(q, t, heads=CFG["heads"], **F32),
        mesh=mesh42, in_specs=(specs, P()), out_specs=P())
    with pytest.raises(ValueError, match="kv_heads"):
        f(p, tokens)
