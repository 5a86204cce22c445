"""GPipe pipeline parallelism: stage-sharded transformer vs the unsharded
oracle (logits + grads), plus the generic schedule on a toy stage_fn.

Beyond parity (reference has no PP, SURVEY.md §2.2)."""

import functools

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.parallel.pipeline import gpipe, stack_layers, unstack_layers

CFG = dict(vocab=29, dim=16, heads=2, depth=4, max_len=32)
F32 = dict(compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def mesh_pp():
    # 2 data x 4 model: pipeline over the 4-way model axis
    return make_mesh(2, model_size=4)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), **CFG)


def _stacked(params):
    return {**params, "blocks": stack_layers(params["blocks"])}


def _toks(B, T, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG["vocab"], (B, T)), jnp.int32)


def test_stack_roundtrip(params):
    s = stack_layers(params["blocks"])
    back = unstack_layers(s)
    f1, _ = jax.flatten_util.ravel_pytree(params["blocks"])
    f2, _ = jax.flatten_util.ravel_pytree(back)
    np.testing.assert_array_equal(np.asarray(f1), np.asarray(f2))


def test_gpipe_schedule_identity():
    """With stage_fn = (x -> x + own-stage constant), the pipeline output
    is x + sum of constants, for every microbatch — the schedule routes
    every microbatch through every stage exactly once."""
    mesh = make_mesh(1, model_size=4)
    consts = jnp.arange(4.0)  # one per stage

    def run(x_mb, c):
        def shard_fn(x_, c_):
            return gpipe(lambda h: h + c_[0], x_, axis_name="model")
        return shard_map(
            shard_fn, mesh=mesh, in_specs=(P(), P("model")),
            out_specs=P())(x_mb, c)

    x = jnp.arange(3 * 2 * 2, dtype=jnp.float32).reshape(3, 2, 2)
    out = run(x, consts)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x) + 6.0)


@pytest.mark.parametrize("M", [1, 2, 4])
def test_pp_logits_match_full(mesh_pp, params, M):
    tokens = _toks(4, 16)
    want = tfm.apply(params, tokens, heads=CFG["heads"], **F32)
    sp = _stacked(params)
    specs = tfm.pp_specs(sp)
    got = shard_map(
        lambda p, t: tfm.apply_pp(p, t, heads=CFG["heads"],
                                  num_microbatches=M, **F32),
        mesh=mesh_pp, in_specs=(specs, P()), out_specs=P())(sp, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # fast tier keeps pp logits parity (3 microbatch cfgs)
def test_pp_grad_matches_full(mesh_pp, params):
    toks = _toks(4, 17, seed=1)
    sp = _stacked(params)
    specs = tfm.pp_specs(sp)

    def pp_loss(p):
        def shard_fn(p_, t_):
            logits = tfm.apply_pp(p_, t_[:, :-1], heads=CFG["heads"],
                                  num_microbatches=2, **F32)
            logp = jax.nn.log_softmax(logits)
            return jnp.mean(
                -jnp.take_along_axis(logp, t_[:, 1:, None], axis=-1)[..., 0])
        return shard_map(shard_fn, mesh=mesh_pp,
                             in_specs=(specs, P()), out_specs=P())(p, toks)

    def full_loss(p):
        return tfm.loss(p, {"tokens": toks}, heads=CFG["heads"], **F32)

    l_pp, g_pp = jax.value_and_grad(pp_loss)(sp)
    l_f, g_f = jax.value_and_grad(full_loss)(params)
    assert abs(float(l_pp) - float(l_f)) < 1e-5
    # compare stacked grads against stacked full grads
    g_f_stacked = {**g_f, "blocks": stack_layers(g_f["blocks"])}
    f1, _ = jax.flatten_util.ravel_pytree(g_f_stacked)
    f2, _ = jax.flatten_util.ravel_pytree(g_pp)
    np.testing.assert_allclose(np.asarray(f2), np.asarray(f1),
                               rtol=2e-4, atol=2e-4)


def test_pp_bad_microbatch_raises(mesh_pp, params):
    sp = _stacked(params)
    specs = tfm.pp_specs(sp)
    with pytest.raises(ValueError, match="microbatch"):
        shard_map(
            lambda p, t: tfm.apply_pp(p, t, heads=CFG["heads"],
                                      num_microbatches=3),
            mesh=mesh_pp, in_specs=(specs, P()), out_specs=P()
        )(sp, _toks(4, 8))


def test_pp_rope_logits_match_full(mesh_pp):
    """RoPE through the pipeline: the stage closure applies the rotation
    (the _forward wrap can't reach it) — logits must match the
    single-program oracle. depth=4 -> one block per stage."""
    p = tfm.init(jax.random.PRNGKey(12), vocab=CFG["vocab"], dim=32,
                 heads=4, depth=4, rope=True)
    tokens = _toks(4, 16, seed=12)
    want = tfm.apply(p, tokens, heads=4, **F32)
    sp = {**p, "blocks": stack_layers(p["blocks"])}
    specs = tfm.pp_specs(sp)
    got = shard_map(
        lambda q, t: tfm.apply_pp(q, t, heads=4, num_microbatches=2,
                                  **F32),
        mesh=mesh_pp, in_specs=(specs, P()), out_specs=P())(sp, tokens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)
