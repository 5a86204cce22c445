"""Expert-parallel MoE (Switch-style top-1) vs the dense oracle.

Beyond parity (reference has no EP, SURVEY.md §2.2): tokens sharded over
the data axis, experts sharded over the same axis, two all_to_alls per
layer. With non-binding capacity the distributed output must equal the
oracle token-for-token; grads (router included) must match too."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.parallel.moe import (
    ep_specs,
    init_moe,
    moe_apply_dense,
    moe_apply_local,
)

E, D, HID = 8, 16, 32
F32 = dict(compute_dtype=jnp.float32)


@pytest.fixture(scope="module")
def params():
    return init_moe(jax.random.PRNGKey(0), E, D, HID)


def _x(N, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (N, D), jnp.float32)


def _ep_apply(mesh, params, x, capacity):
    f = shard_map(
        lambda p, x_: moe_apply_local(p, x_, axis_name="data",
                                      capacity=capacity, **F32),
        mesh=mesh, in_specs=(ep_specs("data"), P("data")),
        out_specs=(P("data"), P()))
    return f(params, x)


def test_ep_matches_dense_oracle(mesh8, params):
    x = _x(64)
    # capacity 64 can never bind (each source device has only 8 tokens)
    y_ep, aux_ep = _ep_apply(mesh8, params, x, capacity=64)
    y_dense, aux_dense = moe_apply_dense(params, x, capacity=1024, **F32)
    np.testing.assert_allclose(np.asarray(y_ep), np.asarray(y_dense),
                               rtol=1e-4, atol=1e-5)
    # aux loss: dense computes over all tokens; ep pmeans per-device stats.
    # frac/mean_p are means over equal-sized shards, so they agree.
    assert abs(float(aux_ep) - float(aux_dense)) < 1e-5


def test_ep_grads_match_dense(mesh8, params):
    x = _x(64, seed=1)
    tgt = _x(64, seed=2)

    def loss_ep(p):
        def shard_fn(p_, x_, t_):
            y, aux = moe_apply_local(p_, x_, axis_name="data",
                                     capacity=64, **F32)
            return (jax.lax.pmean(jnp.mean((y - t_) ** 2), "data")
                    + 0.01 * aux)
        return shard_map(
            shard_fn, mesh=mesh8,
            in_specs=(ep_specs("data"), P("data"), P("data")),
            out_specs=P())(p, x, tgt)

    def loss_dense(p):
        y, aux = moe_apply_dense(p, x, capacity=1024, **F32)
        return jnp.mean((y - tgt) ** 2) + 0.01 * aux

    l_e, g_e = jax.value_and_grad(loss_ep)(params)
    l_d, g_d = jax.value_and_grad(loss_dense)(params)
    assert abs(float(l_e) - float(l_d)) < 1e-5
    fe, _ = jax.flatten_util.ravel_pytree(g_e)
    fd, _ = jax.flatten_util.ravel_pytree(g_d)
    np.testing.assert_allclose(np.asarray(fe), np.asarray(fd),
                               rtol=2e-4, atol=1e-5)


def test_capacity_drops_tokens(params):
    """With capacity 1, each expert processes at most one token; dropped
    tokens output zero (standard Switch behavior)."""
    x = _x(32, seed=3)
    y, _ = moe_apply_dense(params, x, capacity=1, **F32)
    y_full, _ = moe_apply_dense(params, x, capacity=1024, **F32)
    norms = np.linalg.norm(np.asarray(y), axis=-1)
    assert (norms < 1e-9).sum() >= 32 - E        # most tokens dropped
    # surviving tokens match the uncapped output
    alive = norms > 1e-9
    np.testing.assert_allclose(np.asarray(y)[alive],
                               np.asarray(y_full)[alive],
                               rtol=1e-5, atol=1e-6)


def test_router_trains_toward_balance(mesh8, params):
    """Minimizing the aux loss pushes routing toward uniform expert use."""
    import optax

    x = _x(256, seed=4)
    p = jax.tree.map(jnp.copy, params)
    tx = optax.adam(5e-2)
    opt = tx.init(p)

    def loss(p_):
        _, aux = moe_apply_dense(p_, x, capacity=1024, **F32)
        return aux

    for _ in range(30):
        g = jax.grad(loss)(p)
        updates, opt = tx.update(g, opt, p)
        p = optax.apply_updates(p, updates)
    assert float(loss(p)) < float(loss(params))


def test_expert_count_mismatch_raises(mesh8, params):
    # 16 experts stacked (shards cleanly 8-way, 2 per device) but the
    # router still claims 8 -> moe_apply_local's own guard must fire
    bad = dict(params,
               w_in=jnp.concatenate([params["w_in"]] * 2),
               w_out=jnp.concatenate([params["w_out"]] * 2))
    with pytest.raises(ValueError, match="devices hold"):
        _ep_apply(mesh8, bad, _x(64), capacity=8)


class TestMoELM:
    """MoE transformer (dp attention + ep FFN over the same axis)."""

    CFG = dict(vocab=23, dim=16, heads=2, depth=2, max_len=32,
               num_experts=8, expert_hidden=32)

    @pytest.fixture(scope="class")
    def lm_params(self):
        from minips_tpu.models import transformer as tfm
        return tfm.init_moe_lm(jax.random.PRNGKey(1), **self.CFG)

    def _toks(self, B, T, seed=0):
        rng = jax.random.PRNGKey(seed)
        return jax.random.randint(rng, (B, T), 0, self.CFG["vocab"])

    @pytest.mark.slow  # fast tier: test_ep_matches_dense_oracle
    def test_ep_lm_matches_dense(self, mesh8, lm_params):
        from minips_tpu.models import transformer as tfm

        toks = self._toks(8, 12)
        want, aux_want = tfm.apply_moe_dense(
            lm_params, toks, heads=2, capacity=2048, **F32)
        f = shard_map(
            lambda p, t: tfm.apply_ep(p, t, heads=2, capacity=256, **F32),
            mesh=mesh8,
            in_specs=(tfm.ep_lm_specs(lm_params), P("data")),
            out_specs=(P("data"), P()))
        got, aux_got = f(lm_params, toks)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)
        assert abs(float(aux_got) - float(aux_want)) < 1e-5

    @pytest.mark.slow  # fast tier: test_ep_grads_match_dense
    def test_ep_lm_trains(self, mesh8, lm_params):
        """value_and_grad outside the shard_map; loss decreases."""
        import optax
        from minips_tpu.models import transformer as tfm

        toks = self._toks(8, 13, seed=2)

        def loss(p):
            def shard_fn(p_, t_):
                logits, aux = tfm.apply_ep(p_, t_[:, :-1], heads=2,
                                           capacity=256, **F32)
                return jax.lax.pmean(
                    tfm.nll(logits, t_[:, 1:]), "data") + 0.01 * aux
            return shard_map(
                shard_fn, mesh=mesh8,
                in_specs=(tfm.ep_lm_specs(lm_params), P("data")),
                out_specs=P())(p, toks)

        tx = optax.adam(1e-2)
        p = jax.tree.map(jnp.copy, lm_params)
        opt = tx.init(p)

        @jax.jit
        def step(p, opt):
            l, g = jax.value_and_grad(loss)(p)
            updates, opt = tx.update(g, opt, p)
            return optax.apply_updates(p, updates), opt, l

        first = None
        for _ in range(15):
            p, opt, l = step(p, opt)
            if first is None:
                first = float(l)
        assert float(loss(p)) < first


class TestTopK:
    """GShard-style top-2 routing (k_top) on the same dispatch machinery."""

    def test_top2_matches_direct_sum_when_capacity_ample(self, params):
        """With no drops, top-2 output == sum over each token's two best
        experts of gate_e * FFN_e(token), computed directly."""
        x = _x(32, seed=3)
        y, _ = moe_apply_dense(params, x, capacity=64, k_top=2, **F32)

        probs = jax.nn.softmax(x @ params["router"], axis=-1)
        gate, idx = jax.lax.top_k(probs, 2)
        expected = jnp.zeros_like(x)
        for n in range(x.shape[0]):
            for r in range(2):
                e = int(idx[n, r])
                h = jax.nn.gelu(x[n] @ params["w_in"][e])
                expected = expected.at[n].add(
                    float(gate[n, r]) * (h @ params["w_out"][e]))
        np.testing.assert_allclose(y, expected, atol=1e-4, rtol=1e-4)

    def test_top2_ep_matches_dense(self, mesh8, params):
        x = _x(64, seed=4)
        yd, auxd = moe_apply_dense(params, x, capacity=64, k_top=2, **F32)
        f = shard_map(
            lambda p, x_: moe_apply_local(p, x_, axis_name="data",
                                          capacity=64, k_top=2, **F32),
            mesh=mesh8, in_specs=(ep_specs("data"), P("data")),
            out_specs=(P("data"), P()))
        ye, auxe = f(params, x)
        np.testing.assert_allclose(ye, yd, atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(auxe, auxd, atol=1e-5, rtol=1e-5)

    def test_top2_primary_survives_capacity_pressure(self, params):
        """Rank-major slot assignment: when capacity binds, every kept
        secondary route has a queue position after ALL kept primaries of
        its expert — no token loses its primary to another's secondary."""
        from minips_tpu.parallel.moe import _dispatch_combine

        x = _x(48, seed=5)
        cap = 3  # far below 48/8: heavy pressure
        dispatch, _, _, _ = _dispatch_combine(
            x, params["router"], E, cap, k_top=2)
        probs = jax.nn.softmax(x @ params["router"], axis=-1)
        _, idx = jax.lax.top_k(probs, 2)
        routed = dispatch.sum(axis=(1, 2))  # 0..2 kept routes per token
        # every expert's slots fill with primaries first: count primaries
        # kept vs total primaries per expert
        for e in range(E):
            primaries = [n for n in range(48) if int(idx[n, 0]) == e]
            kept_primary = sum(
                float(dispatch[n, e].sum()) > 0 for n in primaries)
            # the first min(cap, #primaries) primaries must all be kept
            assert kept_primary == min(cap, len(primaries))

    def test_top1_equals_legacy_switch(self, params):
        """k_top=1 is bit-for-bit the original Switch path."""
        x = _x(40, seed=6)
        y1, a1 = moe_apply_dense(params, x, capacity=8, **F32)
        y2, a2 = moe_apply_dense(params, x, capacity=8, k_top=1, **F32)
        np.testing.assert_array_equal(y1, y2)
        np.testing.assert_array_equal(a1, a2)


@pytest.mark.slow  # app-level ep sweep; library ep parity stays fast
def test_lm_example_ep_layout_trains(mesh8):
    """The ep layout trains the MoE-LM end-to-end from the app surface
    (experts sharded over the 8-device mesh, top-2 routing)."""
    import argparse

    from minips_tpu.apps import lm_example as app
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.utils.metrics import MetricsLogger

    cfg = Config(
        table=TableConfig(name="lm", kind="dense", updater="adam", lr=3e-3),
        train=TrainConfig(batch_size=16, num_iters=10, log_every=100),
    )
    args = argparse.Namespace(layout="ep", seq_len=32, experts=8, k_top=2,
                              capacity=0, tp=2, microbatches=2)
    out = app.run(cfg, args, MetricsLogger(None, verbose=False))
    losses = out["losses"]
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0], losses
