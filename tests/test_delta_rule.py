"""The chunked gated delta rule (ops/delta_rule.py) against the
token-by-token recurrence it stands for: forward and every gradient (q, k,
v, g, beta) in float32 to 1e-5 of the largest entry, at a T of several
chunks, a write strength in (1, 2) and a strong decay among the cases; two
chunk sizes give one result; a sequence's result does not depend on what
else is in the batch; bfloat16 inputs stay inside a stated band; and a
block checkpoint that saves the scan's named residuals runs the forward
scan once.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.ops import delta_rule as dr
from minips_tpu.utils import profiling as prof

B, T, H, DK, DV = 2, 256, 3, 24, 48
GRADS = ("q", "k", "v", "g", "beta")


def _inputs(case: str, seed: int = 0, b: int = B):
    """q (unit length, scaled), k (unit length), v, g <= 0, beta and a
    cotangent for o. ``plain``: decay about 0.93 a token, beta in (0, 2);
    ``neg_eigval``: beta in (1, 2), so that every eigenvalue along k is
    negative; ``strong_decay``: exp(g) down to e^-10 a token, so that a
    chunk's running sums underflow."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (b, T, H, DK))) / np.sqrt(DK)
    k = unit(jax.random.normal(ks[1], (b, T, H, DK)))
    v = jax.random.normal(ks[2], (b, T, H, DV))
    g = -jax.nn.softplus(jax.random.normal(ks[3], (b, T, H))) \
        * (8.0 if case == "strong_decay" else 0.1)
    s = jax.nn.sigmoid(jax.random.normal(ks[4], (b, T, H)))
    beta = 1.0 + s if case == "neg_eigval" else 2.0 * s
    return (q, k, v, g, beta), jax.random.normal(ks[5], (b, T, H, DV))


def _close(got, want, tol):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))) \
        <= tol * scale


@pytest.mark.parametrize("chunk", [64, 32])
@pytest.mark.parametrize("case", ["plain", "neg_eigval", "strong_decay"])
def test_chunked_rule_is_the_recurrence_forward_and_every_gradient(case,
                                                                   chunk):
    args, w = _inputs(case)
    _close(dr.gated_delta_rule(*args, chunk=chunk),
           dr.delta_rule_recurrence(*args), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(
        dr.gated_delta_rule(*a, chunk=chunk) * w), argnums=range(5))(*args)
    want = jax.grad(lambda *a: jnp.sum(
        dr.delta_rule_recurrence(*a) * w), argnums=range(5))(*args)
    for name, a, b in zip(GRADS, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        _close(a, b, 1e-5)


def test_two_chunk_sizes_give_one_result():
    args, w = _inputs("neg_eigval", seed=3)
    f = lambda c: jax.value_and_grad(lambda *a: jnp.sum(    # noqa: E731
        dr.gated_delta_rule(*a, chunk=c) * w), argnums=range(5))(*args)
    (o64, g64), (o16, g16) = f(64), f(16)
    assert float(abs(o64 - o16)) <= 1e-5 * float(abs(o64)) + 1e-4
    for a, b in zip(g64, g16):
        _close(a, b, 1e-5)


def test_a_sequence_does_not_see_the_rest_of_the_batch():
    """Every row starts from a zero state: row 0 alone, row 0 beside
    another row and row 0 beside itself give the same output and the same
    gradients."""
    (q, k, v, g, beta), w = _inputs("plain", seed=5)
    other = _inputs("strong_decay", seed=6)[0]
    alone = tuple(x[:1] for x in (q, k, v, g, beta))
    mixed = tuple(jnp.concatenate([a, o[1:]]) for a, o in zip(alone, other))
    f = lambda args: jax.vjp(dr.gated_delta_rule, *args)    # noqa: E731
    (o1, pull1), (o2, pull2) = f(alone), f(mixed)
    np.testing.assert_allclose(o1[0], o2[0], atol=1e-6)
    d1 = pull1(w[:1])
    d2 = pull2(jnp.concatenate([w[:1], w[1:] * 3.0]))
    for a, b in zip(d1, d2):
        np.testing.assert_allclose(a[0], b[0], atol=1e-5)


def test_bfloat16_products_stay_inside_their_band():
    """q, k, v in bfloat16 (g, beta, gamma, the solve and the state stay
    float32): the output within 2% of the recurrence's on the same rounded
    inputs, as the norm of the difference."""
    (q, k, v, g, beta), _ = _inputs("neg_eigval", seed=8)
    low = tuple(x.astype(jnp.bfloat16) for x in (q, k, v))
    o = dr.gated_delta_rule(*low, g, beta)
    assert o.dtype == jnp.bfloat16
    want = dr.delta_rule_recurrence(*low, g, beta)
    gap = float(jnp.linalg.norm(o.astype(jnp.float32) - want)
                / jnp.linalg.norm(want))
    assert gap <= 0.02, gap


def test_a_length_that_is_no_whole_number_of_chunks_is_refused():
    args, _ = _inputs("plain")
    with pytest.raises(ValueError, match="chunks of 48"):
        dr.gated_delta_rule(*args, chunk=48)


def test_the_states_handed_out_are_the_recurrences():
    """``chunk_states``: the state at each chunk's start and after the
    last token, as the recurrence reaches them."""
    (q, k, v, g, beta), _ = _inputs("neg_eigval", seed=2)
    o, states, last = dr.chunk_states(q, k, v, g, beta, 64)
    assert states.shape == (T // 64, B, H, DK, DV)
    np.testing.assert_array_equal(states[0], 0.0)

    def state_after(n):
        S = jnp.zeros((B, H, DK, DV))
        for t in range(n):
            S = S * jnp.exp(g[:, t])[..., None, None]
            u = beta[:, t][..., None] * (
                v[:, t] - jnp.einsum("bhkv,bhk->bhv", S, k[:, t]))
            S = S + k[:, t][..., :, None] * u[..., None, :]
        return S
    np.testing.assert_allclose(states[1], state_after(64), atol=2e-6)
    np.testing.assert_allclose(last, state_after(T), atol=5e-6)


def _scans(jaxpr) -> int:
    n = 0
    for eqn in jaxpr.eqns:
        n += eqn.primitive.name == "scan"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            n += _scans(sub)
    return n


def test_a_checkpoint_that_saves_the_named_residuals_scans_forward_once():
    """Under a block checkpoint whose policy saves ``GDN_RESIDUALS`` the
    gradient holds two scans (forward, backward); one that saves nothing
    runs the forward scan again for the backward pass: three."""
    args, w = _inputs("plain")
    f = lambda *a: jnp.sum(dr.gated_delta_rule(*a) * w)     # noqa: E731
    policies = jax.checkpoint_policies

    def scans(policy):
        g = jax.grad(jax.checkpoint(f, policy=policy), argnums=range(5))
        return _scans(jax.make_jaxpr(g)(*args).jaxpr)
    assert scans(policies.save_only_these_names(*prof.GDN_RESIDUALS)) == 2
    assert scans(policies.nothing_saveable) == 3
