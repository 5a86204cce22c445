"""The ZAYA1-shaped decoder (models/zaya.py, parallel/moe.py's dropless
layer) against the benchmark's own plain reference
(bench/benchlib/reference/zaya_ref.py, found through tests/conftest.py's
path hook), at a size a test run can hold: the whole model through
``DenseTable.make_step`` for three steps with the router's balancing bias
carried beside the table, the expert layer's shares, the dropless case,
what the block and the attention's pieces may and may not see, and the
named scopes in the compiled step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.models import zaya
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.parallel.moe import dropless_dispatch, moe_apply_dropless
from minips_tpu.utils import profiling as prof
from tests.conftest import add_bench_paths

CONFIG = {
    "vocab_size": 96, "hidden_size": 32, "num_hidden_layers": 2,
    "num_attention_heads": 8, "num_key_value_heads": 2, "head_dim": 8,
    "cca_time0": 2, "cca_time1": 2, "rms_norm_eps": 1e-5,
    "rope_parameters": {"hybrid": {"partial_rotary_factor": 0.5,
                                   "rope_theta": 5000000}},
    "moe_intermediate_size": 16, "router_hidden_size": 8,
    "num_experts": 2, "published": {"num_experts": 4},
    "held_experts": [0, 2], "num_experts_per_tok": 1, "hidden_act": "silu",
    "tie_word_embeddings": True, "attention_bias": False, "lr": 1e-3,
    "router_bias_rate": 0.05,
}
M = zaya.from_config(CONFIG)
B, T = 4, 16


@pytest.fixture(scope="module")
def ref():
    add_bench_paths()
    from benchlib.reference import zaya_ref
    return zaya_ref


def _names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _params(seed=0, m=M):
    """Seeded weights with every mechanism switched on: a zero ``gamma``
    or taps of one would hide a wrong depth-averaging or convolution."""
    p = zaya.init(jax.random.PRNGKey(seed), m)
    ks = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))
    for blk in p["blocks"]:
        blk["router"]["gamma"] = 0.5 * jax.random.normal(
            next(ks), blk["router"]["gamma"].shape)
        blk["k_temp"] = 1.0 + 0.2 * jax.random.normal(
            next(ks), blk["k_temp"].shape)
        for name in ("conv_q_dw", "conv_k_dw"):
            blk[name] = 0.5 * jax.random.normal(next(ks), blk[name].shape)
        # experts that answer at the residual's scale, a router that
        # decides: a flipped or dropped token then shows
        for name in ("w_gate", "w_up", "w_down"):
            blk["experts"][name] = blk["experts"][name] * 10.0
        blk["router"]["w3"] = blk["router"]["w3"] * 4.0
    return p


def _batches(n=3, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"tokens": np.asarray(jax.random.randint(
        k, (B, T + 1), 0, CONFIG["vocab_size"]))} for k in ks]


# --------------------------------------------- the whole model, three steps
# float32 worker math: program and reference compute the same float32
# mathematics in another order (sorted groups against masks, the scan's
# blocks against full scores, rsqrt against 1/sqrt), so a loss agrees to
# a few float32 roundings and a leaf's norm to 1e-4. bfloat16 worker math
# rounds weights and activations to 8 bits: a loss to 2e-3 (5e-3 allowed:
# from the second step on one of these 64 tokens may route otherwise), a
# leaf's norm to 4%, as long as no token's top-1 choice flips (the seeds
# here flip none in the first step: the test says so itself). The change
# after three Adam steps is, element by element, close to the learning rate times the
# gradient's sign: an element whose small bfloat16 gradient has the other
# sign moves the other way, and on a leaf of 32 elements a few of those
# are a tenth of the norm: 15%, and leaves of under 256 elements (gains of
# 8, temperatures of 2) are left out of it there.
TOLERANCE = {"float32": dict(loss=2e-5, grad=2e-4, delta=2e-3, least=1),
             "bfloat16": dict(loss=5e-3, grad=4e-2, delta=0.15, least=256)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fused_steps_agree_with_the_reference(ref, mesh4, dtype):
    from minips_tpu.apps.lm_example import zaya_dp_step
    config = dict(CONFIG, compute_dtype=dtype, attn="flash", head_chunk=8,
                  updater="adam")
    batches = _batches()
    first = {"tokens": jnp.asarray(batches[0]["tokens"])}
    z = ref._sizes(CONFIG)
    # bfloat16 on one device: over four, each device's shard is another
    # program than the observer's, and rounds (and so routes) otherwise
    mesh = mesh4 if dtype == "float32" else make_mesh(
        1, devices=jax.devices()[:1])
    for seed in range(16):      # the first weights under which bfloat16
        p0 = _params(seed)      # and float32 route the first step alike
        m, table, step, stats = zaya_dp_step(
            config, mesh, p0, first, updater="adam", lr=config["lr"])
        mine = stats(table.pull(), first, table.state)["expert"]
        theirs = ref.hidden(
            p0, first["tokens"][:, :-1],
            ref.centred_bias(p0, first["tokens"], z, 2), z, False)[1]
        if (np.asarray(mine) == np.asarray(theirs)).all():
            break
    names = _names(p0)
    want = ref.run(config, batches, lambda: p0, names, rows_per_block=2)
    # the bias the first step runs under: minus the routers' mean logits
    np.testing.assert_allclose(table.state, want["bias"], atol=TOLERANCE[
        dtype]["loss"])
    flat0 = np.asarray(table.params[: table.num_keys])
    losses, grad = [], None
    for i, b in enumerate(batches):
        if i == 0:
            expert = np.asarray(stats(table.pull(), first,
                                      table.state)["expert"])
        losses.append(float(table.step_inplace(
            step, {"tokens": jnp.asarray(b["tokens"])})))
        if i == 0:   # Adam's first moment after one step: (1 - b1) * g
            mu = [s for s in jax.tree.leaves(
                table.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu")][0].mu
            grad = table._unravel(mu[: table.num_keys] / 0.1)
    assert (expert == want["expert"]).all(), "a top-1 choice flipped"
    tol = TOLERANCE[dtype]
    np.testing.assert_allclose(losses, want["loss"], rtol=tol["loss"])
    got_grad = {n: float(jnp.linalg.norm(x)) for n, x in
                zip(names, jax.tree.leaves(grad))}
    delta = table._unravel(jnp.asarray(
        np.asarray(table.params[: table.num_keys]) - flat0))
    got_delta = {n: float(jnp.linalg.norm(x)) for n, x in
                 zip(names, jax.tree.leaves(delta))}
    med = float(np.median(list(want["grad"].values())))
    sizes = dict(zip(names, (x.size for x in jax.tree.leaves(p0))))
    for n in names:
        assert got_grad[n] == pytest.approx(
            want["grad"][n], rel=tol["grad"], abs=tol["grad"] * med), n
        if want["grad"][n] > 1e-3 * med and sizes[n] >= tol["least"]:
            assert got_delta[n] == pytest.approx(
                want["delta"][n], rel=tol["delta"]), n


def test_gradients_agree_leaf_by_leaf_as_vectors(ref):
    """Not only their norms: every leaf's float32 gradient against the
    reference's, as the norm of the difference."""
    p0, b = _params(3), _batches(1, seed=9)[0]
    z = ref._sizes(CONFIG)
    bias = jax.random.normal(jax.random.PRNGKey(1), (2, 4)) * 0.2
    (_, _), want = jax.value_and_grad(
        lambda p: ref.loss_sum(p, jnp.asarray(b["tokens"]), bias, z, False),
        has_aux=True)(p0)
    _, got, _ = zaya.grad_fn(p0, {"tokens": jnp.asarray(b["tokens"])}, bias,
                             M, compute_dtype=jnp.float32, head_chunk=8)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        w = w / (B * T)                     # a sum against a mean
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * max(
            float(jnp.linalg.norm(w)), 1e-6), n


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                        ("bfloat16", 8e-3)])
def test_the_chunked_head_gives_the_plain_heads_gradients(dtype, tol):
    """``grad_fn`` with ``head_chunk`` 8 (``transformer.nll_chunked``: each
    chunk's gradients formed while its logits are live) against 0 (whole
    logits through autodiff): the loss, every leaf's gradient as a vector,
    and the bias handed on. bfloat16 rounds at 4e-3 and sums ``tok_emb``'s
    gradient chunk by chunk where the plain head sums it in one product."""
    p = _params(4)
    b = {"tokens": jnp.asarray(_batches(1, seed=11)[0]["tokens"])}
    bias = jax.random.normal(jax.random.PRNGKey(5), (2, 4)) * 0.2
    kw = dict(compute_dtype=jnp.dtype(dtype), attn_impl="reference")
    l0, g0, b0 = zaya.grad_fn(p, b, bias, M, head_chunk=0, **kw)
    l1, g1, b1 = zaya.grad_fn(p, b, bias, M, head_chunk=8, **kw)
    assert float(l1) == pytest.approx(float(l0), rel=max(tol, 1e-6))
    np.testing.assert_array_equal(b1, b0)
    for n, got, want in zip(_names(p), jax.tree.leaves(g1),
                            jax.tree.leaves(g0)):
        assert float(jnp.linalg.norm(got - want)) <= tol * max(
            float(jnp.linalg.norm(want)), 1e-6), n


# ------------------------------------- the balancing bias, beside the table
def test_the_bias_rises_where_an_expert_fell_short():
    """An expert with no tokens gains ``rate``, one with twice its even
    share loses ``rate``, an even load stays; each layer by its own sum."""
    loads = jnp.asarray([[0, 8, 4, 4], [2, 2, 2, 2]])
    bias = jnp.asarray([[0.1, 0.2, 0.3, 0.4], [0.0, 0.0, 0.0, 0.0]])
    got = zaya.update_bias(bias, loads, 0.05)
    np.testing.assert_allclose(
        got, [[0.15, 0.15, 0.3, 0.4], [0.0, 0.0, 0.0, 0.0]], atol=1e-7)


def test_the_bias_moves_the_choice_and_not_the_gate_or_the_gradient(ref):
    """A bias that forces every token to expert 1: all of them choose it,
    the loss differs from the unbiased run's (other experts answer), the
    gate is still the unbiased probability, and no gradient reaches the
    bias."""
    p, b = _params(6), {"tokens": jnp.asarray(_batches(1)[0]["tokens"])}
    kw = dict(compute_dtype=jnp.float32, head_chunk=8)
    push = jnp.zeros((2, 4)).at[:, 1].set(100.0)
    _, chosen, _ = zaya.forward(p, b["tokens"][:, :-1], M, push,
                                compute_dtype=jnp.float32)
    assert (np.asarray(chosen) == 1).all()
    assert float(zaya.loss(p, b, M, push, **kw)) != pytest.approx(
        float(zaya.loss(p, b, M, **kw)), rel=1e-6)
    g = jax.grad(lambda bias: zaya.loss(p, b, M, bias, **kw))(push)
    assert not np.asarray(g).any()
    # the reference's layer under the same bias gates alike
    blk, z = p["blocks"][0], ref._sizes(CONFIG)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    r0 = jnp.zeros((2 * T, 8))
    free = ref.experts(blk, x, r0, jnp.zeros(4), z, False)
    forced = ref.experts(blk, x, r0, push[0], z, False)
    assert (np.asarray(forced[2]) == 1).all()
    mask = np.asarray(free[2]) == 1
    assert mask.any() and not mask.all()
    np.testing.assert_allclose(np.asarray(forced[0]).reshape(-1, 32)[mask],
                               np.asarray(free[0]).reshape(-1, 32)[mask],
                               atol=1e-6)


def test_the_centred_bias_cancels_each_routers_mean_logit():
    p, b = _params(2), {"tokens": jnp.asarray(_batches(1)[0]["tokens"])}
    stats = functools.partial(zaya.routing_stats, p, b, m=M,
                              compute_dtype=jnp.float32)
    bias = zaya.centred_bias(stats, M)
    assert bias.shape == (2, 4)
    np.testing.assert_allclose(bias, -stats(bias)["mean_logit"], atol=1e-6)
    # layer by layer: the second layer's logits are read under the first
    # layer's bias, not under zero
    assert not np.allclose(bias[1], -stats(None)["mean_logit"][1], atol=1e-6)


def test_the_step_hands_the_bias_on_counted_over_every_worker(mesh4):
    """Three steps over four workers: after each, the table's state is
    ``update_bias`` of the loads the observer counts for that batch under
    the weights and the bias the step ran with."""
    from minips_tpu.apps.lm_example import zaya_dp_step
    config = dict(CONFIG, compute_dtype="float32", attn="reference",
                  head_chunk=8)
    batches = [{"tokens": jnp.asarray(b["tokens"])} for b in _batches()]
    m, table, step, stats = zaya_dp_step(config, mesh4, _params(3),
                                         batches[0], updater="adam", lr=1e-3)
    for b in batches:
        before = table.state
        st = stats(table.pull(), b, before)
        loads = np.stack([np.bincount(row, minlength=4)
                          for row in np.asarray(st["expert"])])
        table.step_inplace(step, b)
        np.testing.assert_allclose(
            table.state, zaya.update_bias(before, loads, 0.05), atol=1e-6)
        assert not np.allclose(table.state, before)


@pytest.mark.parametrize("what", ["choice", "hidden"])
def test_the_whole_block_is_causal(what):
    """Changing token t+1 leaves every layer's expert choice and the final
    hidden state up to t alone, through attention, router and experts,
    under a bias that is the same for every position."""
    p = _params(4)
    toks = jnp.asarray(_batches(1)[0]["tokens"][:, :-1])
    bias = jax.random.normal(jax.random.PRNGKey(5), (2, 4)) * 0.3
    t = 9
    other = toks.at[:, t + 1].set((toks[:, t + 1] + 1) % CONFIG["vocab_size"])
    run = functools.partial(zaya.forward, p, m=M, bias=bias,
                            compute_dtype=jnp.float32,
                            attn_impl="reference")
    (h1, e1, _), (h2, e2, _) = run(toks), run(other)
    if what == "choice":
        e1, e2 = (np.asarray(e).reshape(2, B, T) for e in (e1, e2))
        np.testing.assert_array_equal(e1[:, :, : t + 1], e2[:, :, : t + 1])
    else:
        np.testing.assert_array_equal(h1[:, : t + 1], h2[:, : t + 1])
        assert not np.allclose(h1[:, t + 1:], h2[:, t + 1:])


def test_a_table_without_state_steps_as_before_and_refuses_accum(mesh4):
    from minips_tpu.tables.dense import DenseTable
    table = DenseTable({"w": jnp.ones(8)}, mesh4, updater="sgd", lr=0.1)

    def grad_fn(params, batch, count):
        return (jnp.sum(params["w"]) * jnp.mean(batch),
                {"w": jnp.ones(8) * jnp.mean(batch)}, count + 1)
    with pytest.raises(ValueError, match="accum"):
        table.make_step(grad_fn, accum=2, state=jnp.zeros(()))
    step = table.make_step(grad_fn, state=jnp.zeros(()))
    for _ in range(3):
        table.step_inplace(step, jnp.ones(4))
    assert float(table.state) == 3.0
    saved = table.state_dict()
    assert float(saved["state"]) == 3.0
    table.state = jnp.zeros(())
    table.load_state_dict(saved)
    assert float(table.state) == 3.0
    plain = DenseTable({"w": jnp.ones(8)}, mesh4, updater="sgd", lr=0.1)
    step = plain.make_step(lambda p, b: (jnp.sum(p["w"]), {"w": jnp.ones(8)}))
    plain.step_inplace(step, jnp.ones(4))
    assert plain.state is None and "state" not in plain.state_dict()


# --------------------------------------------------------- the expert layer
def _layer_inputs(seed=0, n=64, d=32, f=16, experts=4):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    stacks = {"w_gate": jax.random.normal(ks[0], (experts, d, f)) * 0.3,
              "w_up": jax.random.normal(ks[1], (experts, d, f)) * 0.3,
              "w_down": jax.random.normal(ks[2], (experts, f, d)) * 0.3}
    x = jax.random.normal(ks[3], (n, d))
    expert = jax.random.randint(ks[4], (n,), 0, experts)
    gate = jax.random.uniform(ks[5], (n,), minval=0.2, maxval=1.0)
    return stacks, x, expert, gate


def _share(stacks, lo, hi):
    return {k: v[lo:hi] for k, v in stacks.items()}


def _dense_layer(stacks, x, expert, gate):
    """Every token through its own expert, one token at a time."""
    w = jax.tree.map(lambda s: s[expert], stacks)
    h = jax.nn.silu(jnp.einsum("nd,ndf->nf", x, w["w_gate"])) \
        * jnp.einsum("nd,ndf->nf", x, w["w_up"])
    return gate[:, None] * jnp.einsum("nf,nfd->nd", h, w["w_down"])


def test_the_shares_add_up_to_the_uncut_layer(ref):
    """What the share 0-1 and the share 2-3 give, added, is the whole
    layer: the uncut reference's, and the program's own with all held."""
    stacks, x, expert, gate = _layer_inputs()
    f32 = dict(compute_dtype=jnp.float32)
    parts = [moe_apply_dropless(_share(stacks, lo, hi), x, expert, gate,
                                held=(lo, hi), **f32)
             for lo, hi in ((0, 2), (2, 4))]
    whole = moe_apply_dropless(stacks, x, expert, gate, held=(0, 4), **f32)
    want = _dense_layer(stacks, x, expert, gate)
    np.testing.assert_allclose(parts[0] + parts[1], want, atol=2e-5)
    np.testing.assert_allclose(whole, want, atol=2e-5)
    # a share gives nothing to a token whose expert lives elsewhere
    assert not np.asarray(parts[0])[np.asarray(expert) >= 2].any()
    assert np.asarray(parts[0])[np.asarray(expert) < 2].any(axis=1).all()


def test_the_reference_layer_is_the_sum_of_the_programs_shares(ref):
    """Through the router too: the reference with all four experts held
    against the program's two shares of two, on one block's weights."""
    full = dict(CONFIG, num_experts=4, held_experts=[0, 4])
    m4 = zaya.from_config(full)
    blk = _params(5, m4)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, T, 32))
    r0 = jnp.zeros((2 * T, 8))
    bias = jnp.asarray([0.05, -0.1, 0.0, 0.1])
    want, _, chosen, _ = ref.experts(blk, x, r0, bias, ref._sizes(full),
                                     False)
    u = zaya._rms(x, blk["ln2"]["g"], M.eps).reshape(2 * T, 32)
    logits, _ = zaya.route(blk["router"], u, r0, m4)
    expert = jnp.argmax(logits + bias, -1)
    assert (np.asarray(expert) == np.asarray(chosen)).all()
    gate = jnp.take_along_axis(jax.nn.softmax(logits, -1),
                               expert[:, None], 1)[:, 0]
    got = sum(moe_apply_dropless(_share(blk["experts"], lo, hi), u, expert,
                                 gate, held=(lo, hi),
                                 compute_dtype=jnp.float32)
              for lo, hi in ((0, 2), (2, 4)))
    np.testing.assert_allclose(got.reshape(2, T, 32), want, atol=2e-5)
    assert len(set(np.asarray(expert).tolist())) > 1


@pytest.mark.parametrize("target", [0, 3])
def test_dropless_under_skew_loses_no_token(target):
    """A router that sends every token to one expert: no capacity, so all
    of them are computed (or, at an absent expert, all get nothing)."""
    stacks, x, _, gate = _layer_inputs(seed=1)
    expert = jnp.full((x.shape[0],), target, jnp.int32)
    order, inverse, sizes = dropless_dispatch(expert, (0, 2))
    assert sizes.tolist() == ([x.shape[0], 0] if target == 0 else [0, 0])
    assert sorted(order.tolist()) == list(range(x.shape[0]))
    assert (np.asarray(order)[np.asarray(inverse)]
            == np.arange(x.shape[0])).all()
    got = moe_apply_dropless(_share(stacks, 0, 2), x, expert, gate,
                             held=(0, 2), compute_dtype=jnp.float32)
    want = _dense_layer(stacks, x, expert, gate) if target == 0 \
        else jnp.zeros_like(x)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_dropless_gradients_flow_to_tokens_gates_and_held_stacks():
    stacks, x, expert, gate = _layer_inputs(seed=2)
    share = _share(stacks, 0, 2)

    def f(layer):
        return lambda s, x, g: jnp.sum(jnp.sin(layer(s, x, g)))
    got = jax.grad(f(lambda s, x, g: moe_apply_dropless(
        s, x, expert, g, held=(0, 2), compute_dtype=jnp.float32)),
        argnums=(0, 1, 2))(share, x, gate)
    mine = (expert < 2)[:, None]
    want = jax.grad(f(lambda s, x, g: jnp.where(mine, _dense_layer(
        {k: jnp.concatenate([v, stacks[k][2:]]) for k, v in s.items()},
        x, expert, g), 0.0)), argnums=(0, 1, 2))(share, x, gate)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def test_the_stacks_must_be_the_experts_held():
    stacks, x, expert, gate = _layer_inputs()
    with pytest.raises(ValueError, match="held"):
        moe_apply_dropless(stacks, x, expert, gate, held=(0, 2))


# ------------------------------------------- what the attention's parts see
def _qkv(u, blk=None):
    blk = blk or _params(1)["blocks"][0]
    return zaya.cca_qkv(blk, u, jnp.arange(u.shape[1]), M, jnp.float32)


def test_convolutions_and_value_shift_are_causal():
    """Changing token t+1 leaves q, k and v up to t alone, and changes
    them from t+1 on (v at t+2 too: the shifted half)."""
    u = jax.random.normal(jax.random.PRNGKey(0), (2, T, 32))
    t = 6
    u2 = u.at[:, t + 1].add(1.0)
    for a, b in zip(_qkv(u), _qkv(u2)):
        np.testing.assert_array_equal(a[:, : t + 1], b[:, : t + 1])
        assert not np.allclose(a[:, t + 1], b[:, t + 1])
        assert not np.allclose(a[:, t + 2], b[:, t + 2])


def test_value_shift_halves_and_its_first_step():
    """The first half of each key/value head is this token's W_V1
    projection, the second half the previous token's W_V2; at t = 0 the
    second half is zero."""
    blk = _params(1)["blocks"][0]
    u = jax.random.normal(jax.random.PRNGKey(3), (2, T, 32))
    v = _qkv(u, blk)[2]                              # [B, T, kv, hd]
    half = M.head_dim // 2
    now = (u @ blk["wv1"]).reshape(2, T, M.kv_heads, half)
    before = (u @ blk["wv2"]).reshape(2, T, M.kv_heads, half)
    np.testing.assert_allclose(v[..., :half], now, atol=1e-5)
    np.testing.assert_allclose(v[:, 1:, :, half:], before[:, :-1],
                               atol=1e-5)
    assert not np.asarray(v[:, 0, :, half:]).any()


def test_qk_mean_under_eight_over_two_grouping(ref):
    """With the convolutions and the rotation switched off, q of head h is
    the unit-norm (q~_h + k~_(h // 4)) / 2 and k of head j the unit-norm
    (mean of q~ over heads 4j..4j+3 + k~_j) / 2, times its temperature."""
    blk = dict(_params(1)["blocks"][0])
    for name in ("conv_q_hd", "conv_k_hd"):
        blk[name] = jnp.zeros_like(blk[name])
    u = jax.random.normal(jax.random.PRNGKey(4), (1, T, 32))
    m = M._replace(rotary_dim=0)
    q, k, _ = zaya.cca_qkv(blk, u, jnp.arange(T), m, jnp.float32)
    ql = (u @ blk["wq"]).reshape(1, T, 8, 8)
    kl = (u @ blk["wk"]).reshape(1, T, 2, 8)
    unit = lambda x: x / jnp.sqrt(          # noqa: E731
        jnp.mean(x * x, -1, keepdims=True) + M.eps)
    for h in range(8):
        np.testing.assert_allclose(
            q[:, :, h], unit(0.5 * (ql[:, :, h] + kl[:, :, h // 4])),
            atol=1e-5)
    for j in range(2):
        want = unit(0.5 * (ql[:, :, 4 * j: 4 * j + 4].mean(2) + kl[:, :, j]))
        np.testing.assert_allclose(k[:, :, j], want * blk["k_temp"][j],
                                   atol=1e-5)
    # and with everything on, the reference's attention agrees
    blk = _params(1)["blocks"][0]
    x = jax.random.normal(jax.random.PRNGKey(5), (2, T, 32))
    want = ref.attention(blk, x, ref._sizes(CONFIG), False)
    qkv = _qkv(zaya._rms(x, blk["ln1"]["g"], M.eps), blk)
    from minips_tpu.models.transformer import _attn_fn
    got = _attn_fn("reference")(*qkv).reshape(2, T, -1) @ blk["wo"]
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_routers_state_passes_from_layer_to_layer(ref):
    """gamma = 0 cuts the layers apart; with it the second layer's choice
    depends on the first layer's router state."""
    p = _params(2)
    toks = jnp.asarray(_batches(1)[0]["tokens"][:, :-1])
    with_state = zaya.forward(p, toks, M, compute_dtype=jnp.float32)[1]
    cut = jax.tree.map(lambda x: x, p)
    cut["blocks"][1]["router"]["gamma"] = jnp.zeros(8)
    without = zaya.forward(cut, toks, M, compute_dtype=jnp.float32)[1]
    assert (np.asarray(with_state[0]) == np.asarray(without[0])).all()
    assert (np.asarray(with_state[1]) != np.asarray(without[1])).any()
    chosen = ref.hidden(p, toks, jnp.zeros((2, 4)), ref._sizes(CONFIG),
                        False)[1]
    assert (np.asarray(with_state) == np.asarray(chosen)).all()


def test_a_configuration_the_model_does_not_build_is_refused():
    for key, value in (("num_experts_per_tok", 2), ("hidden_act", "gelu"),
                       ("held_experts", [1, 4])):
        with pytest.raises(ValueError, match="zaya"):
            zaya.from_config(dict(CONFIG, **{key: value}))
    with pytest.raises(ValueError, match="sliding"):
        zaya.from_config(dict(CONFIG, layer_types=["hybrid",
                                                   "hybrid_sliding"]))


def test_routing_stats_count_every_token_once():
    p, b = _params(), _batches(1)[0]
    st = zaya.routing_stats(p, {"tokens": jnp.asarray(b["tokens"])}, None,
                            M, compute_dtype=jnp.float32)
    n = B * T
    assert st["tokens_held"].shape == (2, 2) and st["expert"].shape == (2, n)
    for layer in range(2):
        loads = np.bincount(np.asarray(st["expert"][layer]), minlength=4)
        assert st["tokens_held"][layer].tolist() == loads[:2].tolist()
        assert float(st["absent_share"][layer]) == pytest.approx(
            loads[2:].sum() / n)
        assert float(st["load_max_over_mean"][layer]) == pytest.approx(
            loads.max() * 4 / n)


def test_flash_plan_for_gpt2_xl_is_what_it_was():
    """The ZAYA cell runs the kernels at D 128 and T 8,192 (major
    blocks); the dense cell's plan (B 16, H 25, T 1,024, D 64, bf16) stays."""
    from minips_tpu.ops.flash_attention import flash_plan
    plan = flash_plan(1024, 1024, 64, 2)
    assert tuple(plan[:6]) == (512, 512, 1024, 1024, 1024, 1024)
    assert plan.causal_share == 0.75 and plan.bwd_share == 0.5625
    long = flash_plan(8192, 8192, 128, 2)
    assert (long.tile_q, long.tile_k, long.bwd_q, long.bwd_k) == (
        512, 512, 1024, 1024)
    assert long.major_q < 8192 and long.major_k < 8192


# ------------------------------------------------------ scopes and counters
@pytest.fixture(scope="module")
def step_text(mesh4):
    from minips_tpu.apps.lm_example import zaya_dp_step
    config = dict(CONFIG, compute_dtype="bfloat16", attn="flash",
                  head_chunk=8)
    batch = {"tokens": jnp.zeros((B, T + 1), jnp.int32)}
    _, table, step, _ = zaya_dp_step(config, mesh4, _params(), batch,
                                     updater="adam", lr=1e-3)
    return step.lower(table.params, table.opt_state, batch,
                      table.state).compile().as_text()


@pytest.mark.parametrize("scope", [
    prof.LM_ATTN_CCA, prof.LM_MOE_ROUTER, prof.LM_MOE_DISPATCH,
    prof.LM_MOE_EXPERTS, prof.LM_MOE_COMBINE, prof.LM_ATTN, prof.LM_HEAD,
    prof.LM_EMBED])
def test_the_new_scopes_are_in_the_compiled_step(step_text, scope):
    import re
    from minips_tpu.utils.trace_analysis import phase_of
    names = re.findall(r'op_name="([^"]*)"', step_text)
    mine = [n for n in names if phase_of(n)[0] == scope]
    assert mine, scope
    assert all(prof.GRAD in n for n in mine)
    if scope.startswith("lm.moe."):
        assert all(prof.LM_MOE in n for n in mine)
        assert any("transpose(" in n for n in mine)      # its backward
    if scope == prof.LM_ATTN_CCA:
        assert all(prof.LM_ATTN in n for n in mine)


def test_the_only_sort_is_the_dispatchs(step_text):
    """The dropless layer sorts tokens by expert (dispatch) and the router
    sorts nothing; nothing of the block is under lm.mlp."""
    import re
    from minips_tpu.utils.trace_analysis import phase_of
    where = set()
    for line in step_text.splitlines():
        m = re.search(r'op_name="([^"]*)"', line)
        if m and re.search(r" sort\(", line.split("metadata=")[0]):
            where.add(phase_of(m.group(1))[0])
    assert where == {prof.LM_MOE_DISPATCH}
    assert prof.LM_MLP not in {
        phase_of(n)[0] for n in re.findall(r'op_name="([^"]*)"', step_text)}


def test_the_apps_routing_counters_land_in_the_ring(tmp_path):
    """lm_example --model_config logs the observer at log_every and
    records the two counters under loop.readback."""
    import json

    from minips_tpu.apps import lm_example
    path = tmp_path / "zaya-tiny.json"
    path.write_text(json.dumps(dict(
        CONFIG, compute_dtype="float32", attn="reference", head_chunk=8)))
    prof.clear()
    lines = []

    class Sink:
        def log(self, **kw):
            lines.append(kw)

    out = lm_example.main(
        ["--num_iters", "4", "--seq_len", str(T), "--batch_size", "8",
         "--log_every", "2", "--model_config", str(path)], metrics=Sink())
    assert np.isfinite(out["losses"]).all()
    logged = [ln for ln in lines if "moe_tokens_held" in ln]
    assert len(logged) == 2
    assert np.shape(logged[0]["moe_tokens_held"]) == (2, 2)
    spans, counters = prof.snapshot()
    held = [s for s in spans if s.name == prof.MOE_TOKENS_HELD]
    assert len(held) == 2
    assert {s.parent_name for s in held} == {prof.LOOP_READBACK}
    count, total = counters[prof.MOE_TOKENS_HELD]
    assert count == 2 and total == sum(
        int(np.sum(ln["moe_tokens_held"])) for ln in logged)
    assert counters[prof.MOE_LOAD_MAX_OVER_MEAN][1] >= 2.0
    with pytest.raises(SystemExit, match="model_config"):
        lm_example.main(["--model_config", str(path), "--dim", "64"],
                        metrics=Sink())
