"""The latent-attention expert decoder (models/mla_moe.py, the flash
kernels' two head sizes, parallel/moe.py's top-k dropless layer) against
the benchmark's own plain reference (bench/benchlib/reference/joyai_ref.py,
found through tests/conftest.py's path hook), at a size a test run can
hold: the whole model through ``DenseTable.make_step`` for three steps with
the routers' balancing bias carried beside the table, the expert layer's
shares, the dropless top-k case under skew, what the prediction module and
the block may and may not see, the kernels at unequal head sizes in the
interpreter, and the named scopes in the compiled step.
"""

from __future__ import annotations

import functools
import hashlib
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.models import mla_moe, zaya
from minips_tpu.ops import flash_attention as fa
from minips_tpu.parallel.moe import moe_apply_dropless
from minips_tpu.parallel.ring_attention import reference_attention
from minips_tpu.utils import profiling as prof
from tests.conftest import add_bench_paths

CONFIG = {
    "model_type": "joyai_llm_flash", "vocab_size": 96, "hidden_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 32000000, "rms_norm_eps": 1e-6, "intermediate_size": 48,
    "moe_intermediate_size": 16, "n_shared_experts": 1,
    "n_routed_experts": 4, "published": {"n_routed_experts": 8},
    "held_experts": [0, 4], "num_experts_per_tok": 2,
    "routed_scaling_factor": 2.5, "norm_topk_prob": True,
    "num_nextn_predict_layers": 1, "mtp_loss_weight": 0.3,
    "router_bias_rate": 0.001, "hidden_act": "silu",
    "tie_word_embeddings": False, "rope_interleave": True,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "lr": 1e-3,
}
M = mla_moe.from_config(CONFIG)
B, T = 4, 16
F32 = dict(compute_dtype=jnp.float32, attn_impl="flash")


@pytest.fixture(scope="module")
def ref():
    add_bench_paths()
    from benchlib.reference import joyai_ref
    return joyai_ref


def _names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _params(seed=0, m=M):
    """Seeded weights at a scale where every mechanism answers: experts
    and a shared expert at the residual's scale, a router that decides."""
    p = mla_moe.init(jax.random.PRNGKey(seed), m)
    return jax.tree.map(lambda x: x * 4.0 if x.ndim >= 2 else x, p)


def _batches(n=3, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"tokens": np.asarray(jax.random.randint(
        k, (B, T + 1), 0, CONFIG["vocab_size"]))} for k in ks]


def _tokens(seed=7):
    return {"tokens": jnp.asarray(_batches(1, seed)[0]["tokens"])}


def _norms(tree, names) -> dict:
    return {n: float(jnp.linalg.norm(x.astype(jnp.float32)))
            for n, x in zip(names, jax.tree.leaves(tree))}


# --------------------------------------------- the whole model, three steps
# float32 worker math: program and reference compute the same float32
# mathematics in another order (sorted windows against masks, the scan's
# blocks against full scores, rsqrt against 1/sqrt, the module's masked
# last position against a shorter sequence): a loss agrees to a few
# float32 roundings, a leaf's norm to 2e-4. bfloat16 rounds weights and
# activations to 8 bits: a loss to 5e-3, a leaf's norm to 6% (top-2 of 8
# under sigmoid scores: a token near a tie may take another second expert,
# which moves two experts' gradients by one token of 64). The change after
# three Adam steps is close to lr times the gradient's sign element by
# element: 15%, and leaves under 256 elements are left out of it there.
TOLERANCE = {"float32": dict(loss=2e-5, grad=2e-4, delta=2e-3, least=1),
             "bfloat16": dict(loss=5e-3, grad=6e-2, delta=0.15, least=256)}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fused_steps_agree_with_the_reference(ref, mesh4, dtype):
    from minips_tpu.apps.lm_example import model_dp_step
    from minips_tpu.parallel.mesh import make_mesh
    config = dict(CONFIG, compute_dtype=dtype, attn="flash", head_chunk=8,
                  updater="adam")
    batches = _batches()
    first = {"tokens": jnp.asarray(batches[0]["tokens"])}
    mesh = mesh4 if dtype == "float32" else make_mesh(
        1, devices=jax.devices()[:1])
    p0 = _params(1)
    m, table, step, stats = model_dp_step(
        config, mesh, p0, first, updater="adam", lr=config["lr"])
    names = _names(p0)
    want = ref.run(config, batches, lambda: p0, names, rows_per_block=2)
    tol = TOLERANCE[dtype]
    # the bias the first step runs under: minus the routers' mean scores
    np.testing.assert_allclose(table.state, want["bias"], atol=tol["loss"])
    assert table.state.shape == (3, 8)      # two expert layers, the module
    flat0 = np.asarray(table.params[: table.num_keys])
    losses, grad = [], None
    for i, b in enumerate(batches):
        if i == 0:
            st = jax.device_get(stats(table.pull(), first, table.state))
        losses.append(float(table.step_inplace(
            step, {"tokens": jnp.asarray(b["tokens"])})))
        if i == 0:   # Adam's first moment after one step: (1 - b1) * g
            mu = [s for s in jax.tree.leaves(
                table.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu")][0].mu
            grad = table._unravel(mu[: table.num_keys] / 0.1)
    if dtype == "float32":
        np.testing.assert_array_equal(st["tokens_held"],
                                      want["loads"][:, :4])
    assert st["lm_nll"] == pytest.approx(want["nll"]["lm_nll"],
                                         rel=tol["loss"])
    assert st["mtp_nll"] == pytest.approx(want["nll"]["mtp_nll"],
                                          rel=tol["loss"])
    np.testing.assert_allclose(losses, want["loss"], rtol=tol["loss"])
    got_grad = _norms(grad, names)
    delta = table._unravel(jnp.asarray(
        np.asarray(table.params[: table.num_keys]) - flat0))
    got_delta = _norms(delta, names)
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
    reference's, as the norm of the difference; and the bias handed on is
    the reference's rule on the reference's loads."""
    p0, b = _params(3), _batches(1, seed=9)[0]
    z = ref._sizes(CONFIG)
    bias = jax.random.normal(jax.random.PRNGKey(1), (3, 8)) * 0.05
    (_, aux), want = jax.value_and_grad(
        lambda p: ref.loss_sums(p, jnp.asarray(b["tokens"]), bias, z, False),
        has_aux=True)(p0)
    _, got, nxt = mla_moe.grad_fn(p0, {"tokens": jnp.asarray(b["tokens"])},
                                  bias, M, head_chunk=8, **F32)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        w = w / (B * T)                     # a sum against a mean
        assert float(jnp.linalg.norm(g - w)) <= 1e-4 * max(
            float(jnp.linalg.norm(w)), 1e-6), n
    loads = np.asarray(aux[0])
    np.testing.assert_allclose(nxt, np.asarray(bias) + 0.001 * np.sign(
        loads.mean(-1, keepdims=True) - loads), atol=1e-7)
    assert loads.sum(1).tolist() == [B * T * 2, B * T * 2, B * (T - 1) * 2]


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                        ("bfloat16", 8e-3)])
def test_the_chunked_untied_head_gives_the_plain_heads_gradients(dtype, tol):
    """``grad_fn`` with ``head_chunk`` 8 (``transformer.nll_chunked`` over
    the untied ``head``, twice a step) against 0 (whole logits through
    autodiff; the module's T - 1 targets by the same masked row): the
    loss, every leaf's gradient as a vector, the bias handed on."""
    p, b = _params(4), _tokens(11)
    bias = jax.random.normal(jax.random.PRNGKey(5), (3, 8)) * 0.05
    kw = dict(compute_dtype=jnp.dtype(dtype), attn_impl="reference")
    l0, g0, b0 = mla_moe.grad_fn(p, b, bias, M, head_chunk=0, **kw)
    l1, g1, b1 = mla_moe.grad_fn(p, b, bias, M, head_chunk=8, **kw)
    assert float(l1) == pytest.approx(float(l0), rel=max(tol, 1e-6))
    np.testing.assert_array_equal(b1, b0)
    for n, got, want in zip(_names(p), jax.tree.leaves(g1),
                            jax.tree.leaves(g0)):
        assert float(jnp.linalg.norm(got - want)) <= tol * max(
            float(jnp.linalg.norm(want)), 1e-6), n


# ------------------------------------------------ the prediction module
def test_the_mtp_loss_its_weight_targets_and_both_head_uses():
    """loss = lm_nll + weight * mtp_nll; the module's loss is the mean
    over T - 1 targets a row (position i predicts token i + 2), worked out
    by hand from its hidden state; the head's and the embedding's
    gradients are the sums of the main use's and the module's."""
    p, b = _params(5), _tokens(13)
    kw = dict(head_chunk=8, **F32)
    total, aux = mla_moe._loss(p, b, M, None, **kw)
    assert float(total) == pytest.approx(
        float(aux["lm_nll"]) + 0.3 * float(aux["mtp_nll"]), rel=1e-6)
    _, h_mtp, _, _ = mla_moe.forward(p, b["tokens"], M, **F32)
    assert not np.asarray(h_mtp[:, -1]).any()       # the row taken out
    logp = jax.nn.log_softmax(h_mtp[:, :-1] @ p["head"].T)
    by_hand = -jnp.mean(jnp.take_along_axis(
        logp, b["tokens"][:, 2:, None], -1))
    assert float(aux["mtp_nll"]) == pytest.approx(float(by_hand), rel=2e-5)
    heavier = mla_moe._loss(p, b, M._replace(mtp_weight=1.0), None, **kw)[0]
    assert float(heavier) == pytest.approx(
        float(aux["lm_nll"]) + float(aux["mtp_nll"]), rel=1e-6)

    def part(which):
        return jax.grad(lambda q: mla_moe._loss(
            q, b, M, None, **kw)[1][which])(p)

    both = jax.grad(lambda q: mla_moe._loss(q, b, M, None, **kw)[0])(p)
    main, mtp = part("lm_nll"), part("mtp_nll")
    for leaf in ("head", "tok_emb"):
        assert float(jnp.linalg.norm(mtp[leaf])) > 1e-3
        np.testing.assert_allclose(both[leaf], main[leaf] + 0.3 * mtp[leaf],
                                   rtol=1e-4, atol=1e-7)
    assert not any(np.asarray(x).any()              # the main loss does not
                   for x in jax.tree.leaves(main["mtp"]))   # see the module


def test_mtp_position_i_reads_nothing_beyond_token_i_plus_1():
    p, b = _params(6), _tokens(15)
    i = 6
    other = b["tokens"].at[:, i + 2:].set((b["tokens"][:, i + 2:] + 1) % 96)
    one = mla_moe.forward(p, b["tokens"], M, **F32)
    two = mla_moe.forward(p, other, M, **F32)
    np.testing.assert_array_equal(one[1][:, : i + 1], two[1][:, : i + 1])
    assert np.abs(np.asarray(one[1][:, i + 1] - two[1][:, i + 1])).max() > 0
    # and the main model's position i reads nothing beyond token i
    np.testing.assert_array_equal(one[0][:, : i + 2], two[0][:, : i + 2])


def test_the_block_is_causal():
    """Neither kind of block lets a position see a later one: attention's
    mask, and a router and experts that work token by token."""
    p = _params(7)
    pos = jnp.arange(T)
    block = functools.partial(mla_moe._block, pos=pos, m=M,
                              attn_fn=mla_moe.tfm._attn_fn("reference"),
                              compute_dtype=jnp.float32)
    h = jax.random.normal(jax.random.PRNGKey(0), (2, T, 32))
    for blk, args in ((p["blocks"][0], (None, None)),
                      (p["blocks"][1], (jnp.zeros(8), None))):
        for i in (0, 5, T - 2):
            later = h.at[:, i + 1:].add(1.0)
            np.testing.assert_allclose(
                block(h, blk, *args)[0][:, : i + 1],
                block(later, blk, *args)[0][:, : i + 1], atol=1e-6)


def test_k_rope_is_one_vector_a_token_shared_by_every_head():
    a, pos = _params(8)["blocks"][0]["attn"], jnp.arange(T)
    u = jax.random.normal(jax.random.PRNGKey(1), (2, T, 32))
    q, k, v = mla_moe.mla_qkv(a, u, pos, M, jnp.float32)
    assert q.shape == k.shape == (2, T, 2, 24) and v.shape == (2, T, 2, 16)
    np.testing.assert_array_equal(k[:, :, 0, 16:], k[:, :, 1, 16:])
    assert np.abs(np.asarray(k[:, :, 0, :16] - k[:, :, 1, :16])).max() > 0
    assert np.abs(np.asarray(q[:, :, 0, 16:] - q[:, :, 1, 16:])).max() > 0


# ------------------------------------- the balancing bias, beside the table
def test_the_bias_moves_by_the_sign_of_the_load_error():
    loads = jnp.asarray([[0, 8, 4, 4], [2, 2, 2, 2]])
    bias = jnp.asarray([[0.1, 0.2, 0.3, 0.4], [0.0, 0.0, 0.0, 0.0]])
    np.testing.assert_allclose(
        mla_moe.update_bias(bias, loads, 0.001),
        [[0.101, 0.199, 0.3, 0.4], [0.0, 0.0, 0.0, 0.0]], atol=1e-7)


def test_the_bias_moves_the_choice_and_not_the_gate_or_the_gradient():
    p, b = _params(9), _tokens()
    blk = p["blocks"][1]
    u = jax.random.normal(jax.random.PRNGKey(2), (64, 32))
    push = jnp.zeros(8).at[jnp.asarray([6, 7])].set(100.0)
    chosen, gate, s = mla_moe.route(blk["router"], u, push, M)
    assert (np.sort(np.asarray(chosen), 1) == [6, 7]).all()
    # the gates are the UNBIASED scores of the chosen, normalised, x 2.5
    sc = np.take_along_axis(np.asarray(s), np.asarray(chosen), 1)
    np.testing.assert_allclose(gate, 2.5 * sc / sc.sum(1, keepdims=True),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(gate).sum(1), 2.5, rtol=1e-6)
    free = mla_moe.route(blk["router"], u, jnp.zeros(8), M)
    np.testing.assert_array_equal(free[2], s)
    kw = dict(head_chunk=8, **F32)
    forced = jnp.zeros((3, 8)).at[:, 6:].set(100.0)
    assert float(mla_moe.loss(p, b, M, forced, **kw)) != pytest.approx(
        float(mla_moe.loss(p, b, M, **kw)), rel=1e-6)
    g = jax.grad(lambda bias: mla_moe.loss(p, b, M, bias, **kw))(forced)
    assert not np.asarray(g).any()


def test_the_centred_bias_cancels_each_routers_mean_score():
    p, b = _params(2), _tokens()
    stats = functools.partial(mla_moe.routing_stats, p, b, m=M,
                              head_chunk=8, **F32)
    bias = mla_moe.centred_bias(stats, M)
    assert bias.shape == (3, 8)
    np.testing.assert_allclose(bias, -stats(bias)["mean_score"], atol=1e-6)
    assert not np.allclose(bias[1], -stats(None)["mean_score"][1], atol=1e-6)


# --------------------------------------------------- the shares add up
def test_four_shares_and_the_shared_expert_once_give_the_whole_layer(ref):
    """4 shares of 2 of 8 experts at top-2: the routed parts that all the
    shares give, with the shared expert (which every chip computes alike)
    counted once, equal the uncut reference layer, which holds all 8."""
    whole = mla_moe.from_config(dict(CONFIG, n_routed_experts=8,
                                     held_experts=[0, 8]))
    blk = _params(3, whole)["blocks"][1]
    z = ref._sizes(dict(CONFIG, n_routed_experts=8, held_experts=[0, 8]))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, T, 32))
    bias = jax.random.normal(jax.random.PRNGKey(5), (8,)) * 0.05
    want, loads, _ = ref.experts(blk, x, bias, z, False)
    u = ref._rms(x, blk["ln2"]["g"], z["eps"]).reshape(2 * T, 32)
    chosen, gate, _ = mla_moe.route(blk["router"], u, bias, whole)
    total = mla_moe._swiglu(blk["shared"], u, jnp.float32)     # once
    parts = []
    for lo in range(0, 8, 2):
        stacks = {k: v[lo: lo + 2] for k, v in blk["experts"].items()}
        parts.append(moe_apply_dropless(
            stacks, u, chosen, gate, held=(lo, lo + 2),
            compute_dtype=jnp.float32))
        total = total + parts[-1]
    np.testing.assert_allclose(total.reshape(2, T, 32), want, atol=2e-5)
    assert sum(float(jnp.abs(part).sum()) > 0 for part in parts) == 4
    assert int(loads.sum()) == 2 * T * 2


# ------------------------------------------- top-k dropless under skew
def _dense_topk(ex, x, expert, gate, held):
    y = jnp.zeros(x.shape, jnp.float32)
    for e in range(*held):
        i = e - held[0]
        h = (jax.nn.silu(x @ ex["w_gate"][i]) * (x @ ex["w_up"][i])) \
            @ ex["w_down"][i]
        y = y + h * jnp.sum(jnp.where(expert == e, gate, 0.0), 1)[:, None]
    return y


SKEW = {
    "spread": lambda k: jnp.argsort(jax.random.uniform(k, (64, 16)),
                                    -1)[:, :4],
    "every_choice_held": lambda k: jnp.tile(jnp.arange(4, 8), (64, 1)),
    "none_held": lambda k: jnp.tile(jnp.arange(0, 4), (64, 1)),
    "one_expert_takes_all": lambda k: jnp.concatenate(
        [jnp.full((64, 1), 5), jnp.tile(jnp.arange(12, 15), (64, 1))], 1),
    # 65 held assignments: one row past a window of 64
    "one_row_past_a_window": lambda k: jnp.concatenate(
        [jnp.full((64, 1), 5), jnp.full((64, 1), 12).at[0].set(6),
         jnp.tile(jnp.arange(13, 15), (64, 1))], 1),
    # 128: two windows and not a row more
    "two_whole_windows": lambda k: jnp.concatenate(
        [jnp.tile(jnp.arange(5, 7), (64, 1)),
         jnp.tile(jnp.arange(12, 14), (64, 1))], 1),
}


@pytest.mark.parametrize("case", sorted(SKEW))
def test_topk_dropless_drops_nothing_under_any_routing(case):
    """Every assignment to a held expert is computed, a token that several
    held experts chose gets their sum, whatever the routing (no window of
    N rows, one, or all k of them where every choice is held); value and
    all three gradients against a masked dense layer; and one program
    serves every routing (no shape moves: the function is jitted once)."""
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    ex = {"w_gate": jax.random.normal(ks[0], (4, 16, 8)),
          "w_up": jax.random.normal(ks[1], (4, 16, 8)),
          "w_down": jax.random.normal(ks[2], (4, 8, 16))}
    x = jax.random.normal(ks[3], (64, 16))
    gate = jax.random.uniform(ks[4], (64, 4))
    expert = SKEW[case](ks[5])
    held = (4, 8)

    @jax.jit
    def mine(ex, x, gate, expert):
        return jnp.sum(jnp.sin(moe_apply_dropless(
            ex, x, expert, gate, held=held, compute_dtype=jnp.float32)))

    def plain(ex, x, gate, expert):
        return jnp.sum(jnp.sin(_dense_topk(ex, x, expert, gate, held)))

    assert float(mine(ex, x, gate, expert)) == pytest.approx(
        float(plain(ex, x, gate, expert)), rel=1e-4, abs=1e-4)
    got = jax.grad(mine, (0, 1, 2))(ex, x, gate, expert)
    want = jax.grad(plain, (0, 1, 2))(ex, x, gate, expert)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-3)
    assert mine._cache_size() == 1
    mine(ex, x, gate, SKEW["spread"](ks[0]))
    assert mine._cache_size() == 1


# the jaxpr of grad of ZAYA's call (k = 1) at the PARENT of the PR that
# brought top-k (1fc21d6), as this test builds it: the call traces the
# program it traced then. After a change of jax's printing: check out
# that commit, print the digest there, and compare.
TOP1_JAXPR = "4f0a6c2aeeb3a82530facfb96838abe4e7491b9d3047c676d1c6f763f5a4e23d"


def test_top1_traces_the_program_it_traced_before_topk():
    ks = jax.random.split(jax.random.PRNGKey(1), 8)
    ex = {"w_gate": jax.random.normal(ks[0], (4, 16, 8)),
          "w_up": jax.random.normal(ks[1], (4, 16, 8)),
          "w_down": jax.random.normal(ks[2], (4, 8, 16))}
    x = jax.random.normal(ks[3], (64, 16))
    e1 = jnp.argmax(jax.random.uniform(ks[6], (64, 16)), -1)
    g1 = jax.random.uniform(ks[7], (64,))
    f = lambda ex, x: jnp.sum(moe_apply_dropless(   # noqa: E731
        ex, x, e1, g1, held=(4, 8)))
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1)))(ex, x))
    assert hashlib.sha256(text.encode()).hexdigest() == TOP1_JAXPR
    # and k = 1 given as [N, 1] computes what the top-1 call computes
    one = moe_apply_dropless(ex, x, e1, g1, held=(4, 8),
                             compute_dtype=jnp.float32)
    col = moe_apply_dropless(ex, x, e1[:, None], g1[:, None], held=(4, 8),
                             compute_dtype=jnp.float32)
    np.testing.assert_allclose(col, one, atol=1e-5)


# ------------------------------- the kernels at two head sizes, interpreted
def _plain_attention(q, k, v):
    return reference_attention(q, k, v, causal=True)


@pytest.mark.parametrize("H, Hk, D, Dv", [(4, 4, 48, 32), (4, 2, 24, 16),
                                          (2, 2, 192, 128)])
def test_flash_kernels_at_unequal_head_sizes_in_the_interpreter(H, Hk, D,
                                                                Dv):
    """q and k of D channels, v of Dv: forward and dQ, dK, dV of the three
    Pallas kernels (interpreted) against plain attention, with grouped
    queries and without; the output has v's size, and the kernels' blocks
    carry v's size for v, dO and dV (no padded copy)."""
    Tq = 256
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (2, Tq, H, D))
    k = jax.random.normal(ks[1], (2, Tq, Hk, D))
    v = jax.random.normal(ks[2], (2, Tq, Hk, Dv))
    w = jax.random.normal(ks[3], (2, Tq, H, Dv))

    def kernels(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, interpret=True,
                                  block_q=128, block_k=128)

    out = kernels(q, k, v)
    assert out.shape == (2, Tq, H, Dv)
    np.testing.assert_allclose(out, _plain_attention(q, k, v), atol=2e-5,
                               rtol=2e-5)
    got = jax.grad(lambda *a: jnp.sum(kernels(*a) * w), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_plain_attention(*a) * w),
                    (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=3e-4, rtol=3e-4)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(kernels(*a)), (0, 1, 2)))(q, k, v))
    assert f",{Dv}]" in text and f",{D}]" in text
    # the scan, the kernels' twin off the chip, takes v's size from v too
    np.testing.assert_allclose(
        fa.flash_attention(q, k, v, causal=True),
        _plain_attention(q, k, v), atol=2e-5, rtol=2e-5)


# the jaxprs of the forward kernel at the accepted cells' head sizes as
# the PARENT of the PR that brought v's own size (1fc21d6) traced them:
# for equal sizes the forward is the parent's. (See TOP1_JAXPR.) The
# backward of that tree, two kernels, became one in PR 34 and is pinned by
# its gradients (tests/test_flash_attention.py), not by its text.
EQUAL_SIZES = {
    (2, 1024, 25, 25, 64):
        "db9794cbd708dfe29b0894da97400ac0c5505c36288fa8214619fac754269cf0",
    (1, 8192, 8, 2, 128):
        "ab15bb8e1605685085502b2b2c4481369c7e2f023602d2a9ee8b381534c32705",
}
# tiles, majors and computed shares: what the plan held before PR 34
PLANS = {
    (1024, 64): (512, 512, 1024, 1024, 1024, 1024, 0.75, 0.5625),
    (8192, 128): (512, 512, 1024, 1024, 2048, 2048, 0.53125, 0.5078125),
}


@pytest.mark.parametrize("shape", sorted(EQUAL_SIZES))
def test_for_equal_head_sizes_the_plan_and_the_kernels_are_unchanged(shape):
    Bq, Tq, H, Hk, D = shape
    assert fa.flash_plan(Tq, Tq, D, 2)[:8] == PLANS[Tq, D]
    assert fa.flash_plan(Tq, Tq, D, 2, Dv=D) == fa.flash_plan(Tq, Tq, D, 2)
    q = jax.ShapeDtypeStruct((Bq, Tq, H, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((Bq, Tq, Hk, D), jnp.bfloat16)
    f = lambda q, k, v: jnp.sum(fa._flash(     # noqa: E731
        q, k, v, True, D ** -0.5, None, None, False).astype(jnp.float32))
    text = str(jax.make_jaxpr(f)(q, k, k))
    assert hashlib.sha256(text.encode()).hexdigest() == EQUAL_SIZES[shape]


def test_the_plan_at_192_128_sizes_a_resident_operand_by_the_wider():
    plan = fa.flash_plan(8192, 8192, 192, 2, Dv=128)
    assert (plan.major_q, plan.major_k) == (1024, 1024)
    assert plan[:4] == (512, 512, 1024, 1024)
    assert fa.flash_plan(8192, 8192, 128, 2, Dv=192)[:8] == plan[:8]
    assert fa.kernel_supported((2, 8192, 32, 192), (2, 8192, 32, 192),
                               v_head=128)
    assert not fa.kernel_supported((2, 256, 2, 24), (2, 256, 2, 24),
                                   v_head=12)


# ------------------------------------------------- scopes in the step
def test_the_scopes_are_in_the_compiled_step(mesh4):
    from minips_tpu.apps.lm_example import model_dp_step
    from minips_tpu.utils.trace_analysis import phase_of
    config = dict(CONFIG, compute_dtype="float32", attn="flash",
                  head_chunk=8)
    first = _tokens()
    _, table, step, _ = model_dp_step(config, mesh4, _params(), first,
                                      updater="adam", lr=1e-3)
    text = step.lower(table.params, table.opt_state, first,
                      table.state).compile().as_text()
    scopes = set(re.findall(r'op_name="([^"]*)"', text))
    phases = {phase_of(s)[0] for s in scopes}
    for name in (prof.LM_ATTN, prof.LM_ATTN_MLA, prof.LM_MLP, prof.LM_MOE,
                 prof.LM_MOE_ROUTER, prof.LM_MOE_DISPATCH,
                 prof.LM_MOE_EXPERTS, prof.LM_MOE_COMBINE,
                 prof.LM_MOE_SHARED, prof.LM_HEAD, prof.LM_EMBED):
        assert name in phases, name
    # the module's parts stay apart from the main model's
    mtp = {phase_of(s)[0] for s in scopes if prof.LM_MTP in s}
    assert {f"{prof.LM_MTP}/{prof.LM_HEAD}", f"{prof.LM_MTP}/{prof.LM_ATTN}",
            f"{prof.LM_MTP}/{prof.LM_MOE_EXPERTS}"} <= mtp
    assert prof.LM_ATTN_MLA in {phase_of(s)[0] for s in scopes
                                if prof.LM_ATTN in s}


# --------------------------------------------------------------- the app
class Sink:
    def __init__(self):
        self.lines = []

    def log(self, **kw):
        self.lines.append(kw)


def _tiny_file(tmp_path, kind: str):
    if kind == "zaya":
        from tests.test_zaya import CONFIG as ZAYA
        config = dict(ZAYA, model_type="zaya")
    else:
        config = dict(CONFIG)
    path = tmp_path / f"{kind}-tiny.json"
    path.write_text(json.dumps(dict(
        config, compute_dtype="float32", attn="reference", head_chunk=8)))
    return path


@pytest.mark.parametrize("kind", ["zaya", "joyai_llm_flash"])
def test_the_app_builds_the_model_the_files_model_type_names(tmp_path, kind):
    """One step builder for both files (``lm_example.model_dp_step``); the
    observer's counters land in the ring at ``log_every``, the two losses
    where the model has a prediction module."""
    from minips_tpu.apps import lm_example
    assert lm_example.zaya_dp_step is lm_example.model_dp_step
    assert lm_example.config_model({"model_type": kind}) is {
        "zaya": zaya, "joyai_llm_flash": mla_moe}[kind]
    prof.clear()
    sink = Sink()
    out = lm_example.main(
        ["--num_iters", "4", "--seq_len", str(T), "--batch_size", "8",
         "--log_every", "2", "--model_config",
         str(_tiny_file(tmp_path, kind))], metrics=sink)
    assert np.isfinite(out["losses"]).all()
    logged = [ln for ln in sink.lines if "moe_tokens_held" in ln]
    assert len(logged) == 2
    _, counters = prof.snapshot()
    assert counters[prof.MOE_TOKENS_HELD][0] == 2
    if kind == "zaya":
        assert "lm_nll" not in logged[0] and prof.LM_NLL not in counters
        return
    assert np.shape(logged[0]["moe_tokens_held"]) == (3, 4)
    for ln in logged:
        assert ln["loss"] == pytest.approx(
            ln["lm_nll"] + 0.3 * ln["mtp_nll"], rel=0.05)
    assert counters[prof.LM_NLL][0] == counters[prof.MTP_NLL][0] == 2
    assert counters[prof.LM_NLL][1] == pytest.approx(
        sum(ln["lm_nll"] for ln in logged))


def test_an_unknown_model_type_is_refused(tmp_path):
    from minips_tpu.apps import lm_example
    path = tmp_path / "other.json"
    path.write_text(json.dumps(dict(CONFIG, model_type="other")))
    with pytest.raises(SystemExit, match="model_type"):
        lm_example.main(["--model_config", str(path)], metrics=Sink())


@pytest.mark.parametrize("key, value", [
    ("tie_word_embeddings", True), ("scoring_func", "softmax"),
    ("n_group", 8), ("rope_scaling", {"type": "yarn"}),
    ("held_experts", [0, 3]), ("num_nextn_predict_layers", 2)])
def test_a_file_the_model_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match="mla_moe"):
        mla_moe.from_config(dict(CONFIG, **{key: value}))
