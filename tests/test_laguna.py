"""The window/full hybrid expert decoder (models/laguna.py) against the
benchmark's own plain reference (bench/benchlib/reference/laguna_ref.py,
found through tests/conftest.py's path hook) at a size a test run can
hold, every mechanism live: a dense full layer, three window layers and a
full one (6 and 4 query heads over 2 key/value heads: groups of 3 and 2),
a window of 8 over 64 positions, YaRN whose ramp has a pair inside it,
top-3 of 16 experts with 4 held beside a shared one, the choice under a
balancing bias that moves every step. Three steps through
``DenseTable.make_step``; every leaf's gradient as a vector; what a window
layer may see; the rotary frequencies at the published values; the gate;
the shares of heads and of experts against the uncut layer; the app.
"""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.models import laguna
from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.parallel.moe import moe_apply_dropless
from minips_tpu.utils import profiling as prof
from tests.conftest import add_bench_paths

W, FULL = laguna.WINDOW, laguna.FULL
PUBLISHED_ROPE = {
    "full_attention": {"rope_theta": 500000, "rope_type": "yarn",
                       "factor": 128, "original_max_position_embeddings": 8192,
                       "beta_slow": 1, "beta_fast": 32,
                       "attention_factor": 1.4852030263919618,
                       "partial_rotary_factor": 0.5},
    "sliding_attention": {"rope_type": "default", "rope_theta": 10000,
                          "partial_rotary_factor": 1}}
# the tiny model: the original positions at 4,096 put pair 1 of the full
# layers' four rotated pairs inside YaRN's ramp (lo 0, hi 2), the others
# at its ends
CONFIG = {
    "model_type": "laguna", "vocab_size": 96, "hidden_size": 64,
    "intermediate_size": 96, "num_hidden_layers": 5,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 1048576, "attention_bias": False,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
    "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [0],
    "tie_word_embeddings": False, "gating": "per-head", "sliding_window": 8,
    "rope_parameters": {
        FULL: dict(PUBLISHED_ROPE[FULL], original_max_position_embeddings=4096),
        W: PUBLISHED_ROPE[W]},
    "layer_types": [FULL, W, W, W] * 2,
    "moe_apply_router_weight_on_input": False,
    "mlp_layer_types": ["dense"] + ["sparse"] * 7,
    "gating_types": ["per_head"] * 8,
    "moe_routed_scaling_factor": 2.5,
    "num_attention_heads_per_layer": [4, 6, 6, 6] * 2,
    "moe_router_logit_softcapping": 0,
    "published": {"num_experts": 16}, "held_experts": [0, 4],
    "reference_query_rows": 16, "lr": 1e-3, "router_bias_rate": 0.01,
}
M = laguna.from_config(CONFIG)
B, T = 4, 64
F32 = dict(compute_dtype=jnp.float32, attn_impl="flash")


@pytest.fixture(scope="module")
def ref():
    add_bench_paths()
    from benchlib.reference import laguna_ref
    return laguna_ref


def _names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _params(seed=0, m=M, scale=4.0):
    """Seeded weights at a scale where every mechanism answers: the
    matrices ``scale`` times the initial scale, so that the gates and the
    router's scores spread and the attention is no mean over keys."""
    p = laguna.init(jax.random.PRNGKey(seed), m)
    return jax.tree.map(lambda x: x * scale if x.ndim >= 2 else x, p)


def _bias(seed, m=M, scale=0.05):
    """A balancing bias that moves the choice: a spread of a few
    hundredths against scores that differ by as little."""
    return scale * jax.random.normal(jax.random.PRNGKey(seed),
                                     (m.routers, m.experts))


def _batches(n=3, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"tokens": np.asarray(jax.random.randint(
        k, (B, T + 1), 0, CONFIG["vocab_size"]))} for k in ks]


def _norms(tree, names) -> dict:
    return {n: float(jnp.linalg.norm(x))
            for n, x in zip(names, jax.tree.leaves(tree))}


# ------------------------------------------------------- the configuration
def test_the_model_is_the_first_layers_of_the_files_lists():
    assert M.layer_types == (FULL, W, W, W, FULL)
    assert M.mlp_types == ("dense",) + ("sparse",) * 4
    assert M.heads == (4, 6, 6, 6, 4) and M.kv_heads == 2
    assert (M.experts, M.held, M.top_k, M.window) == (16, (0, 4), 3, 8)
    p = laguna.init(jax.random.PRNGKey(0), M)
    assert "mlp" in p["blocks"][0] and "router" not in p["blocks"][0]
    blk = p["blocks"][1]
    assert blk["attn"]["wq"].shape == (64, 6 * 16)
    assert blk["attn"]["wk"].shape == blk["attn"]["wv"].shape == (64, 32)
    assert blk["attn"]["wg"].shape == (64, 6)
    assert blk["attn"]["wo"].shape == (6 * 16, 64)
    assert p["blocks"][4]["attn"]["wg"].shape == (64, 4)
    assert blk["router"]["w"].shape == (64, 16)
    assert blk["experts"]["w_gate"].shape == (4, 64, 16)
    assert blk["shared"]["w_down"].shape == (16, 64)
    assert p["head"].shape == p["tok_emb"].shape == (96, 64)


@pytest.mark.parametrize("key, value", [
    ("attention_bias", True), ("tie_word_embeddings", True),
    ("gating", "per-channel"), ("moe_apply_router_weight_on_input", True),
    ("moe_router_logit_softcapping", 30.0), ("decoder_sparse_step", 2),
    ("mlp_only_layers", [0, 1]), ("gating_types", ["none"] * 8),
    ("layer_types", ["linear_attention"] * 8),
    ("num_attention_heads_per_layer", [4, 5, 5, 5] * 2),
    ("held_experts", [0, 3]), ("num_experts_per_tok", 17),
    ("rope_parameters", {FULL: dict(PUBLISHED_ROPE[FULL], rope_type="linear"),
                         W: PUBLISHED_ROPE[W]}),
    ("rope_parameters", {FULL: PUBLISHED_ROPE[FULL],
                         W: dict(PUBLISHED_ROPE[W], rope_type="yarn")}),
    ("num_hidden_layers", 9)])
def test_a_file_the_model_does_not_build_is_refused(key, value):
    with pytest.raises((ValueError, KeyError), match="laguna|rope"):
        laguna.from_config(dict(CONFIG, **{key: value}))


def test_yarn_is_pinned_at_the_published_values(ref):
    """Of the 32 rotated pairs (64 of 128 channels, theta 5e5): lo 9 and
    hi 18, so pairs 0-9 keep their frequency, 18-31 are divided by the
    factor 128 and 10-17 lie on the ramp between; cos and sin carry the
    attention factor; the window layers rotate all 128 channels at 1e4.
    The reference, which imports nothing of the program, agrees."""
    m = laguna.from_config(dict(CONFIG, rope_parameters=PUBLISHED_ROPE,
                                head_dim=128))
    win, full = m.ropes
    base = [500000.0 ** (-2 * i / 64) for i in range(32)]
    assert full.rotary == 64 and len(full.inv_freq) == 32
    np.testing.assert_allclose(full.inv_freq[:10], base[:10], rtol=1e-12)
    np.testing.assert_allclose(full.inv_freq[18:],
                               [b / 128 for b in base[18:]], rtol=1e-12)
    for i in range(10, 18):     # r = (i - 9) / 9 strictly inside (0, 1)
        r = (i - 9) / 9
        assert full.inv_freq[i] == pytest.approx(
            (1 - r) * base[i] + r * base[i] / 128, rel=1e-12)
    assert full.scale == 1.4852030263919618
    assert win.rotary == 128 and win.scale == 1.0
    assert win.inv_freq[1] == pytest.approx(10000.0 ** (-2 / 128))
    z = ref._sizes(dict(CONFIG, rope_parameters=PUBLISHED_ROPE,
                        head_dim=128))
    np.testing.assert_allclose(z["rope_full"][1], full.inv_freq, rtol=1e-12)
    assert z["rope_full"][2] == full.scale
    # cos and sin carry the factor: a vector keeps its unrotated half and
    # its rotated half grows by the factor at position 0
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 3, 1, 128))
    y = laguna.rotate(x, jnp.arange(3), full)
    np.testing.assert_allclose(y[0, 0, 0, :64], 1.4852030263919618
                               * x[0, 0, 0, :64], rtol=1e-6)
    np.testing.assert_allclose(y[..., 64:], x[..., 64:])


# --------------------------------------------- the whole model, three steps
# float32 worker math: program and reference compute the same float32
# mathematics in another order (the scan's key blocks against full scores
# by query block, the dropless sort against every expert on every token,
# rsqrt against 1/sqrt): a loss agrees to a few float32 roundings, a
# leaf's norm to 2e-4. bfloat16 at the initial weights, where the cell
# reads it: a loss to 1e-3; a leaf's norm to 0.4% outside the routed
# experts' stacks (0.05% to 0.14% read on five seeds) and to 3% on them (a
# token near a tie of scores takes another expert in bfloat16: up to 2.1%
# read); the change after three Adam steps (close to lr times the
# gradient's sign) to 15% over leaves of 256 elements and more. The
# reference's control (fp8 matmul inputs, bfloat16 residual) is outside
# that band on the worst leaf outside the stacks: 1.05% to 1.34% on the
# same five seeds.
TOLERANCE = {"float32": dict(loss=2e-5, grad=2e-4, routed=2e-4, delta=2e-3,
                             least=1),
             "bfloat16": dict(loss=1e-3, grad=4e-3, routed=3e-2, delta=0.15,
                              least=256)}


def _three_steps(config, mesh, p0, batches):
    from minips_tpu.apps.lm_example import model_dp_step
    first = {"tokens": jnp.asarray(batches[0]["tokens"])}
    m, table, step, stats = model_dp_step(
        config, mesh, p0, first, updater="adam", lr=config["lr"])
    bias0 = np.asarray(table.state)     # the centred bias, beside the table
    assert bias0.shape == (m.routers, m.experts) == (4, 16)
    flat0 = np.asarray(table.params[: table.num_keys])
    losses, grad = [], None
    for i, b in enumerate(batches):
        losses.append(float(table.step_inplace(
            step, {"tokens": jnp.asarray(b["tokens"])})))
        if i == 0:   # Adam's first moment after one step: (1 - b1) * g
            mu = [s for s in jax.tree.leaves(
                table.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                if hasattr(s, "mu")][0].mu
            grad = table._unravel(mu[: table.num_keys] / 0.1)
    flat = np.asarray(table.params[: table.num_keys])
    assert not np.array_equal(np.asarray(table.state), bias0)   # it moved
    return losses, grad, table._unravel(jnp.asarray(flat - flat0)), bias0


def _worst_grad_gap(got: dict, want: dict) -> float:
    """Over the leaves outside the routed experts' stacks."""
    med = float(np.median(list(want.values())))
    return max(abs(got[n] - want[n]) / max(want[n], med) for n in want
               if ".experts." not in n)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fused_steps_agree_with_the_reference(ref, dtype):
    config = dict(CONFIG, compute_dtype=dtype, attn="flash", head_chunk=32,
                  updater="adam")
    batches = _batches()
    p0 = _params(1) if dtype == "float32" else _params(1, scale=1.0)
    mesh = make_mesh(1, devices=jax.devices()[:1])
    losses, grad, delta, bias0 = _three_steps(config, mesh, p0, batches)
    names = _names(p0)
    want = ref.run(config, batches, lambda: p0, names, rows_per_block=2)
    tol = TOLERANCE[dtype]
    # each centres its own bias: the mean scores, float32 or bfloat16
    np.testing.assert_allclose(bias0, want["bias"],
                               atol=1e-5 if dtype == "float32" else 2e-3)
    np.testing.assert_allclose(losses, want["loss"], rtol=tol["loss"])
    got_grad, got_delta = _norms(grad, names), _norms(delta, names)
    med = float(np.median(list(want["grad"].values())))
    sizes = dict(zip(names, (x.size for x in jax.tree.leaves(p0))))
    for n in names:
        band = tol["routed" if ".experts." in n else "grad"]
        assert got_grad[n] == pytest.approx(
            want["grad"][n], rel=band, abs=band * med), n
        if want["grad"][n] > 1e-3 * med and sizes[n] >= tol["least"]:
            assert got_delta[n] == pytest.approx(
                want["delta"][n], rel=tol["delta"]), n
    if dtype == "bfloat16":
        control = ref.run(config, batches, lambda: p0, names, low=True,
                          rows_per_block=2, bias=want["bias"])
        assert _worst_grad_gap(got_grad, want["grad"]) < tol["grad"] \
            < _worst_grad_gap(control["grad"], want["grad"])


def test_gradients_agree_leaf_by_leaf_as_vectors(ref):
    """Not only their norms: every leaf's float32 gradient against the
    reference's, as the norm of the difference, the choice under a bias;
    and the bias the step hands on: each expert's by the sign of its load
    error, by the reference's loads."""
    p0, b, bias = _params(3), _batches(1, seed=9)[0], _bias(9)
    z = ref._sizes(CONFIG)
    (_, (loads, _)), want = jax.value_and_grad(lambda p: ref.loss_sum(
        p, jnp.asarray(b["tokens"]), bias, z, False), has_aux=True)(p0)
    _, got, after = laguna.grad_fn(p0, {"tokens": jnp.asarray(b["tokens"])},
                                   bias, M, head_chunk=32, **F32)
    mean = np.mean(np.asarray(loads), -1, keepdims=True)
    np.testing.assert_allclose(after, bias + 0.01 * np.sign(mean - loads),
                               atol=1e-7)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        w = w / (B * T)                     # a sum against a mean
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * max(
            float(jnp.linalg.norm(w)), 1e-6), n


def test_the_step_and_the_routing_observer_are_each_built_once():
    """The centred bias is made on the default device and the step hands
    on a bias replicated on the mesh: the table places the first as the
    step places the next, and the set-up hands the observer what the steps
    do later, so neither program is built a second time."""
    from minips_tpu.apps.lm_example import model_dp_step
    config = dict(CONFIG, compute_dtype="bfloat16", attn="flash",
                  head_chunk=32, updater="adam")
    batches = [{"tokens": jnp.asarray(b["tokens"])} for b in _batches()]
    mesh = make_mesh(1, devices=jax.devices()[:1])
    _, table, step, stats = model_dp_step(
        config, mesh, _params(1, scale=1.0), batches[0], updater="adam",
        lr=config["lr"])
    for b in batches:
        table.step_inplace(step, b)
    jax.block_until_ready(stats(table.pull(), batches[-1], table.state))
    assert step._cache_size() == 1
    assert stats._cache_size() == 1


# ------------------------------------------------ what a position may see
def _one_layer(kind):
    """A one-layer model of ``kind`` with a dense MLP."""
    return laguna.from_config(dict(
        CONFIG, num_hidden_layers=1, layer_types=[kind],
        mlp_layer_types=["dense"], mlp_only_layers=[0],
        num_attention_heads_per_layer=[6 if kind == W else 4]))


@pytest.mark.parametrize("kind", [W, FULL])
def test_a_key_a_window_back_changes_nothing_in_a_window_layer(kind):
    """Token 10 changed: a window layer's outputs at 10 + 8 and later do
    not move (the key lies 8 or more positions back), those at 10 to 17
    do; a full layer's move everywhere after it."""
    m = _one_layer(kind)
    p = _params(5, m)
    toks = _batches(1, seed=11)[0]["tokens"]
    other = toks.copy()
    other[:, 10] = (other[:, 10] + 1) % CONFIG["vocab_size"]
    h = laguna.forward(p, jnp.asarray(toks), m, **F32)[0]
    h2 = laguna.forward(p, jnp.asarray(other), m, **F32)[0]
    np.testing.assert_allclose(h[:, :10], h2[:, :10], atol=1e-6)
    moved = jnp.max(jnp.abs(h - h2), axis=(0, 2))
    assert float(jnp.min(moved[10:18])) > 1e-3
    if kind == W:
        np.testing.assert_allclose(h[:, 18:], h2[:, 18:], atol=1e-6)
    else:
        assert float(jnp.min(moved[18:])) > 1e-4


def test_the_gate_scales_each_head_alone():
    """Head 2's gate column set to zero (its gate 1/2 at every token): the
    output moves by (1/2 - g) times head 2's gated share of W_o and no
    more, g = sigmoid(u W_g) of head 2 at that token; head 2's share is
    what the output loses when its rows of W_o are zero."""
    m = _one_layer(FULL)
    a = _params(6, m)["blocks"][0]["attn"]
    u = jax.random.normal(jax.random.PRNGKey(1), (2, T, 64))
    pos, fn = jnp.arange(T), laguna.tfm._attn_fn("reference")
    seen = {}
    out = laguna.attention(a, u, pos, FULL, m, fn, jnp.float32, seen)
    assert 0.0 < float(seen["gate_mean"]) < 1.0
    half = laguna.attention(dict(a, wg=a["wg"].at[:, 2].set(0.0)), u, pos,
                            FULL, m, fn, jnp.float32)
    keep = jnp.ones(4 * 16).at[2 * 16: 3 * 16].set(0.0)
    other = laguna.attention(dict(a, wo=a["wo"] * keep[:, None]), u, pos,
                             FULL, m, fn, jnp.float32)
    g = jax.nn.sigmoid(u @ a["wg"][:, 2])[..., None]
    np.testing.assert_allclose((half - out) * g, (0.5 - g) * (out - other),
                               atol=1e-5)
    assert float(jnp.max(jnp.abs(half - out))) > 1e-3


def test_two_head_shares_sum_to_the_uncut_attention():
    """Each share holds one of the two key/value heads with the query
    heads that read it, their gate columns and their rows of W_o: the
    shares' partial outputs add up to the uncut sublayer's."""
    for kind, H in ((W, 6), (FULL, 4)):
        m = _one_layer(kind)
        a = _params(7, m)["blocks"][0]["attn"]
        u = jax.random.normal(jax.random.PRNGKey(2), (2, T, 64))
        fn = laguna.tfm._attn_fn("flash", m.window if kind == W else None)
        whole = laguna.attention(a, u, jnp.arange(T), kind, m, fn,
                                 jnp.float32)
        r, share = H // 2, m._replace(kv_heads=1)
        parts = 0.0
        for g in range(2):
            q, kv = slice(g * r * 16, (g + 1) * r * 16), \
                slice(g * 16, (g + 1) * 16)
            cut = {"wq": a["wq"][:, q], "wk": a["wk"][:, kv],
                   "wv": a["wv"][:, kv], "wg": a["wg"][:, g * r:(g + 1) * r],
                   "wo": a["wo"][q]}
            parts = parts + laguna.attention(cut, u, jnp.arange(T), kind,
                                             share, fn, jnp.float32)
        np.testing.assert_allclose(parts, whole, atol=1e-5, rtol=1e-5)


def test_the_expert_shares_sum_to_the_uncut_layer():
    """The 16 experts over four shares of 4: their routed parts, with the
    shared expert counted once, add up to the sublayer that holds all
    16."""
    uncut = M._replace(held=(0, 16))
    blk = _params(8, uncut)["blocks"][1]
    u = jax.random.normal(jax.random.PRNGKey(3), (B * T, 64))
    bias = _bias(3, uncut)[0]
    whole, _, _ = laguna.experts(blk, u, bias, uncut, jnp.float32)
    chosen, gate, _ = laguna.route(blk["router"], u, bias, uncut)
    total = laguna._swiglu(blk["shared"], u, jnp.float32)
    for lo in range(0, 16, 4):
        stacks = jax.tree.map(lambda w: w[lo: lo + 4], blk["experts"])
        total = total + moe_apply_dropless(stacks, u, chosen, gate,
                                           held=(lo, lo + 4),
                                           compute_dtype=jnp.float32)
    np.testing.assert_allclose(total, whole, atol=1e-5, rtol=1e-5)


def test_the_block_checkpoint_changes_no_number():
    p0, b = _params(4), {"tokens": jnp.asarray(_batches(1, 5)[0]["tokens"])}
    bias = _bias(4)
    _, got, _ = laguna.grad_fn(p0, b, bias, M, head_chunk=32, **F32)

    def plain(p):
        seen = []           # the observer's path runs no checkpoint
        h, _, _ = laguna.forward(p, b["tokens"], M, bias, observed=seen,
                                 **F32)
        return laguna._nll(h, p["head"], b["tokens"][:, 1:], 32,
                           jnp.float32)
    want = jax.grad(plain)(p0)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7, err_msg=n)


# --------------------------------------------------------------- the app
class Sink:
    def __init__(self):
        self.lines = []

    def log(self, **kw):
        self.lines.append(kw)


def test_the_app_trains_the_model_and_logs_its_observer(tmp_path):
    """``--model_config`` with ``model_type`` ``laguna``: the one builder,
    the balancing bias beside the table, and at ``log_every`` the observer's
    counters in the ring under ``loop.readback``: the loss, the routing,
    the gate's mean by layer kind."""
    from minips_tpu.apps import lm_example
    assert lm_example.config_model(CONFIG) is laguna
    path = tmp_path / "laguna-tiny.json"
    path.write_text(json.dumps(dict(
        CONFIG, compute_dtype="float32", attn="reference", head_chunk=32)))
    prof.clear()
    sink = Sink()
    out = lm_example.main(
        ["--num_iters", "4", "--seq_len", str(T), "--batch_size", "8",
         "--log_every", "2", "--model_config", str(path)], metrics=sink)
    assert np.isfinite(out["losses"]).all()
    logged = [ln for ln in sink.lines if "attn_gate_mean" in ln]
    assert len(logged) == 2 and "linattn_decay_mean" not in logged[0]
    assert np.shape(logged[0]["moe_tokens_held"]) == (4, 4)
    assert np.shape(logged[0]["attn_gate_mean"]) == (5,)
    spans, counters = prof.snapshot()
    for name in (prof.LM_NLL, prof.MOE_TOKENS_HELD,
                 prof.MOE_LOAD_MAX_OVER_MEAN,
                 prof.ATTN_GATE_MEAN + ".window",
                 prof.ATTN_GATE_MEAN + ".full"):
        assert counters[name][0] == 2, name
    assert counters[prof.ATTN_GATE_MEAN + ".window"][1] == pytest.approx(
        sum(np.mean(ln["attn_gate_mean"][1:4]) for ln in logged))
    under = {s.parent_name for s in spans if s.name == prof.MOE_TOKENS_HELD}
    assert under == {prof.LOOP_READBACK}
