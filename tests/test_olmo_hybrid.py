"""The hybrid decoder of linear-attention and full-attention layers
(models/olmo_hybrid.py over ops/delta_rule.py) against the benchmark's own
plain reference (bench/benchlib/reference/olmo_hybrid_ref.py, the
token-by-token recurrence, found through tests/conftest.py's path hook) at
a size a test run can hold: the whole model through
``DenseTable.make_step`` for three steps, on four devices against one;
every leaf's gradient as a vector; what a block may and may not see; the
one step builder serving a model that carries no state, and building the
two that do to the programs it built before; the named scopes in the
compiled step; the app.
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

from jax.sharding import PartitionSpec as P

from minips_tpu.models import mla_moe, olmo_hybrid, zaya
from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh
from minips_tpu.utils import profiling as prof
from tests.conftest import add_bench_paths

CONFIG = {
    "model_type": "olmo_hybrid", "vocab_size": 96, "hidden_size": 32,
    "intermediate_size": 48, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "max_position_embeddings": 65536,
    "layer_types": ["linear_attention", "linear_attention",
                    "linear_attention", "full_attention"] * 2,
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 8, "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}, "lr": 1e-3,
}
M = olmo_hybrid.from_config(CONFIG)
B, T = 4, 128       # two chunks of the delta rule
F32 = dict(compute_dtype=jnp.float32, attn_impl="flash")


@pytest.fixture(scope="module")
def ref():
    add_bench_paths()
    from benchlib.reference import olmo_hybrid_ref
    return olmo_hybrid_ref


def _names(tree) -> list:
    return [".".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]]


def _params(seed=0, m=M):
    """Seeded weights at a scale where every mechanism answers: the
    projections four times the initial scale, so that a and b move the
    decay and the write strength (beta reaches past 1) and the gates and
    the QK-norms see inputs of different sizes."""
    p = olmo_hybrid.init(jax.random.PRNGKey(seed), m)
    return jax.tree.map(lambda x: x * 4.0 if x.ndim >= 2 else x, p)


def _batches(n=3, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), n)
    return [{"tokens": np.asarray(jax.random.randint(
        k, (B, T + 1), 0, CONFIG["vocab_size"]))} for k in ks]


def _tokens(seed=7):
    return {"tokens": jnp.asarray(_batches(1, seed)[0]["tokens"])}


def _norms(tree, names) -> dict:
    return {n: float(jnp.linalg.norm(x))
            for n, x in zip(names, jax.tree.leaves(tree))}


# ------------------------------------------------------- the configuration
def test_the_model_is_the_first_layers_of_the_files_layer_types():
    assert M.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert (M.depth, M.heads, M.lin_heads, M.lin_dk, M.lin_dv, M.taps,
            M.neg_eigval) == (4, 2, 2, 8, 16, 4, True)
    p = olmo_hybrid.init(jax.random.PRNGKey(0), M)
    assert ["linattn" in b for b in p["blocks"]] == [True] * 3 + [False]
    assert "attn" in p["blocks"][3] and "head" in p and "mlp" in p["blocks"][0]
    lin = p["blocks"][0]["linattn"]
    assert lin["conv_v"].shape == (4, 32) and lin["A_log"].shape == (2,)
    # the initial decay: A in (0, 16), softplus(dt_bias) in (1e-3, 1e-1)
    dt = jax.nn.softplus(lin["dt_bias"])
    assert float(dt.min()) >= 1e-3 * 0.99 and float(dt.max()) <= 0.1 * 1.01
    assert float(jnp.exp(lin["A_log"]).max()) <= 16.0


@pytest.mark.parametrize("key, value", [
    ("hidden_act", "gelu"), ("tie_word_embeddings", True),
    ("attention_bias", True), ("rope_parameters", {"rope_theta": 10000.0}),
    ("num_key_value_heads", 1), ("linear_num_value_heads", 4),
    ("layer_types", ["sliding_attention"] * 4), ("num_hidden_layers", 9)])
def test_a_file_the_model_does_not_build_is_refused(key, value):
    with pytest.raises(ValueError, match="olmo_hybrid"):
        olmo_hybrid.from_config(dict(CONFIG, **{key: value}))


# --------------------------------------------- the whole model, three steps
# float32 worker math: program and reference compute the same float32
# mathematics in another order (the chunked form against the recurrence,
# the scan's blocks against full scores, rsqrt against 1/sqrt): a loss
# agrees to a few float32 roundings, a leaf's norm to 2e-4. bfloat16 rounds
# weights and activations to 8 bits and is held at the initial weights,
# where the cell reads it (at four times their scale the sums over tokens
# that give A_log's, dt_bias's and the q and k projections' gradients
# cancel to a tenth of their terms, and 8 bits leave 20% of a norm, in
# the program and in a bfloat16 reference alike): a loss to 1e-3, a
# leaf's norm to 3% (0.5% to 1.2% read on three seeds); the change after
# three Adam steps is close to lr times the gradient's sign element by
# element: 15%, and leaves under 256 elements are left out of it there.
# The reference's control (fp8 matmul inputs, the state and the decay in
# bfloat16) is outside that band: its worst leaf's gradient norm is off by
# 6% to 11%.
TOLERANCE = {"float32": dict(loss=2e-5, grad=2e-4, delta=2e-3, least=1),
             "bfloat16": dict(loss=1e-3, grad=3e-2, delta=0.15, least=256)}


def _three_steps(config, mesh, p0, batches):
    from minips_tpu.apps.lm_example import model_dp_step
    first = {"tokens": jnp.asarray(batches[0]["tokens"])}
    m, table, step, stats = model_dp_step(
        config, mesh, p0, first, updater="adam", lr=config["lr"])
    assert table.state is None          # nothing carried beside the table
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
    return losses, grad, table._unravel(jnp.asarray(flat - flat0)), flat


def _worst_grad_gap(got: dict, want: dict) -> float:
    med = float(np.median(list(want.values())))
    return max(abs(got[n] - want[n]) / max(want[n], med) for n in want)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_three_fused_steps_agree_with_the_reference(ref, mesh4, dtype):
    config = dict(CONFIG, compute_dtype=dtype, attn="flash", head_chunk=32,
                  updater="adam")
    batches = _batches()
    p0 = _params(1) if dtype == "float32" else olmo_hybrid.init(
        jax.random.PRNGKey(1), M)
    mesh = mesh4 if dtype == "float32" else make_mesh(
        1, devices=jax.devices()[:1])
    losses, grad, delta, _ = _three_steps(config, mesh, p0, batches)
    names = _names(p0)
    want = ref.run(config, batches, lambda: p0, names)
    tol = TOLERANCE[dtype]
    np.testing.assert_allclose(losses, want["loss"], rtol=tol["loss"])
    got_grad, got_delta = _norms(grad, names), _norms(delta, names)
    med = float(np.median(list(want["grad"].values())))
    sizes = dict(zip(names, (x.size for x in jax.tree.leaves(p0))))
    for n in names:
        assert got_grad[n] == pytest.approx(
            want["grad"][n], rel=tol["grad"], abs=tol["grad"] * med), n
        if want["grad"][n] > 1e-3 * med and sizes[n] >= tol["least"]:
            assert got_delta[n] == pytest.approx(
                want["delta"][n], rel=tol["delta"]), n
    if dtype == "bfloat16":
        control = ref.run(config, batches, lambda: p0, names, low=True)
        assert _worst_grad_gap(got_grad, want["grad"]) < tol["grad"] \
            < _worst_grad_gap(control["grad"], want["grad"])


def test_the_step_on_four_devices_is_the_step_on_one(mesh4):
    """The same three batches through the table sharded four ways and
    through one shard: the state after three steps to the band
    ``test_transformer.py`` holds dp against one device to."""
    config = dict(CONFIG, compute_dtype="float32", attn="flash",
                  head_chunk=32)
    batches, p0 = _batches(), _params(2)
    l4, _, _, flat4 = _three_steps(config, mesh4, p0, batches)
    l1, _, _, flat1 = _three_steps(
        config, make_mesh(1, devices=jax.devices()[:1]), p0, batches)
    np.testing.assert_allclose(l4, l1, rtol=1e-5)
    np.testing.assert_allclose(flat4, flat1, rtol=1e-4, atol=1e-5)


def test_gradients_agree_leaf_by_leaf_as_vectors(ref):
    """Not only their norms: every leaf's float32 gradient against the
    reference's, as the norm of the difference."""
    p0, b = _params(3), _batches(1, seed=9)[0]
    z = ref._sizes(CONFIG)
    want = jax.grad(lambda p: ref.loss_sum(
        p, jnp.asarray(b["tokens"]), z, False))(p0)
    _, got = olmo_hybrid.grad_fn(p0, {"tokens": jnp.asarray(b["tokens"])}, M,
                                 head_chunk=32, **F32)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        w = w / (B * T)                     # a sum against a mean
        assert float(jnp.linalg.norm(g - w)) <= 2e-4 * max(
            float(jnp.linalg.norm(w)), 1e-6), n


def test_the_block_checkpoint_changes_no_number():
    """``forward`` under its checkpoint (the scan's and the kernel's named
    residuals kept) gives the gradients of the plain composition."""
    p0, b = _params(4), _tokens(5)
    _, got = olmo_hybrid.grad_fn(p0, b, M, head_chunk=32, **F32)

    def plain(p):
        seen = []           # the observer's path runs no checkpoint
        h = olmo_hybrid.forward(p, b["tokens"], M, observed=seen, **F32)
        return olmo_hybrid._nll(h, p["head"], b["tokens"][:, 1:], 32,
                                jnp.float32)
    want = jax.grad(plain)(p0)
    for n, g, w in zip(_names(p0), jax.tree.leaves(got),
                       jax.tree.leaves(want)):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-7, err_msg=n)


# ------------------------------------------------ what a position may see
def test_every_layer_is_causal_and_a_row_sees_no_other_row():
    p, toks = _params(5), _tokens(11)["tokens"]
    h = olmo_hybrid.forward(p, toks, M, **F32)
    cut = 70                # inside the second chunk
    other = toks.at[:, cut:].set((toks[:, cut:] + 1) % CONFIG["vocab_size"])
    h2 = olmo_hybrid.forward(p, other, M, **F32)
    np.testing.assert_allclose(h[:, :cut], h2[:, :cut], atol=1e-5)
    assert float(jnp.max(jnp.abs(h[:, cut:] - h2[:, cut:]))) > 1e-3
    rows = toks.at[1:].set((toks[1:] + 5) % CONFIG["vocab_size"])
    np.testing.assert_allclose(
        h[0], olmo_hybrid.forward(p, rows, M, **F32)[0], atol=1e-5)


def test_the_full_layer_carries_no_position_signal():
    """Without the linear layers before it a full-attention block treats
    its past as a set: swapping two earlier tokens leaves a later
    position's output as it was. The linear layers do not."""
    for kinds, same in ((["full_attention"], True),
                        (["linear_attention"], False)):
        m = olmo_hybrid.from_config(dict(
            CONFIG, num_hidden_layers=1, layer_types=kinds))
        p = _params(6, m)
        toks = _tokens(13)["tokens"]
        swapped = toks.at[:, 3].set(toks[:, 9]).at[:, 9].set(toks[:, 3])
        a = olmo_hybrid.forward(p, toks, m, **F32)[:, 40:]
        b = olmo_hybrid.forward(p, swapped, m, **F32)[:, 40:]
        assert bool(jnp.allclose(a, b, atol=1e-5)) is same, kinds


def test_beta_is_doubled_only_where_the_file_allows_negative_eigenvalues():
    b = _tokens(3)
    seen = jax.jit(lambda p: olmo_hybrid.observe(
        p, b, M, head_chunk=32, **F32))(_params(7))
    assert seen["beta_mean"].shape == seen["decay_mean"].shape == (3,)
    assert 0.8 < float(seen["beta_mean"].min()) < 1.2       # 2 sigmoid(~0)
    m1 = olmo_hybrid.from_config(dict(CONFIG, linear_allow_neg_eigval=False))
    seen1 = olmo_hybrid.observe(_params(7), b, m1, head_chunk=32, **F32)
    np.testing.assert_allclose(seen1["beta_mean"][0],
                               seen["beta_mean"][0] / 2, rtol=1e-5)
    assert float(seen["decay_mean"].max()) < 1.0
    assert np.isfinite(seen["state_absmax"]).all()
    want = olmo_hybrid.loss(_params(7), b, M, head_chunk=32, **F32)
    assert float(seen["lm_nll"]) == pytest.approx(float(want), rel=1e-5)


# ---------------------------------------------- the one builder of steps
# the jaxprs of ZAYA's and JoyAI's fused steps as the builder traces them,
# and of the dense transformer's as the benchmark's ``lm`` adapter builds
# it. On FOUR shards, the program of the change that gave a sharded table
# its leaves (its parent b4b5bd9 had the range-sharded vector's): each
# leaf gathered and reduce-scattered on its own dimension, the vectors in
# a flat range-sharded remainder (these small models' leaves are all
# narrower than a lane tile, so all of them lie there). On ONE shard, as
# the one-chip cells run them, the leaves' program of the change that
# gave a one-shard table its leaves (its parent 96f325b), which the
# sharded change left as it was. After a change of jax's printing: check
# out those commits, print the digests there, and compare.
STEP_JAXPR = {
    ("zaya", 4):
    "d57a0d7c683ccbacd67a434826749c7103e3f9cd6a12db0e65654e8671704fc6",
    ("joyai_llm_flash", 4):
    "b9d472cd2ca313954e13af7f9b0c477f9679d67ef46c64b69555e8d6a0e55d68",
    ("lm", 4):
    "6cf3308756804600d4a65f067fb9f6dac492a930b85e6bcf60667c2bd052628b",
    ("zaya", 1):
    "697e9d35db0bae56c42e97d9451ba3a821bda1b03948c8f08806bc008bd97968",
    ("joyai_llm_flash", 1):
    "c436f4b926f2e3a003bd17fb7b464c89b1e8fac5ca29dc9c5713864d811b9cac",
    ("lm", 1):
    "2b6fc1b60e9ef1d5d4c1aea35d158dacd9a4a05a95498523792472e95499cc2a"}


def _step_digest(kind: str, mesh) -> str:
    from minips_tpu.apps.lm_example import model_dp_step
    first = {"tokens": jnp.asarray(np.arange(4 * 17).reshape(4, 17) % 64,
                                   jnp.int32)}
    if kind == "lm":
        from minips_tpu.models import transformer as tfm
        from minips_tpu.tables.dense import DenseTable
        p = tfm.init(jax.random.PRNGKey(0), vocab=64, dim=16, heads=2,
                     depth=2, max_len=16)
        table = DenseTable(p, mesh, name="lm", updater="adam", lr=1e-3)
        step = table.make_step(
            functools.partial(tfm.grad_fn, heads=2, attn_impl="flash",
                              remat="dots", head_chunk=8),
            batch_spec=P(DATA_AXIS), accum=1, compute_dtype=jnp.bfloat16,
            comm="float32")
        args = (*table.step_state, first)
    else:
        if kind == "zaya":
            from tests.test_zaya import CONFIG as C
            config, mod = dict(C, model_type="zaya"), zaya
        else:
            from tests.test_mla_moe import CONFIG as C
            config, mod = dict(C), mla_moe
        config = dict(config, compute_dtype="bfloat16", attn="flash",
                      head_chunk=8)
        first["tokens"] = first["tokens"] % config["vocab_size"]
        p = mod.init(jax.random.PRNGKey(0), mod.from_config(config))
        _, table, step, _ = model_dp_step(config, mesh, p, first,
                                          updater="adam", lr=1e-3)
        args = (*table.step_state, first, table.state)
    text = str(jax.make_jaxpr(step)(*args))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)     # a function's address
    text = re.sub(                  # a set prints in the order of its hashes
        r"frozenset\(\{([^}]*)\}\)", lambda s: "frozenset({%s})" % ", ".join(
            sorted(s.group(1).split(", "))), text)
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("kind, shards", sorted(STEP_JAXPR))
def test_a_step_traces_to_the_program_it_traced(kind, shards):
    mesh = make_mesh(shards, devices=jax.devices()[:shards])
    assert _step_digest(kind, mesh) == STEP_JAXPR[kind, shards]


def test_the_scopes_are_in_the_compiled_step(mesh4):
    from minips_tpu.apps.lm_example import model_dp_step
    from minips_tpu.utils.trace_analysis import phase_of
    config = dict(CONFIG, compute_dtype="float32", attn="flash",
                  head_chunk=32)
    first = _tokens()
    _, table, step, _ = model_dp_step(config, mesh4, _params(), first,
                                      updater="adam", lr=1e-3)
    text = step.lower(*table.step_state,
                      first).compile().as_text()
    phases = {phase_of(s)[0]
              for s in set(re.findall(r'op_name="([^"]*)"', text))}
    for name in (prof.LM_LINATTN, prof.LM_LINATTN_PROJ, prof.LM_LINATTN_CONV,
                 prof.LM_LINATTN_SCAN, prof.LM_LINATTN_GATE, prof.LM_ATTN,
                 prof.LM_MLP, prof.LM_HEAD, prof.LM_EMBED, prof.PULL,
                 prof.PUSH, prof.UPDATE):
        assert name in phases, name
    assert set(prof.PHASES) >= {prof.LM_LINATTN_SCAN, prof.LM_LINATTN_GATE}


# --------------------------------------------------------------- the app
class Sink:
    def __init__(self):
        self.lines = []

    def log(self, **kw):
        self.lines.append(kw)


def test_the_app_trains_the_model_and_logs_its_observer(tmp_path):
    """``--model_config`` with ``model_type`` ``olmo_hybrid``: the one
    builder, no state beside the table, and at ``log_every`` the
    observer's counters in the ring under ``loop.readback``."""
    from minips_tpu.apps import lm_example
    assert lm_example.config_model(CONFIG) is olmo_hybrid
    path = tmp_path / "olmo-tiny.json"
    path.write_text(json.dumps(dict(
        CONFIG, compute_dtype="float32", attn="reference", head_chunk=32)))
    prof.clear()
    sink = Sink()
    out = lm_example.main(
        ["--num_iters", "4", "--seq_len", str(T), "--batch_size", "8",
         "--log_every", "2", "--model_config", str(path)], metrics=sink)
    assert np.isfinite(out["losses"]).all()
    logged = [ln for ln in sink.lines if "linattn_decay_mean" in ln]
    assert len(logged) == 2 and "moe_tokens_held" not in logged[0]
    assert np.shape(logged[0]["linattn_state_absmax"]) == (3,)
    spans, counters = prof.snapshot()
    for name in (prof.LM_NLL, prof.LINATTN_DECAY_MEAN,
                 prof.LINATTN_BETA_MEAN, prof.LINATTN_STATE_ABSMAX):
        assert counters[name][0] == 2, name
    assert prof.MOE_TOKENS_HELD not in counters
    assert counters[prof.LM_NLL][1] == pytest.approx(
        sum(ln["lm_nll"] for ln in logged))
    assert counters[prof.LINATTN_BETA_MEAN][1] == pytest.approx(
        sum(max(ln["linattn_beta_mean"]) for ln in logged))
    under = {s.parent_name for s in spans
             if s.name == prof.LINATTN_STATE_ABSMAX}
    assert under == {prof.LOOP_READBACK}
