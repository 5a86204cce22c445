"""Transformer LM: sequence-parallel forward/backward vs. the single-program
oracle, and end-to-end training through a DenseTable.

Beyond-parity family (reference has no attention, SURVEY.md §2.2); the point
under test is that the ring-attention path is exact in BOTH directions —
logits AND gradients — so long-context training can shard the sequence axis
without changing numerics.
"""

import functools

import jax

import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from jax import shard_map
from minips_tpu.models import transformer as tfm

CFG = dict(vocab=61, dim=32, heads=4, depth=2, max_len=128)
F32 = dict(compute_dtype=jnp.float32)  # tight tolerances for parity tests


def _toks(B, T, seed=0, vocab=CFG["vocab"]):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, vocab, size=(B, T)), jnp.int32)


@pytest.fixture(scope="module")
def params():
    return tfm.init(jax.random.PRNGKey(0), **CFG)


def _sp_logits(mesh, params, tokens, n, attn_impl="reference"):
    T_local = tokens.shape[1] // n

    def shard_fn(p, toks):
        shift = jax.lax.axis_index("data") * T_local
        return tfm.apply_sp(p, toks, shift, heads=CFG["heads"],
                            attn_impl=attn_impl, **F32)

    f = shard_map(shard_fn, mesh=mesh,
                      in_specs=(P(), P(None, "data")),
                      out_specs=P(None, "data"))
    return f(params, tokens)


def test_sp_forward_matches_full(mesh8, params):
    tokens = _toks(2, 64)
    want = tfm.apply(params, tokens, heads=CFG["heads"], **F32)
    got = _sp_logits(mesh8, params, tokens, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.slow  # heaviest compile; fast tier keeps sp-vs-dp grad
# coverage via test_dp_and_sp_training_steps_match
def test_sp_grad_matches_full(mesh8, params):
    """d(loss)/d(params) identical whether the sequence is sharded 8 ways
    (ring attention, pmean'd loss) or computed in one program."""
    B, T = 2, 64
    toks = _toks(B, T + 1, seed=1)
    inputs, targets = toks[:, :-1], toks[:, 1:]

    full_loss = functools.partial(tfm.loss, heads=CFG["heads"], **F32)
    g_full = jax.grad(lambda p: full_loss(p, {"tokens": toks}))(params)

    T_local = T // 8

    def sp_loss(p, inp, tgt):
        def shard_fn(p_, i_, t_):
            shift = jax.lax.axis_index("data") * T_local
            return tfm.loss_sp(p_, i_, t_, shift, heads=CFG["heads"], **F32)
        return shard_map(
            shard_fn, mesh=mesh8,
            in_specs=(P(), P(None, "data"), P(None, "data")),
            out_specs=P())(p, inp, tgt)

    l_sp, g_sp = jax.value_and_grad(sp_loss)(params, inputs, targets)
    l_full = full_loss(params, {"tokens": toks})
    assert abs(float(l_sp) - float(l_full)) < 1e-5
    flat_f, _ = jax.flatten_util.ravel_pytree(g_full)
    flat_s, _ = jax.flatten_util.ravel_pytree(g_sp)
    np.testing.assert_allclose(np.asarray(flat_s), np.asarray(flat_f),
                               rtol=2e-4, atol=2e-4)


def test_trains_through_dense_table(mesh8):
    """The LM is a PS citizen: params in a DenseTable, fused
    pull→grad→push→update step, loss decreases on a learnable pattern."""
    from minips_tpu.tables.dense import DenseTable

    params = tfm.init(jax.random.PRNGKey(1), vocab=16, dim=32, heads=2,
                      depth=1, max_len=64)
    table = DenseTable(params, mesh8, updater="adam", lr=3e-3,
                       name="lm")
    rng = np.random.default_rng(0)
    # periodic sequences -> next token is predictable
    base = rng.integers(0, 16, size=8)
    seq = np.tile(base, 6)[: 33]
    batch = {"tokens": jnp.asarray(np.stack([seq] * 8), jnp.int32)}

    step = table.make_step(
        functools.partial(tfm.grad_fn, heads=2), batch_spec=P("data"))
    sharded = jax.device_put(
        batch, NamedSharding(mesh8, P("data")))
    losses = [float(table.step_inplace(step, sharded)) for _ in range(40)]
    assert losses[-1] < losses[0] * 0.7, losses[::10]


def test_heads_mismatch_raises():
    with pytest.raises(ValueError):
        tfm.init(jax.random.PRNGKey(0), dim=30, heads=4)


def test_dp_and_sp_training_steps_match(mesh8):
    """One fused make_step update must produce the same new params whether
    the batch axis (dp) or the sequence axis (sp, ring attention + local
    loss) is sharded — the in-shard_map grad composition is exact."""
    from minips_tpu.tables.dense import DenseTable

    model = dict(vocab=16, dim=32, heads=2, depth=1, max_len=64)
    B, T = 8, 32
    toks = _toks(B, T + 1, seed=3, vocab=16)
    init_p = tfm.init(jax.random.PRNGKey(2), **model)

    # --- dp step
    t_dp = DenseTable(init_p, mesh8, updater="sgd", lr=0.1)
    step_dp = t_dp.make_step(
        lambda p, b: jax.value_and_grad(
            functools.partial(tfm.loss, heads=2, **F32))(p, b),
        batch_spec=P("data"))
    t_dp.step_inplace(step_dp, jax.device_put(
        {"tokens": toks}, NamedSharding(mesh8, P("data"))))

    # --- sp step from the same init
    t_sp = DenseTable(init_p, mesh8, updater="sgd", lr=0.1)
    T_local = T // 8

    def sp_grad(p, b):
        def shard_loss(p_, inp, tgt):
            shift = jax.lax.axis_index("data") * T_local
            return tfm.loss_sp(p_, inp, tgt, shift, heads=2,
                               reduce="local", **F32)
        return jax.value_and_grad(shard_loss)(p, b["inp"], b["tgt"])

    step_sp = t_sp.make_step(
        sp_grad, batch_spec={"inp": P(None, "data"),
                             "tgt": P(None, "data")})
    seq_sh = NamedSharding(mesh8, P(None, "data"))
    t_sp.step_inplace(step_sp, {
        "inp": jax.device_put(toks[:, :-1], seq_sh),
        "tgt": jax.device_put(toks[:, 1:], seq_sh)})

    f_dp, _ = jax.flatten_util.ravel_pytree(t_dp.pull())
    f_sp, _ = jax.flatten_util.ravel_pytree(t_sp.pull())
    np.testing.assert_allclose(np.asarray(f_sp), np.asarray(f_dp),
                               rtol=2e-4, atol=2e-5)


def test_seq_len_over_max_len_raises(params):
    long_toks = _toks(1, 200)  # CFG max_len=128
    with pytest.raises(ValueError, match="max_len"):
        tfm.apply(params, long_toks, heads=CFG["heads"])


def test_remat_matches_no_remat(mesh8, params):
    """jax.checkpoint'd blocks change memory, not math: logits and grads
    identical with and without remat, including through ring attention."""
    toks = _toks(2, 65, seed=5)

    def loss_fn(remat):
        def f(p):
            logits = tfm.apply(p, toks[:, :-1], heads=CFG["heads"],
                               remat=remat, **F32)
            return tfm.nll(logits, toks[:, 1:])
        return f

    l0, g0 = jax.value_and_grad(loss_fn(False))(params)
    l1, g1 = jax.value_and_grad(loss_fn(True))(params)
    assert float(l0) == float(l1)
    f0, _ = jax.flatten_util.ravel_pytree(g0)
    f1, _ = jax.flatten_util.ravel_pytree(g1)
    np.testing.assert_allclose(np.asarray(f1), np.asarray(f0),
                               rtol=1e-6, atol=1e-7)

    # sp path with remat still matches the full-program oracle
    T = 64
    def sp_loss(p):
        def shard_fn(p_, inp, tgt):
            shift = jax.lax.axis_index("data") * (T // 8)
            logits = tfm.apply_sp(p_, inp, shift, heads=CFG["heads"],
                                  remat=True, **F32)
            return jax.lax.pmean(tfm.nll(logits, tgt), "data")
        return shard_map(
            shard_fn, mesh=mesh8,
            in_specs=(P(), P(None, "data"), P(None, "data")),
            out_specs=P())(p, toks[:, :-1], toks[:, 1:])

    l_sp = sp_loss(params)
    assert abs(float(l_sp) - float(l0)) < 1e-5


def test_lm_example_remat_matches_no_remat(mesh8):
    """--remat changes memory, not math: dp trajectories agree."""
    import argparse

    from minips_tpu.apps import lm_example as app
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.utils.metrics import MetricsLogger

    cfg = Config(
        table=TableConfig(name="lm", kind="dense", updater="adam", lr=3e-3),
        train=TrainConfig(batch_size=16, num_iters=6, log_every=100),
    )
    finals = {}
    for remat in (False, True):
        out = app.run(cfg, argparse.Namespace(layout="dp", seq_len=32,
                                              tp=2, microbatches=2,
                                              remat=remat),
                      MetricsLogger(None, verbose=False))
        finals[remat] = out["losses"]
    np.testing.assert_allclose(finals[False], finals[True],
                               rtol=2e-5, atol=2e-5)


def test_lm_example_remat_rejected_off_dp():
    import argparse

    import pytest as _pytest

    from minips_tpu.apps import lm_example as app
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.utils.metrics import MetricsLogger

    cfg = Config(
        table=TableConfig(name="lm", kind="dense", updater="adam", lr=3e-3),
        train=TrainConfig(batch_size=16, num_iters=2, log_every=100),
    )
    with _pytest.raises(SystemExit, match="remat"):
        app.run(cfg, argparse.Namespace(layout="sp", seq_len=32, tp=2,
                                        microbatches=2, remat=True),
                MetricsLogger(None, verbose=False))


def _head_case(vocab=64):
    p = tfm.init(jax.random.PRNGKey(0), vocab=vocab, dim=32, heads=2,
                 depth=2, max_len=16)
    toks = jnp.asarray(
        np.random.default_rng(0).integers(0, vocab, size=(2, 17)))
    return p, {"tokens": toks}


def _leaf_errors(got, want):
    """Each leaf's ``|got - want| / |want|`` as vectors."""
    return [float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))]


@pytest.mark.parametrize("chunk", [4, 16])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_chunked_head_nll_matches_plain(dtype, chunk):
    """nll_chunked (scanned tied head + CE, logits never whole, each
    chunk's gradients formed while its logits are live) must equal the
    plain path in loss AND grads — it is a memory-layout change, not a
    numerics change."""
    p, batch = _head_case()

    def f(chunk):
        return jax.value_and_grad(
            lambda q: tfm.loss(q, batch, heads=2, head_chunk=chunk,
                               compute_dtype=jnp.dtype(dtype)))(p)

    (l0, g0), (l1, g1) = f(0), f(chunk)
    if dtype == "float32":
        # f32 compute isolates the MATH parity
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=1e-6)
    else:
        # bf16 (the bench path): same loss to bf16 resolution; the
        # emb-grad's sequential per-chunk matmul accumulation legitimately
        # differs from the one-shot matmul by ~1e-3 — an order change,
        # not an error (bf16 rounds at 4e-3)
        np.testing.assert_allclose(float(l0), float(l1), rtol=2e-3)
        assert max(_leaf_errors(g1, g0)) < 4e-3


@pytest.mark.parametrize("dtype, tol", [("float32", 1e-6),
                                        ("bfloat16", 8e-3)])
def test_chunked_head_scales_with_the_cotangent(dtype, tol):
    """The gradients are formed in the forward loop for a cotangent of 1;
    the backward rule owes the scale of any other."""
    p, batch = _head_case()

    def g(scale):
        return jax.grad(
            lambda q: scale * tfm.loss(q, batch, heads=2, head_chunk=4,
                                       compute_dtype=jnp.dtype(dtype)))(p)

    assert max(_leaf_errors(
        g(3.0), jax.tree.map(lambda x: 3.0 * x, g(1.0)))) < tol


def _head_instructions(fn, *args):
    """(opcode, op_name) of the compiled instructions under ``lm.head``."""
    from minips_tpu.utils import profiling as prof
    from minips_tpu.utils.trace_analysis import phase_of
    from tests.test_named_scopes import _instructions

    text = jax.jit(fn).lower(*args).compile().as_text()
    return [(op, scope) for op, scope in _instructions(text)
            if phase_of(scope)[0] == prof.LM_HEAD]


@pytest.mark.parametrize("differentiated, products", [(False, 1), (True, 3)])
def test_chunked_head_runs_no_product_twice(differentiated, products):
    """From the compiled module: one loop over the chunks either way; one
    vocabulary-sized product a chunk for the loss alone, and under
    differentiation the three a step's mathematics needs (logits, dh,
    dW) — no chunk's logits are made again for a backward loop."""
    p, batch = _head_case()

    def loss(q):
        return tfm.loss(q, batch, heads=2, head_chunk=4,
                        compute_dtype=jnp.float32)

    got = _head_instructions(jax.grad(loss) if differentiated else loss, p)
    assert sum(op == "while" for op, _ in got) == 1
    assert sum(op == "dot" for op, _ in got) == products
    assert not any("rematted_computation" in scope for _, scope in got)


@pytest.mark.parametrize("dtype, tol", [("float32", 2e-5),
                                        ("bfloat16", 1e-2)])
def test_chunked_head_in_the_dense_step_on_four_devices(mesh4, dtype, tol):
    """Inside ``DenseTable.make_step``'s shard_map ``h`` and the pulled
    weights vary over ``data``: the scan's carries and the cotangents the
    backward rule returns must carry those axes, and one SGD step must
    move the parameters as the plain head's step moves them."""
    from minips_tpu.tables.dense import DenseTable

    params = tfm.init(jax.random.PRNGKey(0), vocab=128, dim=32, heads=2,
                      depth=2, max_len=32)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, 128, size=(8, 33)), jnp.int32)}

    def one_step(head_chunk):
        table = DenseTable(params, mesh4, updater="sgd", lr=0.5)
        before = np.asarray(table.params)
        step = table.make_step(
            lambda p, b: jax.value_and_grad(functools.partial(
                tfm.loss, heads=2, head_chunk=head_chunk,
                compute_dtype=jnp.dtype(dtype)))(p, b),
            compute_dtype=jnp.dtype(dtype))
        loss = table.step_inplace(step, batch)
        return float(loss), np.asarray(table.params) - before

    (l0, d0), (l1, d1) = one_step(0), one_step(8)
    np.testing.assert_allclose(l1, l0, rtol=tol)
    assert np.linalg.norm(d0) > 0
    assert np.linalg.norm(d1 - d0) < tol * np.linalg.norm(d0)


def test_chunked_head_with_replicated_weights_inside_shard_map(mesh4):
    """Differentiated inside shard_map with the weights replicated and the
    batch sharded, the head's inputs vary over different axes: the
    weights' cotangent must come back summed over the workers, as autodiff
    sums it for the plain head."""
    from minips_tpu.parallel.mesh import DATA_AXIS

    p, _ = _head_case()
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(2).integers(0, 64, size=(8, 17)), jnp.int32)}

    def grads(head_chunk):
        def local(q, b):
            return jax.grad(lambda r: tfm.loss(
                r, b, heads=2, head_chunk=head_chunk, **F32))(q)
        return jax.jit(jax.shard_map(
            local, mesh=mesh4, in_specs=(P(), P(DATA_AXIS)),
            out_specs=P()))(p, batch)

    for a, b in zip(jax.tree.leaves(grads(4)), jax.tree.leaves(grads(0))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-5, atol=1e-6)


def test_chunked_head_rejects_nondivisible():
    import jax
    import jax.numpy as jnp
    import pytest

    from minips_tpu.models import transformer as tfm

    p = tfm.init(jax.random.PRNGKey(0), vocab=64, dim=32, heads=2,
                 depth=1, max_len=16)
    batch = {"tokens": jnp.zeros((1, 17), jnp.int32)}
    with pytest.raises(ValueError, match="divide"):
        tfm.loss(p, batch, heads=2, head_chunk=5)


def test_remat_modes_grad_parity():
    """Every remat mode (full / attn-saved / dots-saved) is a pure
    memory-schedule change: losses and grads must equal the no-remat
    path exactly (f32)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from minips_tpu.models import transformer as tfm

    p = tfm.init(jax.random.PRNGKey(1), vocab=32, dim=32, heads=2,
                 depth=2, max_len=16)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, 32, size=(2, 17)))}

    def f(remat):
        return jax.value_and_grad(
            lambda q: tfm.loss(q, batch, heads=2,
                               compute_dtype=jnp.float32,
                               remat=remat))(p)

    l0, g0 = f(False)
    for mode in (True, "attn", "dots", "hybrid", "hybrid_qkv"):
        l1, g1 = f(mode)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)
    import pytest

    with pytest.raises(ValueError, match="unknown remat mode"):
        f("nonsense")


# ---------------------------------------------------------------- GQA
GQA_CFG = dict(vocab=61, dim=32, heads=4, depth=2, max_len=128,
               kv_heads=2)


@pytest.mark.parametrize("kv", [1, 2])
def test_gqa_flash_matches_reference_impl(kv):
    """Grouped-query logits agree between the two attention impls (the
    reference path repeats KV heads, the flash path head-maps) — same
    parity discipline as the full-head model."""
    p = tfm.init(jax.random.PRNGKey(3), **{**GQA_CFG, "kv_heads": kv})
    toks = _toks(2, 32, seed=3)
    ref = tfm.apply(p, toks, heads=4, attn_impl="reference", **F32)
    fl = tfm.apply(p, toks, heads=4, attn_impl="flash", **F32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fl),
                               rtol=1e-4, atol=1e-4)


def test_gqa_param_tree_and_sizes():
    """GQA halves the KV projection: wkv is [dim, 2, kv_heads*hd] and no
    fused qkv leaf exists; kv_heads=heads (or None) keeps the exact
    pre-GQA tree (checkpoint compatibility)."""
    p = tfm.init(jax.random.PRNGKey(0), **GQA_CFG)
    blk = p["blocks"][0]
    assert "qkv" not in blk and blk["wq"].shape == (32, 32)
    assert blk["wkv"].shape == (32, 2, 2 * 8)   # kv_heads=2, hd=8
    p_full = tfm.init(jax.random.PRNGKey(0), **{**GQA_CFG,
                                                "kv_heads": None})
    assert "qkv" in p_full["blocks"][0] and "wkv" not in p_full["blocks"][0]
    with pytest.raises(ValueError, match="divide"):
        tfm.init(jax.random.PRNGKey(0), **{**GQA_CFG, "kv_heads": 3})


def test_gqa_remat_modes_grad_parity():
    """The remat spectrum must stay a pure memory-schedule change on the
    split q/kv layout too (both projections carry the 'qkv' checkpoint
    name, so hybrid_qkv saves them)."""
    p = tfm.init(jax.random.PRNGKey(1), vocab=32, dim=32, heads=4,
                 depth=2, max_len=16, kv_heads=2)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, 32, size=(2, 17)))}

    def f(remat):
        return jax.value_and_grad(
            lambda q: tfm.loss(q, batch, heads=4,
                               compute_dtype=jnp.float32,
                               remat=remat))(p)

    l0, g0 = f(False)
    for mode in (True, "attn", "dots", "hybrid", "hybrid_qkv"):
        l1, g1 = f(mode)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_gqa_sp_forward_matches_full(mesh8):
    """Sequence-parallel GQA: the ring rotates the SMALL kv shards across
    devices; logits must match the single-program oracle."""
    p = tfm.init(jax.random.PRNGKey(4), **GQA_CFG)
    tokens = _toks(2, 64, seed=4)
    want = tfm.apply(p, tokens, heads=4, **F32)
    got = _sp_logits(mesh8, p, tokens, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_gqa_trains_through_dense_table(mesh8):
    """e2e: a GQA LM trains through the fused DenseTable step and the
    loss decreases — the whole PS path is layout-agnostic."""
    from minips_tpu.tables.dense import DenseTable

    p = tfm.init(jax.random.PRNGKey(5), vocab=61, dim=32, heads=4,
                 depth=1, max_len=64, kv_heads=1)   # MQA extreme
    from minips_tpu.parallel.mesh import make_mesh
    mesh = make_mesh()
    table = DenseTable(p, mesh, name="gqa_lm", updater="adam", lr=1e-2)
    step = table.make_step(functools.partial(tfm.grad_fn, heads=4))
    toks = _toks(8, 33, seed=5)
    losses = [float(table.step_inplace(step, {"tokens": toks}))
              for _ in range(12)]
    assert losses[-1] < losses[0] * 0.9, losses


# ---------------------------------------------------------------- RoPE
def test_rope_dot_depends_on_relative_position_only():
    """The defining RoPE identity: <rotate(q, p1), rotate(k, p2)> equals
    <rotate(q, p1-p2), rotate(k, 0)> — scores see relative offsets."""
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(1, 1, 1, 16)), jnp.float32)
    for p1, p2 in ((5, 3), (40, 11), (7, 7)):
        a = jnp.sum(tfm.rope_rotate(q, jnp.array([p1]))
                    * tfm.rope_rotate(k, jnp.array([p2])))
        b = jnp.sum(tfm.rope_rotate(q, jnp.array([p1 - p2]))
                    * tfm.rope_rotate(k, jnp.array([0])))
        np.testing.assert_allclose(float(a), float(b), rtol=1e-5)


def test_rope_param_tree_has_no_pos_emb():
    p = tfm.init(jax.random.PRNGKey(0), vocab=61, dim=32, heads=4,
                 depth=1, rope=True)
    assert "pos_emb" not in p
    with pytest.raises(ValueError, match="even head dim"):
        tfm.init(jax.random.PRNGKey(0), vocab=61, dim=36, heads=4,
                 depth=1, rope=True)   # hd=9


def test_rope_unbounded_sequence_length():
    """No positional table -> no max_len cap: a rope model runs sequences
    far past the (ignored) max_len where the learned table raises."""
    p_learned = tfm.init(jax.random.PRNGKey(0), vocab=61, dim=32, heads=4,
                         depth=1, max_len=16)
    p_rope = tfm.init(jax.random.PRNGKey(0), vocab=61, dim=32, heads=4,
                      depth=1, max_len=16, rope=True)
    toks = _toks(1, 48, seed=6)
    with pytest.raises(ValueError, match="max_len"):
        tfm.apply(p_learned, toks, heads=4, **F32)
    logits = tfm.apply(p_rope, toks, heads=4, **F32)
    assert logits.shape == (1, 48, 61)


def test_rope_flash_matches_reference_impl():
    """Rotation happens before either attention impl — parity must hold
    (incl. composed with GQA)."""
    p = tfm.init(jax.random.PRNGKey(8), vocab=61, dim=32, heads=4,
                 depth=2, rope=True, kv_heads=2)
    toks = _toks(2, 32, seed=8)
    ref = tfm.apply(p, toks, heads=4, attn_impl="reference", **F32)
    fl = tfm.apply(p, toks, heads=4, attn_impl="flash", **F32)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(fl),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("attn_impl", ["reference", "flash"])
def test_rope_sp_forward_matches_full(mesh8, attn_impl):
    """Sequence-parallel RoPE: each shard rotates its resident Q and its
    HOME K rows by their global positions before the ring moves K — the
    sharded logits must match the single-program oracle through BOTH ring
    impls (the flash impl runs its exact offset-blockwise path off-TPU)."""
    p = tfm.init(jax.random.PRNGKey(9), vocab=61, dim=32, heads=4,
                 depth=2, rope=True)
    tokens = _toks(2, 64, seed=9)
    want = tfm.apply(p, tokens, heads=4, **F32)
    got = _sp_logits(mesh8, p, tokens, 8, attn_impl=attn_impl)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-4)


def test_rope_trains_through_dense_table(mesh8):
    from minips_tpu.tables.dense import DenseTable
    from minips_tpu.parallel.mesh import make_mesh

    p = tfm.init(jax.random.PRNGKey(10), vocab=61, dim=32, heads=4,
                 depth=1, rope=True)
    mesh = make_mesh()
    table = DenseTable(p, mesh, name="rope_lm", updater="adam", lr=1e-2)
    step = table.make_step(functools.partial(tfm.grad_fn, heads=4))
    toks = _toks(8, 33, seed=10)
    losses = [float(table.step_inplace(step, {"tokens": toks}))
              for _ in range(12)]
    assert losses[-1] < losses[0] * 0.9, losses


def test_rope_remat_modes_grad_parity():
    """Remat must stay a pure memory-schedule change with the rotation
    inside the block's attention call."""
    p = tfm.init(jax.random.PRNGKey(11), vocab=32, dim=32, heads=4,
                 depth=2, rope=True)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(11).integers(0, 32, size=(2, 17)))}

    def f(remat):
        return jax.value_and_grad(
            lambda q: tfm.loss(q, batch, heads=4,
                               compute_dtype=jnp.float32,
                               remat=remat))(p)

    l0, g0 = f(False)
    for mode in (True, "attn", "dots", "hybrid", "hybrid_qkv"):
        l1, g1 = f(mode)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


# ------------------------------------------------------------- dropout
def test_dropout_zero_is_identity():
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=2, max_len=32)
    toks = _toks(2, 16)
    base = tfm.apply(p, toks, heads=4, **F32)
    same = tfm.apply(p, toks, heads=4, dropout=0.0,
                     rng=jax.random.PRNGKey(1), **F32)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(same))
    # eval convention: no rng -> identity even with a rate set
    ev = tfm.apply(p, toks, heads=4, dropout=0.5, **F32)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(ev))


def test_dropout_keyed_deterministic_and_varying():
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=2, max_len=32)
    toks = _toks(2, 16)
    a = tfm.apply(p, toks, heads=4, dropout=0.3,
                  rng=jax.random.PRNGKey(5), **F32)
    b = tfm.apply(p, toks, heads=4, dropout=0.3,
                  rng=jax.random.PRNGKey(5), **F32)
    c = tfm.apply(p, toks, heads=4, dropout=0.3,
                  rng=jax.random.PRNGKey(6), **F32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.allclose(np.asarray(a), np.asarray(c))
    base = tfm.apply(p, toks, heads=4, **F32)
    assert not np.allclose(np.asarray(a), np.asarray(base))


def test_dropout_remat_grad_parity_same_key():
    """remat must replay the SAME dropout masks in recompute (the key is
    a traced arg of the checkpointed block): grads with and without
    remat are identical for a fixed batch key."""
    p = tfm.init(jax.random.PRNGKey(1), vocab=32, dim=32, heads=4,
                 depth=2, max_len=16)
    batch = {"tokens": jnp.asarray(
        np.random.default_rng(1).integers(0, 32, size=(2, 17))),
        "rng": jax.random.PRNGKey(9)}

    def f(remat):
        return jax.value_and_grad(
            lambda q: tfm.loss(q, batch, heads=4,
                               compute_dtype=jnp.float32, remat=remat,
                               dropout=0.25))(p)

    l0, g0 = f(False)
    for mode in (True, "attn", "dots"):
        l1, g1 = f(mode)
        np.testing.assert_allclose(float(l0), float(l1), rtol=1e-6)
        for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-7)


def test_dropout_without_key_raises():
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=1, max_len=32)
    with pytest.raises(ValueError, match="rng"):
        tfm.loss(p, {"tokens": jnp.zeros((1, 9), jnp.int32)}, heads=4,
                 dropout=0.1)


def test_dropout_trains_through_dense_table(mesh8):
    """e2e through the fused step: the per-step key rides the batch with
    a replicated spec; loss decreases."""
    import functools

    from minips_tpu.parallel.mesh import make_mesh
    from minips_tpu.tables.dense import DenseTable

    p = tfm.init(jax.random.PRNGKey(2), vocab=61, dim=32, heads=4,
                 depth=1, max_len=64)
    mesh = make_mesh()
    table = DenseTable(p, mesh, name="drop_lm", updater="adam", lr=1e-2)
    step = table.make_step(
        functools.partial(tfm.grad_fn, heads=4, dropout=0.1),
        batch_spec={"tokens": P("data"), "rng": P()})
    toks = _toks(8, 33, seed=3)
    key = jax.random.PRNGKey(0)
    losses = [float(table.step_inplace(
        step, {"tokens": toks, "rng": jax.random.fold_in(key, i)}))
        for i in range(15)]
    assert losses[-1] < losses[0] * 0.9, losses


def test_dropout_per_worker_key_stack():
    """A [W, 2] per-worker key stack: loss() uses row 0 of its local
    slice, so feeding the stack replicated equals feeding row 0 alone —
    and the rate guard rejects out-of-range values."""
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=1, max_len=32)
    toks = _toks(2, 17)
    key = jax.random.PRNGKey(4)
    l_flat = tfm.loss(p, {"tokens": toks, "rng": key}, heads=4,
                      dropout=0.3, **F32)
    stack = jnp.stack([key, jax.random.PRNGKey(99)])
    l_stack = tfm.loss(p, {"tokens": toks, "rng": stack}, heads=4,
                       dropout=0.3, **F32)
    np.testing.assert_allclose(float(l_flat), float(l_stack), rtol=1e-6)
    with pytest.raises(ValueError, match="outside"):
        tfm.loss(p, {"tokens": toks, "rng": key}, heads=4, dropout=1.0)


def test_dropout_rng_contract_rejects_typed_and_malformed_keys():
    """ADVICE r3: loss() infers the per-worker stack from ndim == 2 on
    RAW uint32 keys, so typed jax.random.key arrays (which would bypass
    the slice and silently broadcast one mask) and non-[W, 2] stacks
    must fail loudly, not degrade."""
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=1, max_len=32)
    toks = _toks(2, 17, vocab=31)  # stay in THIS model's id range
    with pytest.raises(TypeError, match="typed"):
        tfm.loss(p, {"tokens": toks, "rng": jax.random.key(3)}, heads=4,
                 dropout=0.1)
    with pytest.raises(ValueError, match=r"\[W, 2\]"):
        tfm.loss(p, {"tokens": toks,
                     "rng": jnp.zeros((4, 3), jnp.uint32)}, heads=4,
                 dropout=0.1)
    # eval convention: dropout=0 never reads the key, so a reused
    # training batch carrying a typed key must NOT start raising
    l_eval = tfm.loss(p, {"tokens": toks, "rng": jax.random.key(3)},
                      heads=4)
    assert np.isfinite(float(l_eval))


def test_dropout_refused_on_parallel_schedule_paths():
    """ADVICE r3: per-block residual dropout lives in the sequential
    layer loop; an apply_blocks (pipeline-style) caller asking for
    dropout > 0 must get a loud refusal, not silent embedding-only
    regularization."""
    p = tfm.init(jax.random.PRNGKey(0), vocab=31, dim=32, heads=4,
                 depth=1, max_len=32)
    toks = _toks(2, 16)
    with pytest.raises(ValueError, match="apply_blocks"):
        tfm._forward(p, toks, jnp.arange(16), 4,
                     tfm._attn_fn("reference"), jnp.float32,
                     apply_blocks=lambda h: h, dropout=0.1,
                     rng=jax.random.PRNGKey(1))


# ------------------------------------------------ the flash kernel's
# residuals across the block checkpoint (kernels under interpret=True)
KEEPING_MODES = ("dots", "attn", "hybrid", "hybrid_qkv")


def _flash_attn(q, k, v):
    from minips_tpu.ops.flash_attention import flash_attention

    return flash_attention(q, k, v, causal=True, interpret=True)


def _flash_model():
    p = tfm.init(jax.random.PRNGKey(2), vocab=32, dim=32, heads=2,
                 depth=2, max_len=128)
    toks = _toks(2, 128, seed=2, vocab=32)

    def loss_of(remat):
        def loss(q):
            logits, _ = tfm._forward(q, toks, jnp.arange(128), 2,
                                     _flash_attn, jnp.float32, remat=remat)
            return tfm.nll(logits, jnp.roll(toks, -1, axis=1))
        return loss
    return p, loss_of


@pytest.mark.parametrize("remat, forward_calls_a_block", [
    (False, 1), (True, 2)] + [(m, 1) for m in KEEPING_MODES])
def test_flash_forward_kernel_calls_a_block(remat, forward_calls_a_block):
    """Every mode that keeps anything keeps the forward kernel's ``out``
    and ``lse``, so the rematted forward holds no ``flash_fwd``: one call
    a block in the whole of ``grad``; ``remat=True`` runs it twice."""
    from minips_tpu.utils import profiling as prof

    from tests.conftest import pallas_call_names

    p, loss_of = _flash_model()
    names = pallas_call_names(
        jax.make_jaxpr(jax.grad(loss_of(remat)))(p).jaxpr)
    blocks = len(p["blocks"])
    assert names.count(prof.FLASH_FWD) == forward_calls_a_block * blocks
    assert names.count(prof.FLASH_BWD) == blocks
    assert not {"flash_dq", "flash_dkv"} & set(names)


@pytest.mark.parametrize("remat", KEEPING_MODES)
def test_flash_residuals_are_saved_by_the_policy(remat, capsys):
    """What crosses the checkpoint, a block: ``lse`` under its name and
    ``out`` as it is named, ``[B, T, H hd]``, from the kernel's module
    (jax prints a residual that the block's own outputs also read as the
    output of the no-op ``reduce_precision`` it puts behind it, not by
    its name)."""
    from minips_tpu.utils import profiling as prof

    p, loss_of = _flash_model()
    jax.ad_checkpoint.print_saved_residuals(loss_of(remat), p)
    lines = [l for l in capsys.readouterr().out.splitlines()
             if "ops/flash_attention.py" in l]
    blocks = len(p["blocks"])
    assert sum(f"named '{prof.FLASH_LSE}'" in l for l in lines) == blocks
    outs = [l for l in lines if l.startswith("f32[2,128,32] ")]
    assert len(outs) == blocks == len(lines) - blocks
    assert all(f"named '{prof.FLASH_OUT}'" in l or "reduce_precision" in l
               for l in outs)


def test_flash_residuals_are_not_saved_by_full_remat(capsys):
    p, loss_of = _flash_model()
    jax.ad_checkpoint.print_saved_residuals(loss_of(True), p)
    out = capsys.readouterr().out
    assert "flash_" not in out and "ops/flash_attention.py" not in out


def test_dots_keeping_the_flash_residuals_changes_no_value(monkeypatch):
    """The kept ``out`` / ``lse`` are what the kernel's second run made:
    loss and every gradient leaf are bitwise the values of the policy
    without the two names (``checkpoint_dots`` alone, which runs the
    kernel again), and equal to no remat."""
    p, loss_of = _flash_model()
    l1, g1 = jax.value_and_grad(loss_of("dots"))(p)
    l0, g0 = jax.value_and_grad(loss_of(False))(p)
    monkeypatch.setattr(
        tfm, "_remat_policy",
        lambda remat: jax.checkpoint_policies.checkpoint_dots)
    l2, g2 = jax.value_and_grad(loss_of("dots"))(p)
    assert np.asarray(l1).tobytes() == np.asarray(l2).tobytes()
    for a, b, c in zip(*(jax.tree.leaves(g) for g in (g1, g2, g0))):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()
        np.testing.assert_allclose(np.asarray(a), np.asarray(c),
                                   rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-6)


@pytest.mark.parametrize("what", ["forward", "grad"])
def test_flash_block_without_a_checkpoint_lowers_as_unnamed(what,
                                                            monkeypatch):
    """Outside ``jax.checkpoint`` a name is the identity: a block with
    the kernels lowers to the text it lowers to with the names taken out
    (ZAYA's step, decode and every caller without remat), but for the
    serial numbers jax gives its private functions."""
    import re

    from minips_tpu.ops import flash_attention as fa

    p, _ = _flash_model()
    h = jnp.ones((2, 128, 32), jnp.float32)

    def run(hh, blk):
        return tfm._block(hh, blk, 2, _flash_attn, jnp.float32)[0].sum()

    def text():
        jax.clear_caches()
        f = run if what == "forward" else jax.grad(run, argnums=(0, 1))
        return re.sub(r"@(\w+?)_\d+\b", r"@\1",
                      jax.jit(f).lower(h, p["blocks"][0]).as_text())

    named = text()
    monkeypatch.setattr(fa, "checkpoint_name", lambda x, name: x)
    assert text() == named
