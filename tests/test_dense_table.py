"""DenseTable vs NumPy oracle on the 8-fake-device mesh (SURVEY.md §4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.parallel.mesh import make_mesh
from minips_tpu.tables.dense import DenseTable


def _template():
    # 17 keys -> eight shards of one tile (mesh.SHARD_TILE) each
    return {"w": jnp.zeros((3, 4)), "b": jnp.zeros(5)}


def test_init_pull_roundtrip(mesh8):
    t = DenseTable(_template(), mesh8)
    assert t.num_keys == 17 and t.padded == 8 * 1024
    pulled = t.pull()
    assert pulled["w"].shape == (3, 4) and pulled["b"].shape == (5,)
    np.testing.assert_allclose(np.asarray(pulled["w"]), 0.0)


def test_sharded_init_keeps_the_template_values(mesh8):
    """The padded vector is built in the sharded layout; its values are
    the raveled template's, bitwise, and the padding is zeros."""
    tmpl = {"w": jax.random.normal(jax.random.PRNGKey(2), (3, 4)),
            "b": jnp.arange(5.0)}
    t = DenseTable(tmpl, mesh8, updater="adam")
    flat = np.concatenate([np.asarray(tmpl["b"]),
                           np.asarray(tmpl["w"]).ravel()])  # sorted keys
    np.testing.assert_array_equal(np.asarray(t.params)[:17], flat)
    np.testing.assert_array_equal(np.asarray(t.params)[17:], 0.0)
    assert {s.data.shape for s in t.params.addressable_shards} == {(1024,)}


def test_push_sgd_matches_oracle(mesh8):
    t = DenseTable(_template(), mesh8, updater="sgd", lr=0.5)
    grads = {"w": jnp.ones((3, 4)) * 2.0, "b": jnp.arange(5.0)}
    t.push(grads)
    pulled = t.pull()
    np.testing.assert_allclose(np.asarray(pulled["w"]), -1.0, rtol=1e-6)
    np.testing.assert_allclose(np.asarray(pulled["b"]),
                               -0.5 * np.arange(5.0), rtol=1e-6)


def test_push_adagrad_matches_oracle(mesh8):
    lr, eps_acc = 0.1, 0.1
    t = DenseTable({"w": jnp.zeros(8)}, mesh8, updater="adagrad", lr=lr)
    g = np.linspace(1.0, 2.0, 8).astype(np.float32)
    acc = np.full(8, eps_acc)
    w = np.zeros(8)
    for _ in range(3):
        t.push({"w": jnp.asarray(g)})
        acc = acc + g * g
        w = w - lr * g / np.sqrt(acc)
    np.testing.assert_allclose(np.asarray(t.pull()["w"]), w, rtol=1e-5)


def test_pull_keys_and_push_keys(mesh8):
    t = DenseTable({"w": jnp.zeros(16)}, mesh8, updater="sgd", lr=1.0)
    keys = np.array([1, 5, 5, 9])
    vals = jnp.array([1.0, 2.0, 3.0, 4.0])
    t.push_keys(keys, vals)  # duplicate key 5 must accumulate (Add semantics)
    got = np.asarray(t.pull_keys(np.array([1, 5, 9, 0])))
    np.testing.assert_allclose(got, [-1.0, -5.0, -4.0, 0.0], rtol=1e-6)


def test_fused_step_quadratic_descent(mesh8):
    """Fused pull→grad→push→update: minimize ||params - target||^2 with the
    batch unused; every worker computes the same grad, mean-reduce keeps
    scale, loss must drop monotonically."""
    target = jnp.arange(24.0)
    t = DenseTable({"w": jnp.zeros(24)}, mesh8, updater="sgd", lr=0.2,
                   grad_reduce="mean")

    def grad_fn(params, batch):
        loss = jnp.sum((params["w"] - target) ** 2)
        return loss, {"w": 2.0 * (params["w"] - target)}

    step = t.make_step(grad_fn)
    batch = jnp.zeros((8, 1))  # sharded over workers, unused
    losses = [float(t.step_inplace(step, batch)) for _ in range(20)]
    assert losses[-1] < losses[0] * 1e-3
    np.testing.assert_allclose(np.asarray(t.pull()["w"]), np.arange(24.0),
                               atol=1e-2)


def test_fused_step_data_parallel_grads_average(mesh8):
    """Each worker sees a different batch shard; push must reduce across
    workers exactly like the oracle mean of per-shard grads."""
    t = DenseTable({"w": jnp.zeros(8)}, mesh8, updater="sgd", lr=1.0,
                   grad_reduce="mean")

    def grad_fn(params, batch):
        # grad = mean over local batch rows of (batch_row)
        g = jnp.mean(batch, axis=0)
        return jnp.sum(params["w"] * 0.0), {"w": g}

    step = t.make_step(grad_fn)
    batch = jnp.arange(16.0).reshape(16, 1) * jnp.ones((1, 8))
    t.step_inplace(step, batch)
    # oracle: mean over 8 shards of per-shard mean = global mean of column
    expect = -np.mean(np.arange(16.0)) * np.ones(8)
    np.testing.assert_allclose(np.asarray(t.pull()["w"]), expect, rtol=1e-6)


def test_state_dict_roundtrip(mesh8):
    t = DenseTable(_template(), mesh8, updater="adagrad", lr=0.1)
    t.push({"w": jnp.ones((3, 4)), "b": jnp.ones(5)})
    state = t.state_dict()
    t2 = DenseTable(_template(), mesh8, updater="adagrad", lr=0.1)
    t2.load_state_dict(state)
    np.testing.assert_allclose(np.asarray(t2.pull()["w"]),
                               np.asarray(t.pull()["w"]))
    t.push({"w": jnp.ones((3, 4)), "b": jnp.ones(5)})
    t2.push({"w": jnp.ones((3, 4)), "b": jnp.ones(5)})
    np.testing.assert_allclose(np.asarray(t2.pull()["w"]),
                               np.asarray(t.pull()["w"]))


def test_push_keys_adam_does_not_drift_untouched_keys(mesh8):
    """Regression: per-key server semantics — stateful updaters must not
    move keys that were not pushed (SURVEY.md §3.3 per-key Update)."""
    t = DenseTable({"w": jnp.zeros(16)}, mesh8, updater="adam", lr=0.1)
    t.push_keys(np.array([5]), jnp.array([1.0]))
    w5_before = float(np.asarray(t.params)[5])
    t.push_keys(np.array([7]), jnp.array([1.0]))
    assert float(np.asarray(t.params)[5]) == w5_before
    assert float(np.asarray(t.params)[7]) != 0.0


def test_step_timer_warmup_zero():
    from minips_tpu.utils.timing import StepTimer
    import time as _time
    timer = StepTimer(warmup_steps=0)
    _time.sleep(0.01)
    timer.step(100)
    assert timer.samples_per_sec > 0


def test_grad_accumulation_matches_full_batch():
    """accum=k on a mean-loss model equals one step on the full batch:
    grads average over microbatches exactly (f32 fold), so the update is
    identical up to float reassociation."""
    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)
    rng = np.random.default_rng(0)
    dim = 16
    X = rng.normal(size=(256, dim)).astype(np.float32)
    y = (X @ rng.normal(size=dim) > 0).astype(np.float32)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(y)}
    grad_fn = jax.value_and_grad(
        lambda p, b: lr_model.bce_with_logits(
            lr_model.logits_dense(p, b["x"]), b["y"]))

    losses = {}
    params = {}
    for accum in (1, 4):
        t = DenseTable(lr_model.init(dim), mesh, name=f"a{accum}",
                       updater="sgd", lr=0.5)
        step = t.make_step(grad_fn, accum=accum)
        losses[accum] = [float(t.step_inplace(step, batch))
                        for _ in range(5)]
        params[accum] = np.asarray(t.params)
    np.testing.assert_allclose(losses[1], losses[4], atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(params[1], params[4], atol=1e-5, rtol=1e-5)


def test_accum_rejects_ragged_batch():
    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)
    t = DenseTable(lr_model.init(4), mesh, name="rag", updater="sgd",
                   lr=0.1)
    grad_fn = jax.value_and_grad(
        lambda p, b: lr_model.bce_with_logits(
            lr_model.logits_dense(p, b["x"]), b["y"]))
    step = t.make_step(grad_fn, accum=3)
    batch = {"x": jnp.zeros((64, 4)), "y": jnp.zeros(64)}  # 64/8=8, 8%3!=0
    with pytest.raises(ValueError, match="divide by"):
        t.step_inplace(step, batch)


def test_lr_schedule_callable():
    """lr may be an optax schedule: step sizes follow the schedule (a
    decaying schedule shrinks successive updates of a constant grad)."""
    import optax

    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)
    sched = optax.piecewise_constant_schedule(1.0, {2: 0.1})
    t = DenseTable(lr_model.init(4), mesh, name="sch", updater="sgd",
                   lr=sched)
    grad_fn = lambda p, b: (jnp.zeros(()),  # noqa: E731
                            jax.tree.map(jnp.ones_like, p))
    step = t.make_step(grad_fn)
    batch = {"x": jnp.zeros((8, 4))}
    n = t.num_keys  # the padded tail gets zero grads, so slice it off
    before = np.asarray(t.params)[:n]
    t.step_inplace(step, batch)         # lr 1.0
    d1 = before - np.asarray(t.params)[:n]
    t.step_inplace(step, batch)         # lr 1.0
    mid = np.asarray(t.params)[:n]
    t.step_inplace(step, batch)         # lr 0.1 after boundary
    d3 = mid - np.asarray(t.params)[:n]
    np.testing.assert_allclose(d1, 1.0, atol=1e-6)
    np.testing.assert_allclose(d3, 0.1, atol=1e-6)


def test_accum_sum_semantics_not_rescaled():
    """grad_reduce='sum' with a summed loss: accum must not divide the
    accumulated grads — microbatch sums already add to the batch sum."""
    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)

    def grad_fn(p, b):  # summed loss -> summed grads
        def loss(p_):
            logits = lr_model.logits_dense(p_, b["x"])
            return jnp.sum((logits - b["y"]) ** 2)
        return jax.value_and_grad(loss)(p)

    rng = np.random.default_rng(1)
    batch = {"x": jnp.asarray(rng.normal(size=(64, 4)), jnp.float32),
             "y": jnp.asarray(rng.normal(size=64), jnp.float32)}
    outs = {}
    for accum in (1, 4):
        t = DenseTable(lr_model.init(4), mesh, name=f"s{accum}",
                       updater="sgd", lr=0.01, grad_reduce="sum")
        step = t.make_step(grad_fn, accum=accum)
        t.step_inplace(step, batch)
        outs[accum] = np.asarray(t.params)[:t.num_keys]
    np.testing.assert_allclose(outs[1], outs[4], atol=1e-5, rtol=1e-5)


def test_accum_with_replicated_batch_spec():
    """accum under batch_spec=P() (replicated batch): the scan carries
    must still adopt the params' varying axes — this traced wrong before."""
    from jax.sharding import PartitionSpec as P

    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)
    t = DenseTable(lr_model.init(4), mesh, name="rep", updater="sgd",
                   lr=0.1)
    grad_fn = jax.value_and_grad(
        lambda p, b: lr_model.bce_with_logits(
            lr_model.logits_dense(p, b["x"]), b["y"]))
    step = t.make_step(grad_fn, batch_spec=P(), accum=4)
    batch = {"x": jnp.zeros((16, 4)), "y": jnp.zeros(16)}
    loss = t.step_inplace(step, batch)
    assert jnp.isfinite(loss)


def test_make_step_bfloat16_compute(mesh8):
    """compute_dtype=bfloat16: worker math in bf16, f32 master weights.
    The bf16 trajectory converges like f32 (loose tolerance), params stay
    float32, and grad_fn provably sees bf16 inputs."""
    from minips_tpu.models import lr as lr_model

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=16).astype(np.float32)
    X = rng.normal(size=(512, 16)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(y)}

    seen_dtypes = []

    def grad_fn(params, b):
        seen_dtypes.append((params["w"].dtype, b["x"].dtype))
        return lr_model.grad_fn_dense(params, b)

    losses = {}
    for label, cd in [("f32", None), ("bf16", jnp.bfloat16)]:
        t = DenseTable(lr_model.init(16), mesh8, updater="adagrad", lr=0.5)
        step = t.make_step(grad_fn, compute_dtype=cd)
        ls = [float(t.step_inplace(step, batch)) for _ in range(30)]
        losses[label] = ls
        assert t.params.dtype == jnp.float32  # master weights untouched
    # tracing recorded the compute dtype grad_fn actually saw
    assert (jnp.float32, jnp.float32) in seen_dtypes
    assert (jnp.bfloat16, jnp.bfloat16) in seen_dtypes
    assert losses["bf16"][-1] < losses["bf16"][0] * 0.5
    assert abs(losses["bf16"][-1] - losses["f32"][-1]) < 0.1


def test_make_step_bfloat16_composes_with_accum_and_comm(mesh8):
    from minips_tpu.models import lr as lr_model

    rng = np.random.default_rng(1)
    X = rng.normal(size=(64, 8)).astype(np.float32)
    y = rng.integers(0, 2, size=64).astype(np.float32)
    batch = {"x": jnp.asarray(X), "y": jnp.asarray(y)}
    t = DenseTable(lr_model.init(8), mesh8, updater="sgd", lr=0.3)
    step = t.make_step(lr_model.grad_fn_dense, compute_dtype=jnp.bfloat16,
                       accum=2, comm="bfloat16")
    ls = [float(t.step_inplace(step, batch)) for _ in range(20)]
    assert np.isfinite(ls).all()
    assert ls[-1] < ls[0]


def test_clip_norm_bounds_update():
    """clip_norm: a huge constant gradient is clipped to the given global
    norm before SGD applies it — the update magnitude equals lr * clip /
    ||g|| * g elementwise."""
    from minips_tpu.models import lr as lr_model

    mesh = make_mesh(8)
    t = DenseTable(lr_model.init(4), mesh, name="clip", updater="sgd",
                   lr=1.0, updater_kwargs={"clip_norm": 1.0})
    grad_fn = lambda p, b: (jnp.zeros(()),  # noqa: E731
                            jax.tree.map(
                                lambda x: 100.0 * jnp.ones_like(x), p))
    step = t.make_step(grad_fn)
    n = t.num_keys
    before = np.asarray(t.params)[:n]
    t.step_inplace(step, {"x": jnp.zeros((8, 4))})
    delta = before - np.asarray(t.params)[:n]
    # clipped GLOBAL norm (cross-shard psum, not per-owner-shard) = 1.0
    # -> each of n entries moves by 1/sqrt(n)
    np.testing.assert_allclose(delta, 1.0 / np.sqrt(n), rtol=1e-5)


def test_adamw_masked_decay_only_decays_masked_rows():
    """adamw + decay_mask: with ZERO gradients, masked entries shrink by
    wd * lr per step while unmasked entries (the 'LN/bias' rows) stay
    exactly put — the decoupled decay never leaks across the mask."""
    mesh = make_mesh(8)
    template = {"w": jnp.ones((4, 4)), "b": jnp.ones(4)}
    mask = {"w": jnp.ones((4, 4)), "b": jnp.zeros(4)}
    t = DenseTable(template, mesh, name="adamw", updater="adamw", lr=0.5,
                   updater_kwargs={"weight_decay": 0.1,
                                   "decay_mask": mask})
    grad_fn = lambda p, b: (jnp.zeros(()),  # noqa: E731
                            jax.tree.map(jnp.zeros_like, p))
    step = t.make_step(grad_fn)
    t.step_inplace(step, {"x": jnp.zeros((8, 2))})
    out = t.pull()
    # w: 1 - lr * wd * 1 = 0.95;  b: untouched
    np.testing.assert_allclose(np.asarray(out["w"]), 0.95, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(out["b"]), 1.0, rtol=1e-6)


def test_adamw_decay_mask_shape_mismatch_raises():
    mesh = make_mesh(8)
    template = {"w": jnp.ones((4, 4))}
    with pytest.raises(ValueError, match="params-shaped"):
        DenseTable(template, mesh, name="bad", updater="adamw",
                   updater_kwargs={"decay_mask": {"w": jnp.ones(3)}})


def test_transformer_decay_mask_rule():
    """decay_mask: 1 on matrices (ndim >= 2), 0 on LN gains/biases."""
    from minips_tpu.models import transformer as tfm

    p = tfm.init(jax.random.PRNGKey(0), vocab=16, dim=32, heads=4,
                 depth=1)
    m = tfm.decay_mask(p)
    assert float(m["blocks"][0]["qkv"][0, 0, 0]) == 1.0
    assert float(m["tok_emb"][0, 0]) == 1.0
    assert float(m["ln_f"]["g"][0]) == 0.0
    assert float(m["blocks"][0]["ln1"]["b"][0]) == 0.0


def test_clip_norm_applies_on_push_path_too():
    """clip_norm must never be a silent no-op: the raw push() path clips
    by the same cross-shard global norm as the fused step."""
    mesh = make_mesh(8)
    t = DenseTable({"w": jnp.zeros(8)}, mesh, name="clip2", updater="sgd",
                   lr=1.0, updater_kwargs={"clip_norm": 1.0})
    t.push({"w": 100.0 * jnp.ones(8)})
    delta = -np.asarray(t.pull()["w"])
    np.testing.assert_allclose(delta, 1.0 / np.sqrt(8), rtol=1e-5)


# --------------------------------------------- low-precision adam states
def _lr_batches(n, d=127, bsz=256):
    from minips_tpu.models import lr as lr_model  # noqa: F401 (template)

    rng = np.random.default_rng(0)
    w_true = rng.normal(size=d)
    data = np.random.default_rng(1)
    out = []
    for _ in range(n):
        x = data.normal(size=(bsz, d)).astype(np.float32)
        out.append({"x": x, "y": (x @ w_true > 0).astype(np.float32)})
    return out


def _adam_run(updater, kw, batches):
    from minips_tpu.models import lr as lr_model

    t = DenseTable(lr_model.init(127), make_mesh(8), name=f"t_{updater}",
                   updater=updater, lr=0.01, updater_kwargs=kw)
    step = t.make_step(lr_model.grad_fn_dense)
    losses = [float(t.step_inplace(step, b)) for b in batches]
    st = [x for x in jax.tree.leaves(t.opt_state) if hasattr(x, "dtype")]
    return losses, sum(x.size * x.dtype.itemsize for x in st), t


def test_adam_bf16_matches_adam_trajectory(mesh8):
    """VERDICT r3 next #4: the frontier is HBM-bound by f32 adam state.
    bf16 moments must HALVE moment bytes while staying on adam's loss
    trajectory (only moment STORAGE loses mantissa; math is f32)."""
    bs = _lr_batches(40)
    ref, ref_bytes, _ = _adam_run("adam", {}, bs)
    lowp, lowp_bytes, t = _adam_run("adam_bf16", {}, bs)
    # moments halve; the int32 step count rides along in both
    assert lowp_bytes <= ref_bytes // 2 + 8
    np.testing.assert_allclose(lowp, ref, atol=2e-3)
    assert lowp[-1] < lowp[0] * 0.6
    # moments really are stored bf16 and sharded like the params
    vecs = [x for x in jax.tree.leaves(t.opt_state)
            if getattr(x, "ndim", 0) == 1 and x.shape[0] == t.padded]
    assert vecs and all(x.dtype == jnp.bfloat16 for x in vecs)


def test_adam8_blockwise_matches_adam_trajectory(mesh8):
    """int8 blockwise moments: ~4.03 bytes/param of state (codes + one
    f32 scale per block) vs adam's 8, same trajectory within quantization
    tolerance; the per-block scale leaves shard over the data axis
    alongside the codes (dense.py sub-padded sharding rule)."""
    bs = _lr_batches(40)
    ref, ref_bytes, _ = _adam_run("adam", {}, bs)
    q, q_bytes, t = _adam_run("adam8", {"block": 8}, bs)
    assert q_bytes < ref_bytes * 0.55   # 2*(1 + 4/8) + 4 ≈ 3/8 of 8B here
    np.testing.assert_allclose(q, ref, atol=5e-3)
    assert q[-1] < q[0] * 0.6
    from jax.sharding import PartitionSpec as P

    scales = [x for x in jax.tree.leaves(t.opt_state)
              if getattr(x, "ndim", 0) == 1 and x.dtype == jnp.float32
              and 1 < x.shape[0] < t.padded]
    assert scales and all(
        x.sharding.spec == P("data") for x in scales)


def test_adam8_odd_size_aligns_padding(mesh8):
    """A param count that doesn't divide into whole blocks per shard must
    ALIGN the range padding (RangePartitioner align=block), not error and
    not mis-slice: 65 keys over 8 shards with block 8 pads to whole blocks
    AND whole tiles a shard (lcm(8, 1,024) keys each), trains, and padding
    stays zero."""
    from minips_tpu.models import lr as lr_model

    t = DenseTable(lr_model.init(64), make_mesh(8), name="odd8",
                   updater="adam8", lr=0.05, updater_kwargs={"block": 8})
    assert t.padded == 8192 and t.partitioner.shard_size == 1024
    bs = _lr_batches(10, d=64)
    step = t.make_step(lr_model.grad_fn_dense)
    losses = [float(t.step_inplace(step, b)) for b in bs]
    assert losses[-1] < losses[0]
    flat = np.asarray(t.params)
    assert (flat[t.num_keys:] == 0).all()  # padding never moved


def _adam8_state(t):
    from minips_tpu.tables.updaters import Adam8bitState

    leaves = jax.tree.leaves(
        t.opt_state, is_leaf=lambda x: isinstance(x, Adam8bitState))
    st = [x for x in leaves if isinstance(x, Adam8bitState)]
    assert len(st) == 1
    return st[0]


def test_push_keys_adam8_blockwise_masked_restore(mesh8):
    """ADVICE r4 medium: the masked (per-key) push path must restore
    adam8's quantized moments at BLOCK granularity. An elementwise
    where() restores the CODES but leaves them paired with freshly
    recomputed SCALES, silently moving untouched keys' moments. Contract:
    a block with no touched key is restored bit-identically (codes AND
    scale); a block mixing touched and untouched keys is merged in f32
    and requantized, so untouched keys there move by at most one codebook
    roundtrip (~7% relative), never a foreign-absmax rescale or a decay
    step."""
    from minips_tpu.tables.updaters import _dequantize_block

    # 64 keys, block 8, 8 shards -> shard_size 8 = exactly one block each
    t = DenseTable({"w": jnp.zeros(64)}, mesh8, updater="adam8", lr=0.1,
                   updater_kwargs={"block": 8})
    t.push_keys(np.array([5]), jnp.array([1.0]))
    st = _adam8_state(t)
    mu_q0, mu_s0 = np.asarray(st.mu_q), np.asarray(st.mu_s)
    nu_q0, nu_s0 = np.asarray(st.nu_q), np.asarray(st.nu_s)
    m0 = np.asarray(_dequantize_block(st.mu_q, st.mu_s, 8))
    w5 = float(np.asarray(t.params)[5])
    assert m0[5] != 0.0  # the moment we are protecting is real

    # key 60 lives in a different block: block 0 must restore EXACTLY
    t.push_keys(np.array([60]), jnp.array([1.0]))
    st = _adam8_state(t)
    np.testing.assert_array_equal(np.asarray(st.mu_q)[:8], mu_q0[:8])
    np.testing.assert_array_equal(np.asarray(st.nu_q)[:8], nu_q0[:8])
    assert float(np.asarray(st.mu_s)[0]) == float(mu_s0[0])
    assert float(np.asarray(st.nu_s)[0]) == float(nu_s0[0])
    assert float(np.asarray(t.params)[5]) == w5

    # key 7 shares block 0 with key 5: mixed block — key 5's params stay
    # put and its moment takes at most one requantize roundtrip
    t.push_keys(np.array([7]), jnp.array([1.0]))
    st = _adam8_state(t)
    m2 = np.asarray(_dequantize_block(st.mu_q, st.mu_s, 8))
    assert abs(m2[5] - m0[5]) <= 0.08 * abs(m0[5]) + 1e-12, (m2[5], m0[5])
    assert float(np.asarray(t.params)[5]) == w5
    assert float(np.asarray(t.params)[7]) != 0.0


def test_custom_tx_adam8_scales_shard_and_misalign_raises(mesh8):
    """The per-block-scale sharding tag keys on the Adam8bitState TYPE in
    the opt state, so a user-supplied quantized transform via the tx
    escape hatch gets the same treatment as updater='adam8'; a block that
    does not divide the shard size must refuse loudly at construction,
    not mis-slice inside shard_map."""
    from jax.sharding import PartitionSpec as P

    from minips_tpu.tables.updaters import make_updater

    t = DenseTable({"w": jnp.zeros(64)}, mesh8, name="ctx8",
                   tx=make_updater("adam8", 0.01, block=8))
    scales = [x for x in jax.tree.leaves(t.opt_state)
              if getattr(x, "ndim", 0) == 1 and x.dtype == jnp.float32
              and 1 < x.shape[0] < t.padded]
    assert scales and all(x.sharding.spec == P("data") for x in scales)
    t.push({"w": jnp.ones(64)})
    assert float(np.abs(np.asarray(t.pull()["w"])).sum()) > 0
    # 64 keys / 8 shards = a tile of 1,024 per shard; block 2,048 divides
    # padded (adam8's own init check passes) but not the shard — must
    # refuse loudly
    with pytest.raises(ValueError, match="whole blocks"):
        DenseTable({"w": jnp.zeros(64)}, mesh8, name="ctx2048",
                   tx=make_updater("adam8", 0.01, block=2048))


def test_quantize_roundtrip_log_codebook_relative_error():
    """Blockwise dynamic 8-bit: the LOG codebook keeps ~6 decades of
    RELATIVE precision inside a block, so roundtrip error is bounded
    per element at ~6% of the value (plus the codebook floor for values
    ~1e6x below the block absmax) — not at scale/2 as linear absmax
    codes would be."""
    from minips_tpu.tables.updaters import (_dequantize_block,
                                            _quantize_block)

    for signed in (True, False):
        x = np.abs(np.random.default_rng(3).normal(size=512)) \
            if not signed else np.random.default_rng(3).normal(size=512)
        # heterogeneous magnitudes inside each block: spread 4 decades
        x = (x * 10.0 ** np.random.default_rng(4).uniform(
            -4, 0, size=512)).astype(np.float32)
        xj = jnp.asarray(x)
        q, s = _quantize_block(xj, 64, signed=signed)
        back = np.asarray(_dequantize_block(q, s, 64, signed=signed))
        scale = np.repeat(np.asarray(s), 64)
        rel_ok = np.abs(back - x) <= 0.07 * np.abs(x) + 1e-12
        floor_ok = np.abs(x) <= 2e-6 * scale  # below the codebook floor
        assert (rel_ok | floor_ok).all(), (
            np.abs(back - x) / np.maximum(np.abs(x), 1e-30)).max()


def test_adam8_outlier_block_does_not_spike_updates(mesh8):
    """r4 review finding: with LINEAR absmax codes, a small-|g| element
    sharing a block with a large-|g| outlier had its second moment
    quantized to zero and its update spiked ~45x vs f32 adam. The log
    codebook must keep every element's update within a tight factor of
    f32 adam in exactly that scenario."""
    import optax

    from minips_tpu.tables.updaters import make_updater

    n, block = 64, 64
    g_scale = np.ones(n, np.float32) * 0.01
    g_scale[7] = 10.0   # one outlier dominates the block absmax
    g_scale[9] = 1e-3   # ~7 decades of v below the outlier: sub-floor
    # (exercises the round-UP-to-floor-code rule — a positive v stored
    # as exactly zero would collapse the denominator and spike ~30x)
    rng = np.random.default_rng(5)
    tx8 = make_updater("adam8", 0.001, block=block)
    txf = make_updater("adam", 0.001)
    p = jnp.zeros(n)
    s8, sf = tx8.init(p), txf.init(p)
    peak8 = peakf = 0.0
    err_num = err_den = 0.0
    for i in range(200):
        g = jnp.asarray(rng.normal(size=n).astype(np.float32) * g_scale)
        u8, s8 = tx8.update(g, s8, p)
        uf, sf = txf.update(g, sf, p)
        if i > 20:  # steady state
            a8, af = np.asarray(u8), np.asarray(uf)
            peak8 = max(peak8, float(np.abs(a8).max()))
            peakf = max(peakf, float(np.abs(af).max()))
            err_num += float(np.square(a8 - af).sum())
            err_den += float(np.square(af).sum())
    # the spike signature: quantized updates exceeding adam's own peak
    # magnitude by a large factor (elementwise per-step RATIOS are not
    # meaningful — f32 updates cross zero). Log codes: peak8/peakf ~1.03.
    assert peak8 < 2.0 * peakf, (peak8, peakf)
    # and the whole update stream stays close in RMS
    assert err_num / err_den < 0.05, err_num / err_den
