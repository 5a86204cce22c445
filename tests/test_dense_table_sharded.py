"""A ``DenseTable`` on several shards keeps its leaves too: each leaf that
divides into shards of whole tiles of the chip is sharded on that
dimension, the rest lie in one flat range-sharded remainder, and the step
gathers and reduce-scatters leaf by leaf. Against the range-sharded vector
(the layout adam8 keeps, forced here by a patch of ``dense._needs_flat``):
the same steps, views, checkpoints, key-space reads and writes, and
compressed wires, on 2 and 4 of the test's CPU devices."""

from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from minips_tpu.models import olmo_hybrid
from minips_tpu.models import transformer as tfm
from minips_tpu.parallel.mesh import DATA_AXIS, SHARD_TILE, make_mesh
from minips_tpu.tables import dense
from minips_tpu.tables.dense import DenseTable, shard_dim
from minips_tpu.utils import profiling as prof

TOKENS = {"tokens": jnp.asarray(np.arange(8 * 17).reshape(8, 17) % 64,
                                jnp.int32)}
# Olmo-Hybrid at widths where its matrices shard: 128 wide, two linear
# heads of 64 key and 64 value channels, two full heads of 64
OLMO = {
    "model_type": "olmo_hybrid", "vocab_size": 128, "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 4,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "hidden_act": "silu", "attention_bias": False, "rms_norm_eps": 1e-6,
    "tie_word_embeddings": False, "max_position_embeddings": 65536,
    "layer_types": ["linear_attention", "linear_attention",
                    "linear_attention", "full_attention"],
    "linear_num_key_heads": 2, "linear_num_value_heads": 2,
    "linear_key_head_dim": 64, "linear_value_head_dim": 64,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None},
}


def _mesh(shards: int):
    return make_mesh(shards, devices=jax.devices()[:shards])


def _lm():
    return tfm.init(jax.random.PRNGKey(0), vocab=64, dim=128, heads=2,
                    depth=2, max_len=16)


def _table(layout: str, shards: int, template=None, updater="adam", **kw):
    flat = mock.patch.object(dense, "_needs_flat", lambda *a: True)
    with flat if layout == "flat" else mock.MagicMock():
        t = DenseTable(_lm() if template is None else template,
                       _mesh(shards), updater=updater, lr=1e-2, **kw)
    assert (t._leaves is None) == (layout == "flat")
    return t


def _grad_fn(params, batch):
    return tfm.grad_fn(params, batch, heads=2)


def _grad_fn_with_state(params, batch, bias):
    """A state carried beside the table, as ZAYA's balancing bias is."""
    loss, grads = tfm.grad_fn(params, batch, heads=2)
    return loss + 0.0 * bias.sum(), grads, bias + jax.lax.pmean(
        loss, DATA_AXIS)


# name -> (DenseTable kwargs, make_step kwargs, the step's state)
CASES = {
    "plain": ({}, {}, None),
    "bf16": ({}, {"compute_dtype": jnp.bfloat16}, None),
    "decay_mask": ({"updater": "adamw", "updater_kwargs": {
        "decay_mask": tfm.decay_mask(_lm()), "weight_decay": 0.1}},
        {}, None),
    "clip_norm": ({"updater_kwargs": {"clip_norm": 0.5}}, {}, None),
    "state": ({}, {"compute_dtype": jnp.bfloat16}, jnp.zeros(3)),
    "accum2": ({}, {"accum": 2}, None),
}


def _exact_jit(step):
    """``make_step(jit=False)``'s step jitted as ``make_step`` jits it,
    with every rounding the program writes kept: by default XLA may drop
    a bfloat16 round trip where a program's form lets it, and the vector
    layout's padded push (the workers' gradients raveled in bfloat16, the
    cast to float32 last) keeps rounding that the leaves' cast drops."""
    return jax.jit(step, donate_argnums=(0, 1),
                   compiler_options={"xla_allow_excess_precision": False})


def _stepped(layout: str, shards: int, case: str, steps: int = 3):
    table_kw, step_kw, state = CASES[case]
    t = _table(layout, shards, **table_kw)
    fn = _grad_fn if state is None else _grad_fn_with_state
    step = _exact_jit(t.make_step(fn, state=state, jit=False, **step_kw))
    losses = [float(t.step_inplace(step, TOKENS)) for _ in range(steps)]
    return t, losses


def _same(a, b, exact=True):
    """Equal, or equal to 1e-6 of each array's largest entry."""
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        if exact:
            np.testing.assert_array_equal(x, y)
        else:
            np.testing.assert_allclose(
                x, y, rtol=1e-6, atol=1e-6 * float(np.max(np.abs(y))))


@pytest.mark.parametrize("shards", [2, 4])
@pytest.mark.parametrize("case", list(CASES))
def test_a_sharded_leaf_step_is_the_vector_step(case, shards):
    """Three Adam steps of a small transformer whose matrices shard: the
    same losses, ``params`` and ``opt_state`` views (both moments), pulled
    tree and carried state as the range-sharded vector's, to 1e-6 (XLA:CPU
    contracts a multiply and an add into one rounding on some elements of
    one program's loops and not of another's). The clipped step is
    compared after ONE step: its norm sums the squares part by part on
    each shard, where the vector sums a contiguous range, so its scale
    differs in the last bit, and Adam at this rate carries a last-bit
    difference of the parameters into the next gradients."""
    steps = 1 if case == "clip_norm" else 3
    (leaf, lo), (flat, fo) = (_stepped("leaf", shards, case, steps),
                              _stepped("flat", shards, case, steps))
    assert any(d is not None for d in
               map(lambda s: shard_dim(s, shards), leaf._leaves.shapes))
    np.testing.assert_allclose(lo, fo, rtol=1e-6)
    assert jax.tree.structure(leaf.opt_state) == jax.tree.structure(
        flat.opt_state)
    _same(leaf.params, flat.params, exact=False)
    _same(leaf.opt_state, flat.opt_state, exact=False)
    _same(leaf.pull(), flat.pull(), exact=False)
    if CASES[case][2] is not None:
        _same(leaf.state, flat.state, exact=False)


def test_the_olmo_step_on_sharded_leaves_is_the_vector_step():
    """The hybrid's fused step as the benchmark builds it
    (``lm_example.model_dp_step``), bfloat16 worker math, four shards:
    three steps as the range-sharded vector's."""
    from minips_tpu.apps.lm_example import model_dp_step
    config = dict(OLMO, compute_dtype="bfloat16", attn="flash",
                  head_chunk=32)
    m = olmo_hybrid.from_config(config)
    p = olmo_hybrid.init(jax.random.PRNGKey(0), m)
    toks = {"tokens": jnp.asarray(
        np.random.default_rng(3).integers(0, 128, (4, 129)), jnp.int32)}
    out = {}
    for layout in ("leaf", "flat"):
        flat = mock.patch.object(dense, "_needs_flat", lambda *a: True)
        with flat if layout == "flat" else mock.MagicMock():
            _, t, step, _ = model_dp_step(config, _mesh(4), p, toks,
                                          updater="adam", lr=1e-3)
        assert (t._leaves is None) == (layout == "flat")
        out[layout] = ([float(t.step_inplace(step, toks))
                        for _ in range(3)], t)
    np.testing.assert_allclose(out["leaf"][0], out["flat"][0], rtol=1e-6)
    _same(out["leaf"][1].params, out["flat"][1].params, exact=False)
    _same(out["leaf"][1].opt_state, out["flat"][1].opt_state, exact=False)
    dims = [shard_dim(s, 4) for s in out["leaf"][1]._leaves.shapes]
    assert dims.count(0) >= 20 and None in dims


@pytest.mark.parametrize("shape, shards, dim", [
    ((3840, 11008), 4, 0), ((11008, 3840), 4, 0), ((3840, 5760), 4, 0),
    ((12544, 3840), 4, 0), ((3840, 2880), 4, 0),
    ((4, 2880), 4, None),       # one row a shard; 720 lanes: off the tile
    ((3840, 30), 4, None),      # narrower than a lane tile
    ((30,), 4, None), ((3840,), 4, None), ((), 4, None),
    ((8, 2048, 2048), 4, 0), ((2, 256, 512), 4, 1), ((16, 128), 4, None),
    ((16, 128), 2, 0), ((24, 512), 4, 1)])
def test_a_leaf_is_sharded_where_its_shards_are_whole_tiles(shape, shards,
                                                            dim):
    assert shard_dim(shape, shards) == dim


@pytest.mark.parametrize("shards", [2, 4, 8])
def test_every_shard_of_a_sharded_leaf_is_whole_tiles(shards):
    """Each sharded leaf's shards end on the chip's tile on their
    dimension; the remainder's on ``SHARD_TILE``; the counters say how
    many leaves shard and how many keys lie in the remainder."""
    prof.clear()
    t = _table("leaf", shards)
    L = t._leaves
    params, _ = t.step_state
    for x, d, spec in zip(params, L.dims, L.specs):
        assert spec == P(*[DATA_AXIS if k == d else None
                           for k in range(x.ndim)])
        (shard,) = {s.data.shape for s in x.addressable_shards}
        tile = {x.ndim - 1: 128, x.ndim - 2: 8}.get(d, 1) if x.ndim > 1 \
            else SHARD_TILE
        assert shard[d] * shards == x.shape[d] and shard[d] % tile == 0
    counters = prof.snapshot()[1]
    sharded = len(L.groups) - bool(L.rest)
    assert counters[prof.TABLE_SHARDED_LEAVES][1] == sharded >= 8
    assert counters[prof.TABLE_FLAT_KEYS][1] == L.rest_keys == sum(
        int(np.prod(L.shapes[i])) for i in L.rest)
    assert counters[prof.TABLE_PAD_KEYS][1] == L.rest_padded - L.rest_keys
    # the views hand out the range-sharded padded vector the flat table has
    assert t.params.shape == (t.padded,) == _table("flat", shards).params.shape
    np.testing.assert_array_equal(np.asarray(t.params)[t.num_keys:], 0.0)


@pytest.mark.parametrize("wrote, loads", [
    (("leaf", 4), ("flat", 4)), (("flat", 4), ("leaf", 4)),
    (("leaf", 1), ("leaf", 4)), (("leaf", 4), ("leaf", 1)),
    (("leaf", 4), ("leaf", 2))])
def test_a_checkpoint_crosses_layouts_and_shard_counts(wrote, loads):
    """``state_dict`` is the key space either way: what one layout or
    shard count writes, another loads key for key; and a step after it is
    the vector's on the loader's shard count from the same checkpoint
    (the steps of other shard counts split the batch otherwise)."""
    src, _ = _stepped(*wrote, "plain")
    dst, ref = _table(*loads), _table("flat", loads[1])
    n = src.num_keys
    for t in (dst, ref):
        t.load_state_dict(src.state_dict())
    _same(_keys(dst.params, n), _keys(src.params, n))
    _same(_keys(dst.opt_state, n), _keys(src.opt_state, n))
    for t in (dst, ref):
        t.step_inplace(t.make_step(_grad_fn), TOKENS)
    _same(_keys(dst.params, n), _keys(ref.params, n), exact=False)
    _same(_keys(dst.opt_state, n), _keys(ref.opt_state, n), exact=False)


@pytest.mark.parametrize("shards", [2, 4])
def test_the_key_space_reads_and_writes_as_the_vector_does(shards):
    """``pull_keys`` / ``push_keys`` / ``push`` on the sharded leaves; and
    the assignments ``train/ssp_spmd.py`` makes of ``params`` (base plus a
    merged delta) and of ``opt_state`` (its moments averaged)."""
    leaf, flat = _table("leaf", shards), _table("flat", shards)
    keys = np.array([0, 5, 5, 100, 70000, leaf.num_keys - 1])
    vals = jnp.array([1.0, 2.0, 3.0, -1.0, 0.25, 0.5])
    grads = jax.tree.map(jnp.ones_like, _lm())
    for t in (leaf, flat):
        t.push_keys(keys, vals)
        t.push(grads)
        base = jax.jit(jnp.copy)(t.params)
        t.params = jax.jit(lambda b: b + 0.25)(base)
        t.opt_state = jax.tree.map(
            lambda x: x * 0.5 if x.shape == (t.padded,) else x, t.opt_state)
    _same(leaf.pull_keys(keys), flat.pull_keys(keys))
    # the vector keeps what is assigned to its padding keys; the leaves
    # hold no padding there, and their view reads zeros
    n = leaf.num_keys
    _same(np.asarray(leaf.params)[:n], np.asarray(flat.params)[:n],
          exact=False)
    _same(_keys(leaf.opt_state, n), _keys(flat.opt_state, n), exact=False)
    np.testing.assert_array_equal(np.asarray(leaf.params)[n:], 0.0)
    assert leaf.params.sharding == leaf._sharding


def _keys(tree, n: int) -> list:
    """The leaves of a state as host arrays, each key-space vector cut to
    the ``n`` keys (shard counts pad it to other lengths)."""
    return [np.asarray(x)[:n] if np.ndim(x) == 1 else np.asarray(x)
            for x in jax.tree.leaves(tree)]


def _records(name, fun=None):
    spans, _ = prof.snapshot()
    return [s for s in spans if s.name == name
            and (fun is None or s.fun_name == fun)]


def test_a_sharded_view_costs_one_program_and_a_step_makes_none():
    """Two reads of a view: two ``ps.table.view`` records and at most one
    program loaded (the moments are params-shaped: the same program). The
    steps read no view and compile one program."""
    t = _table("leaf", 4)
    prof.clear()
    t.params.block_until_ready()
    t.params.block_until_ready()
    jax.block_until_ready(t.opt_state)
    assert len(_records(prof.TABLE_VIEW)) == 3
    assert len(_records(prof.COMPILE)) <= 1
    prof.clear()
    step = t.make_step(_grad_fn)
    for _ in range(2):
        t.step_inplace(step, TOKENS).block_until_ready()
    assert _records(prof.TABLE_VIEW) == []
    assert len(_records(prof.COMPILE, f"jit({prof.DENSE_STEP_FN})")) == 1


def test_a_sharded_view_waits_for_the_step_in_flight():
    """A view is as large as the state it reads and the step holds its
    temporaries until it ends, so a sharded table's view, as a one-shard
    table's, waits for the step before it ravels the state."""
    t = _table("leaf", 4)
    step = t.make_step(_grad_fn)
    t.step_inplace(step, TOKENS)
    with mock.patch.object(jax, "block_until_ready",
                           wraps=jax.block_until_ready) as waited:
        t.params.block_until_ready()
    assert waited.call_count == 1


def _linear(seed: int = 0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {"w": jax.random.normal(k1, (64, 128)) * 0.1,
            "b": jax.random.normal(k2, (128,)) * 0.1}


def _linear_batch(i: int):
    kx, ky = jax.random.split(jax.random.PRNGKey(100 + i))
    return {"x": jax.random.normal(kx, (8, 64)),
            "y": jax.random.normal(ky, (8,))}


@pytest.mark.parametrize("scale", [1.0, 4.0])
def test_the_joint_step_steps_the_sharded_leaves(scale):
    """``PSTrainStep`` takes the table's state as the table keeps it, on
    four shards too: its steps read no view, and the table comes out as
    the vector's (a linear model with a sharded matrix and a vector in
    the remainder: the compiler partitions the joint step from the
    leaves' shardings, and a deeper model's sums would run in other
    orders)."""
    from minips_tpu.train.ps_step import PSTrainStep

    def loss_fn(dp, rows, b):
        return jnp.mean(((b["x"] @ dp["w"]).sum(-1) + dp["b"].sum()
                         - b["y"]) ** 2)

    tables = {}
    for layout in ("leaf", "flat"):
        t = tables[layout] = _table(layout, 4, _linear(), updater="sgd")
        ps = PSTrainStep(loss_fn, dense=t, grad_scale=scale)
        prof.clear()
        for i in range(3):
            ps(ps.shard_batch(_linear_batch(i)))
        assert _records(prof.TABLE_VIEW) == []
    assert tables["leaf"]._leaves.dims == [0, 0]
    _same(tables["leaf"].params, tables["flat"].params, exact=False)
    _same(tables["leaf"].opt_state, tables["flat"].opt_state, exact=False)


# ------------------------------------------------- the compressed wires
@pytest.mark.parametrize("dim", [0, 1])
@pytest.mark.parametrize("comm, gather_tol, scatter_tol", [
    ("float32", 0.0, 1e-6), ("bfloat16", 1e-2, 4e-2),
    ("int8", 1.6e-2, 4e-2)])
def test_a_leaf_crosses_the_wire_within_the_vectors_tolerance(
        dim, comm, gather_tol, scatter_tol):
    """One leaf gathered and reduce-scattered on its own dimension through
    each wire, against the exact gather and sum: the bounds
    ``tests/test_quantized_comm.py`` holds the vector's to (the gather's
    error under ``tol`` times the largest entry, the sum's under ``tol``
    times the largest entry times the workers)."""
    mesh = _mesh(4)
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 512)).astype(np.float32))
    spec = P(*[DATA_AXIS if k == dim else None for k in range(2)])

    def run(fn, v):
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=spec,
                                     out_specs=spec))(v)

    def gathered(s):
        # the NEXT worker's block of what this worker gathered
        full = dense._gather(s, dim, comm, None)
        n = s.shape[dim]
        nxt = (jax.lax.axis_index(DATA_AXIS) + 1) % 4
        return jax.lax.dynamic_slice_in_dim(full, nxt * n, n, axis=dim)

    got = np.asarray(run(gathered, x))
    want = np.roll(np.asarray(x), -x.shape[dim] // 4, axis=dim)
    err = np.max(np.abs(got - want))
    assert err <= gather_tol * np.max(np.abs(np.asarray(x))), err
    # every worker pushes the whole leaf scaled by its index + 1: the sum
    # is 10 times the leaf, each shard its block
    summed = run(lambda s: dense._scatter(
        jax.lax.all_gather(s, DATA_AXIS, axis=dim, tiled=True)
        * (jax.lax.axis_index(DATA_AXIS) + 1.0), dim, comm), x)
    err = np.max(np.abs(np.asarray(summed) - 10.0 * np.asarray(x)))
    assert err <= scatter_tol * np.max(np.abs(np.asarray(x))) * 4 * 4, err


@pytest.mark.parametrize("comm", ["bfloat16", "int8"])
def test_a_sharded_leaf_table_trains_through_a_compressed_wire(comm):
    """End to end, as the vector's test does: a logistic regression whose
    weights are a sharded matrix reaches the float32 wire's loss within
    0.02 through a compressed one."""
    rng = np.random.default_rng(2)
    dim, n = 64, 512
    w_true = rng.normal(size=dim)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)
    batch = (jnp.asarray(X), jnp.asarray(y))

    def bce(params, b):
        def f(p):
            # every column moves alike: their sum is the regression's
            # weights, at the vector test's rate
            logits = (b[0] @ p["w"]).sum(axis=1)
            return jnp.mean(jnp.maximum(logits, 0) - logits * b[1]
                            + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return jax.value_and_grad(f)(params)

    losses = {}
    for mode in ("float32", comm):
        t = DenseTable({"w": jnp.zeros((dim, 128))}, _mesh(4),
                       updater="sgd", lr=0.5 / 128)
        assert t._leaves.dims == [0]
        step = t.make_step(bce, comm=mode)
        for _ in range(60):
            last = t.step_inplace(step, batch)
        losses[mode] = float(last)
    assert losses[comm] < 0.35, losses
    assert abs(losses[comm] - losses["float32"]) < 0.02, losses
