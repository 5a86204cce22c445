"""A sharded ``DenseTable`` ends its shards on the chip's tile
(``mesh.SHARD_TILE``): its length, that the padding changes no value the
table hands out (against a table on one shard, which pads nothing), that
padding keys stay zero, and checkpoints across paddings. What the chip's
compiler builds for such shards is ``tests/test_flash_compile_tpu.py``'s."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.parallel.mesh import SHARD_TILE, make_mesh
from minips_tpu.tables.dense import DenseTable
from minips_tpu.utils import profiling as prof

N = 4 * 1021        # divides by four: until PR 36 four shards of 1,021


def _template(seed: int = 0):
    w = jax.random.normal(jax.random.PRNGKey(seed), (N - 5,)) * 0.1
    return {"w": w, "b": jnp.arange(5.0) * 0.1}


def _mesh(shards: int):
    return make_mesh(shards, devices=jax.devices()[:shards])


def _batch(i: int, rows: int = 8):
    kx, ky = jax.random.split(jax.random.PRNGKey(100 + i))
    return {"x": jax.random.normal(kx, (rows, N - 5)),
            "y": jax.random.normal(ky, (rows,))}


def _grad_fn(p, b):
    def loss(p):
        return jnp.mean((b["x"] @ p["w"] + jnp.sum(p["b"]) - b["y"]) ** 2)
    return jax.value_and_grad(loss)(p)


@pytest.mark.parametrize("shards, updater, kw, padded", [
    (4, "adam", {}, 4096),
    (4, "adam8", {"block": 256}, 4096),           # lcm(256, 1024) = 1024
    (4, "adam8", {"block": 384}, 4 * 3072),       # lcm(384, 1024) = 3072
    (1, "adam", {}, N),                           # nothing to gather
    (1, "adam8", {"block": 256}, 4096),           # adam8's blocks alone
], ids=["adam-x4", "adam8-256-x4", "adam8-384-x4", "adam-x1", "adam8-x1"])
def test_a_shard_is_whole_tiles_and_whole_blocks(shards, updater, kw,
                                                 padded):
    prof.clear()
    t = DenseTable(_template(), _mesh(shards), updater=updater, lr=0.01,
                   updater_kwargs=kw)
    assert SHARD_TILE == 1024
    assert (t.num_keys, t.padded) == (N, padded)
    assert t._shard_shape == (padded // shards,)
    assert {s.data.shape for s in t.params.addressable_shards} == {
        t._shard_shape}
    assert prof.snapshot()[1][prof.TABLE_PAD_KEYS][1] == padded - N
    np.testing.assert_array_equal(np.asarray(t.params)[N:], 0.0)


def _steps(t, **kw):
    step = t.make_step(_grad_fn, **kw)
    for i in range(3):
        t.step_inplace(step, _batch(i))
    return t


def _push_keys(t):
    t.push_keys(np.array([0, 5, 5, 1020, 1021, N - 1]),
                jnp.array([1.0, 2.0, 3.0, -1.0, 0.5, 4.0]))
    t.push_keys(np.array([1021, 7]), jnp.array([1.0, 1.0]))
    return t


def _push(t):
    for i in range(2):
        t.push(_grad_fn(t.pull(), _batch(i))[1])
    return t


_DECAY = {"decay_mask": {"w": jnp.ones(N - 5), "b": jnp.zeros(5)},
          "weight_decay": 0.1}

# name -> (updater, updater_kwargs, what is done to the table)
CASES = {
    "pull": ("sgd", {}, lambda t: t),
    "make_step-adam": ("adam", {}, _steps),
    "make_step-bf16-worker-math": (
        "sgd", {}, lambda t: _steps(t, compute_dtype=jnp.bfloat16)),
    "make_step-accum": ("adam", {}, lambda t: _steps(t, accum=2)),
    "make_step-sgd-sum": ("sgd", {}, _steps),
    "push_keys": ("adam", {}, _push_keys),
    "push": ("adagrad", {}, _push),
    "decay_mask": ("adamw", _DECAY, _steps),
    "clip_norm": ("sgd", {"clip_norm": 0.5}, _steps),
    "clip_norm-push": ("sgd", {"clip_norm": 0.5}, _push),
}


@pytest.mark.parametrize("case", list(CASES))
def test_the_padding_changes_no_value(case):
    """Four shards of 1,024 (12 padding keys) against one shard of 4,084
    (none): the same parameters after the same pushes, the padding keys
    zero throughout."""
    updater, kw, do = CASES[case]
    got, want = (do(DenseTable(_template(), _mesh(shards), updater=updater,
                               lr=0.05, updater_kwargs=dict(kw)))
                 for shards in (4, 1))
    assert (got.padded, want.padded) == (4096, N)
    # four workers sum their shares of the batch in another order than
    # one does; in bfloat16 each rounds its own share
    # (a product over 2 rows and over 8: the band is bfloat16's, and what
    # it holds is that the padded gradient is cast up, whole and in place)
    rtol, atol = (1e-2, 3e-2) if "bf16" in case else (2e-4, 1e-6)
    for a, b in zip(jax.tree.leaves(got.pull()),
                    jax.tree.leaves(want.pull())):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=rtol,
                                   atol=atol)
    np.testing.assert_array_equal(np.asarray(got.params)[N:], 0.0)
    if updater.startswith("adam"):          # moments start at zero
        for leaf in jax.tree.leaves(got.opt_state):
            if getattr(leaf, "shape", ()) == (got.padded,):
                np.testing.assert_array_equal(np.asarray(leaf)[N:], 0.0)


def _moments(t):
    """The optimizer's state, key by key (adam8's decoded)."""
    from minips_tpu.tables.updaters import Adam8bitState, _dequantize_block

    out = []
    for st in jax.tree.leaves(
            t.opt_state, is_leaf=lambda x: isinstance(x, Adam8bitState)):
        if isinstance(st, Adam8bitState):
            block = st.mu_q.shape[0] // st.mu_s.shape[0]
            out += [_dequantize_block(st.mu_q, st.mu_s, block),
                    _dequantize_block(st.nu_q, st.nu_s, block, signed=False)]
        elif getattr(st, "shape", ()) == (t.padded,):
            out.append(st)
    return [np.asarray(x, np.float32)[:N] for x in out]


@pytest.mark.parametrize("updater, kw", [
    ("adam", {}), ("adam_bf16", {}), ("adam8", {"block": 384})])
@pytest.mark.parametrize("wrote, loads", [(1, 4), (4, 1), (4, 8)])
def test_a_checkpoint_loads_at_another_padding(updater, kw, wrote, loads):
    """A table on one shard pads as a table on four did until PR 36 (N
    divides by four): its checkpoint, params and shard-shaped moments,
    loads on four shards of 1,024, the other way round and on eight
    shards, and training goes on as if nothing had been moved."""
    def table(shards, seed):
        return DenseTable(_template(seed), _mesh(shards), updater=updater,
                          lr=0.01, updater_kwargs=dict(kw))

    src = _steps(table(wrote, 0))
    dst = table(loads, 1)
    assert src.padded != dst.padded
    dst.load_state_dict(src.state_dict())
    assert dst.params.shape == (dst.padded,)
    assert dst.params.sharding == dst._sharding
    np.testing.assert_array_equal(np.asarray(dst.params)[:N],
                                  np.asarray(src.params)[:N])
    np.testing.assert_array_equal(np.asarray(dst.params)[N:], 0.0)
    for a, b in zip(_moments(dst), _moments(src), strict=True):
        np.testing.assert_array_equal(a, b)
    for t in (src, dst):
        t.step_inplace(t.make_step(_grad_fn), _batch(7))
    np.testing.assert_allclose(np.asarray(dst.params)[:N],
                               np.asarray(src.params)[:N], atol=1e-6)
    np.testing.assert_array_equal(np.asarray(dst.params)[N:], 0.0)


def test_a_checkpoint_that_is_too_short_is_refused():
    t = DenseTable(_template(), _mesh(4), updater="sgd")
    with pytest.raises(ValueError, match="does not cover"):
        t.load_state_dict({"params": np.zeros(N - 1, np.float32)})
