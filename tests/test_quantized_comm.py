"""Quantized PS collectives (EQuARX-style, PAPERS.md): wire-format
compression of pull/push must keep f32 semantics to within quantization
error, and training through it must still converge."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.ops.quantized_comm import (
    quantized_all_gather,
    quantized_psum_scatter,
)
from minips_tpu.tables.dense import DenseTable


def _run(mesh, fn, *xs):
    from jax import shard_map

    return jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("data"),) * len(xs),
        out_specs=P("data")))(*xs)


@pytest.fixture(scope="module")
def vec():
    rng = np.random.default_rng(0)
    return jnp.asarray(rng.normal(size=512).astype(np.float32))


def test_all_gather_f32_exact(mesh8, vec):
    out = _run(mesh8, lambda x: quantized_all_gather(x, "data"), vec)
    # tiled all-gather of the full vector replicates it: each device's
    # output rows are the whole vector -> global result is 8 copies
    np.testing.assert_array_equal(np.asarray(out).reshape(8, -1)[0],
                                  np.asarray(vec))


@pytest.mark.parametrize("comm,tol", [("bfloat16", 1e-2), ("int8", 1.6e-2)])
def test_all_gather_quantized_error_bounded(mesh8, vec, comm, tol):
    out = _run(mesh8,
               lambda x: quantized_all_gather(x, "data", comm), vec)
    got = np.asarray(out).reshape(8, -1)[0]
    err = np.max(np.abs(got - np.asarray(vec)))
    # int8 bound: scale/2 = max|shard|/254 per element
    assert err <= tol * np.max(np.abs(np.asarray(vec))), err


@pytest.mark.parametrize("comm,tol", [("float32", 1e-6),
                                      ("bfloat16", 4e-2), ("int8", 4e-2)])
def test_psum_scatter_matches_sum(mesh8, comm, tol):
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.normal(size=512).astype(np.float32))

    out = _run(mesh8,
               lambda x: quantized_psum_scatter(x, "data", comm), g)
    # each device contributed its local [512/8=64] view reshaped [8, 8]:
    # global semantic: sum over devices of device-local chunk row j -> dev j
    locals_ = np.asarray(g).reshape(8, 64)           # per-device locals
    want = np.zeros((8, 8), np.float32)              # [dev, chunk]
    for dev in range(8):
        want[dev] = locals_.reshape(8, 8, 8)[:, dev, :].sum(axis=0)
    got = np.asarray(out).reshape(8, 8)
    scale = np.max(np.abs(locals_))
    np.testing.assert_allclose(got, want, atol=tol * scale * 8)


@pytest.mark.parametrize("comm", ["bfloat16", "int8"])
def test_lr_converges_with_quantized_comm(mesh8, comm):
    """End-to-end: LR through a DenseTable with compressed collectives
    reaches (near) the f32 loss — the EQuARX quality claim."""
    rng = np.random.default_rng(2)
    dim, n = 64, 512
    w_true = rng.normal(size=dim)
    X = rng.normal(size=(n, dim)).astype(np.float32)
    y = (X @ w_true > 0).astype(np.float32)
    batch = (jnp.asarray(X), jnp.asarray(y))

    def bce(params, b):
        Xb, yb = b
        logits = Xb @ params["w"]
        l = jnp.mean(jnp.maximum(logits, 0) - logits * yb
                     + jnp.log1p(jnp.exp(-jnp.abs(logits))))
        return l, jax.grad(lambda p: jnp.mean(
            jnp.maximum(Xb @ p["w"], 0) - (Xb @ p["w"]) * yb
            + jnp.log1p(jnp.exp(-jnp.abs(Xb @ p["w"])))))(params)

    losses = {}
    for mode in ("float32", comm):
        tbl = DenseTable({"w": jnp.zeros(dim)}, mesh8, updater="sgd", lr=0.5)
        step = tbl.make_step(bce, comm=mode)
        for _ in range(60):
            last = tbl.step_inplace(step, batch)
        losses[mode] = float(last)
    assert losses[comm] < 0.35, losses          # well below log(2) chance
    assert abs(losses[comm] - losses["float32"]) < 0.02, losses


def test_invalid_comm_rejected(mesh8):
    tbl = DenseTable({"w": jnp.zeros(8)}, mesh8)
    with pytest.raises(ValueError):
        tbl.make_step(lambda p, b: (0.0, p), comm="int4")


def test_int8_block_scales_preserve_small_tensors(mesh8):
    """A raveled model mixes magnitudes (layernorm ~1.0 next to weights
    ~0.005). Per-BLOCK scales must keep the small ones alive — a single
    per-shard scale would flush them to exactly zero."""
    rng = np.random.default_rng(3)
    big = np.ones(1024, np.float32)                        # ln-like
    small = (rng.normal(size=1024) * 0.005).astype(np.float32)
    x = jnp.asarray(np.concatenate([big, small]))

    out = _run(mesh8, lambda v: quantized_all_gather(v, "data", "int8"), x)
    got_small = np.asarray(out).reshape(8, -1)[0][1024:]
    # small values survive with blockwise relative error, not zeroed
    assert np.max(np.abs(got_small)) > 0.001
    rel = np.max(np.abs(got_small - small)) / np.max(np.abs(small))
    assert rel < 0.02, rel


def test_bf16_push_accumulates_in_f32(mesh8):
    """The compressed push must sum contributions in f32: N-1 tiny grads
    plus one large one keep the tiny ones' total, which a bf16 running sum
    would drop."""
    # device 0 contributes 1.0, devices 1..7 contribute 2**-10 each to the
    # same chunk element; bf16 running sum after the big term loses them
    locals_ = np.zeros((8, 64), np.float32)
    locals_[0, :] = 1.0
    locals_[1:, :] = 2.0 ** -10
    g = jnp.asarray(locals_.reshape(-1))
    out = _run(mesh8,
               lambda v: quantized_psum_scatter(v, "data", "bfloat16"), g)
    got = np.asarray(out)
    want = 1.0 + 7 * 2.0 ** -10
    # each bf16-cast term is exact here (powers of two), so an f32
    # accumulation is exact; a bf16 accumulation would return ~1.0039
    np.testing.assert_allclose(got, want, rtol=1e-6)


# ----------------------------------------- sorted-run key delta codec
def test_key_delta_roundtrip_and_narrowest_width():
    from minips_tpu.ops.quantized_comm import (decode_key_deltas,
                                               delta_stream_bytes,
                                               encode_key_deltas)

    rng = np.random.default_rng(5)
    for top, want_dw in ((200, 1), (60_000, 2), (1 << 20, 4)):
        keys = np.unique(rng.integers(0, top, size=300).astype(np.int64))
        # force at least one maximal gap so the width claim is tight
        keys = np.unique(np.concatenate([keys, [0, top]]))
        dw, stream = encode_key_deltas(keys)
        assert dw <= want_dw  # never wider than the gap bound needs
        assert len(stream) == delta_stream_bytes(keys.size, dw)
        got = decode_key_deltas(stream, keys.size, dw)
        np.testing.assert_array_equal(got, keys)
    # singleton and empty edges
    dw, s1 = encode_key_deltas(np.array([7], np.int64))
    assert decode_key_deltas(s1, 1, dw)[0] == 7
    dw, s0 = encode_key_deltas(np.empty(0, np.int64))
    assert decode_key_deltas(s0, 0, dw).size == 0
    # unsorted/duplicate input is the caller's bug, loudly
    with pytest.raises(ValueError):
        encode_key_deltas(np.array([3, 3, 5], np.int64))
    with pytest.raises(ValueError):
        encode_key_deltas(np.array([5, 3], np.int64))


def test_key_delta_beats_plain_width_on_hot_runs():
    """The codec's reason to exist: a near-contiguous hot set pays ~1
    byte per key where the plain narrowest stream pays the key-space
    width (2 at 64Ki rows, 4 beyond)."""
    from minips_tpu.ops.quantized_comm import (delta_stream_bytes,
                                               encode_key_deltas)

    keys = np.arange(1000, 1512, dtype=np.int64)  # a contiguous run
    dw, stream = encode_key_deltas(keys)
    assert dw == 1
    assert len(stream) == delta_stream_bytes(keys.size, 1)
    assert len(stream) < keys.size * 2  # beats u16, 4x under i32
