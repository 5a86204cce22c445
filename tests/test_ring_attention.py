"""Ring attention vs. the O(T^2) oracle, on the 8-fake-device mesh.

Sequence parallelism is absent in the reference (SURVEY.md §2.2/§5.7) —
these tests cover the rebuild's beyond-parity long-context module: exact
blockwise attention with K/V shards rotating over ppermute must match full
attention bit-for-bit (up to fp tolerance) for every (causal, shape) combo.
"""

import jax

import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from minips_tpu.parallel.ring_attention import (
    make_ring_attention,
    reference_attention,
    ring_attention_local,
)


def _qkv(B, T, H, D, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (B, T, H, D)
    return tuple(jax.random.normal(k, shape, jnp.float32) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_matches_oracle(mesh8, causal):
    B, T, H, D = 2, 64, 4, 16  # T sharded 8 ways -> 8 tokens per device
    q, k, v = _qkv(B, T, H, D)
    attn = make_ring_attention(mesh8, causal=causal)
    out = attn(attn.shard(q), attn.shard(k), attn.shard(v))
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_matches_oracle_4way(mesh4, causal):
    B, T, H, D = 1, 32, 2, 8
    q, k, v = _qkv(B, T, H, D, seed=1)
    attn = make_ring_attention(mesh4, causal=causal)
    out = attn(attn.shard(q), attn.shard(k), attn.shard(v))
    want = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("hk", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_matches_repeat_oracle(mesh4, causal, hk):
    """GQA through the ring: K/V shards rotate at the SMALL head count
    (the ppermute wire shrinks by the group factor); result must equal
    the explicit repeat-KV full-head oracle."""
    B, T, H, D = 1, 32, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, T, hk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, T, hk, D), jnp.float32)
    attn = make_ring_attention(mesh4, causal=causal)
    out = attn(attn.shard(q), attn.shard(k), attn.shard(v))
    want = reference_attention(q, jnp.repeat(k, H // hk, axis=2),
                               jnp.repeat(v, H // hk, axis=2),
                               causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_single_device_degenerates_to_full_attention():
    """n=1 ring = one online-softmax pass over the whole sequence."""
    B, T, H, D = 2, 16, 2, 8
    q, k, v = _qkv(B, T, H, D, seed=2)
    # run under a size-1 shard_map so axis_name resolves
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    f = shard_map(
        lambda a, b, c: ring_attention_local(a, b, c, causal=True),
        mesh=mesh, in_specs=(P("data"), P("data"), P("data")),
        out_specs=P("data"))
    np.testing.assert_allclose(
        np.asarray(f(q, k, v)),
        np.asarray(reference_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


def test_scale_override(mesh4):
    B, T, H, D = 1, 16, 1, 4
    q, k, v = _qkv(B, T, H, D, seed=3)
    attn = make_ring_attention(mesh4, scale=0.5)
    out = attn(attn.shard(q), attn.shard(k), attn.shard(v))
    want = reference_attention(q, k, v, scale=0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_memory_is_blockwise(mesh8):
    """The compiled program must move K/V with ring hops (collective-permute)
    and never all-gather the sequence — a regression to gather-then-full-
    attention would reintroduce O(T) per-device memory and [T, T] scores."""
    B, T, H, D = 1, 128, 2, 8
    q, k, v = _qkv(B, T, H, D, seed=4)
    attn = make_ring_attention(mesh8)
    sq, sk, sv = attn.shard(q), attn.shard(k), attn.shard(v)
    hlo = jax.jit(lambda a, b, c: attn(a, b, c)).lower(
        sq, sk, sv).compile().as_text()
    assert "collective-permute" in hlo
    assert "all-gather" not in hlo
    out = attn(sq, sk, sv)
    assert out.sharding.spec == jax.sharding.PartitionSpec(None, "data")
    assert np.isfinite(np.asarray(out)).all()


def test_bf16_inputs_keep_f32_softmax_state(mesh4):
    """bf16 q/k/v may round the matmul INPUTS, but the softmax statistics
    (running max / normalizer / accumulator) must stay f32 — both the
    oracle and the ring path should sit within bf16-input rounding of the
    all-f32 result, and the ring must agree with the oracle at much
    tighter than bf16 resolution (both consume identical bf16 inputs)."""
    B, T, H, D = 1, 32, 2, 8
    q, k, v = _qkv(B, T, H, D, seed=7)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))

    want = reference_attention(q, k, v, causal=True)
    ref_b = reference_attention(qb, kb, vb, causal=True)
    assert ref_b.dtype == jnp.bfloat16
    np.testing.assert_allclose(np.asarray(ref_b, np.float32),
                               np.asarray(want), rtol=5e-2, atol=5e-2)

    attn = make_ring_attention(mesh4, causal=True)
    out_b = attn(attn.shard(qb), attn.shard(kb), attn.shard(vb))
    assert out_b.dtype == jnp.bfloat16
    # same bf16 inputs on both sides: only the (f32) accumulation order
    # differs, so agreement must be near-exact — this catches any
    # regression to bf16 carries, which would drift with ring steps
    np.testing.assert_allclose(np.asarray(out_b, np.float32),
                               np.asarray(ref_b, np.float32),
                               rtol=1e-2, atol=1e-2)
