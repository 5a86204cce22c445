"""Flash/blockwise attention vs the O(T^2) oracle — forward and gradients.

The Pallas kernel runs in interpret mode here (no TPU in CI; compiled path
is exercised by bench.py on the real chip). Oracle equality is the same
test discipline as ring attention (test_ring_attention.py)."""

import jax

import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from minips_tpu.ops.flash_attention import (blockwise_attention,
                                            flash_attention,
                                            kernel_supported)
from minips_tpu.parallel.ring_attention import reference_attention


def _qkv(B=2, T=64, H=2, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    shp = (B, T, H, D)
    return tuple(jax.random.normal(k, shp, dtype) for k in ks)


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_oracle(causal):
    q, k, v = _qkv()
    out = blockwise_attention(q, k, v, causal=causal, block_k=16)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_matches_oracle_interpret(causal):
    q, k, v = _qkv()
    assert kernel_supported(q.shape, k.shape, 32, 16)
    out = flash_attention(q, k, v, causal=causal, block_q=32, block_k=16,
                          interpret=True)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def _qkv_gqa(B=2, T=64, H=4, Hk=2, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D), dtype)
    k = jax.random.normal(ks[1], (B, T, Hk, D), dtype)
    v = jax.random.normal(ks[2], (B, T, Hk, D), dtype)
    return q, k, v


def _gqa_oracle(q, k, v, causal):
    """Explicit repeat-KV + full-head oracle: the defining semantics of
    grouped-query attention (q-head h attends through kv head h // g)."""
    g = q.shape[2] // k.shape[2]
    return reference_attention(q, jnp.repeat(k, g, axis=2),
                               jnp.repeat(v, g, axis=2), causal=causal)


@pytest.mark.parametrize("hk", [1, 2])   # 1 = MQA, 2 = 2-way GQA of H=4
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_forward_matches_repeat_oracle(causal, hk):
    q, k, v = _qkv_gqa(Hk=hk)
    ref = _gqa_oracle(q, k, v, causal)
    out_bw = blockwise_attention(q, k, v, causal=causal, block_k=16)
    np.testing.assert_allclose(out_bw, ref, atol=1e-5, rtol=1e-5)
    assert kernel_supported(q.shape, k.shape, 32, 16)
    out_kn = flash_attention(q, k, v, causal=causal, block_q=32,
                             block_k=16, interpret=True)
    np.testing.assert_allclose(out_kn, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("hk", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_gradients_match_repeat_oracle(causal, hk):
    """dK/dV under GQA must aggregate over every q-head in the group —
    the kernel's combined (group-head, Q-block) sweep vs AD through the
    explicit repeat (whose transpose is exactly that group-sum)."""
    q, k, v = _qkv_gqa(T=32, Hk=hk)

    def loss_ref(q, k, v):
        return jnp.sum(_gqa_oracle(q, k, v, causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        assert a.shape == b.shape    # dk/dv at the SMALL kv head count
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_gqa_rejects_nondivisible_heads():
    q, k, v = _qkv_gqa(H=4, Hk=3)
    assert not kernel_supported(q.shape, k.shape, 32, 16)
    with pytest.raises(ValueError, match="divide"):
        blockwise_attention(q, k, v, causal=True, block_k=16)


def test_blockwise_ragged_tail_still_exact():
    q, k, v = _qkv(T=48)
    out = blockwise_attention(q, k, v, causal=True, block_k=32)  # 48 % 32 != 0
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(causal):
    q, k, v = _qkv(T=32)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=16,
                                       block_k=16, interpret=True) ** 2)

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fl):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_unsupported_shapes_fall_back():
    q, k, v = _qkv(T=48, D=12)  # D % 8 != 0 -> no kernel
    assert not kernel_supported(q.shape, k.shape, 256, 256)
    out = flash_attention(q, k, v, causal=True)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_on_tpu_the_kernel_runs_or_raises(monkeypatch):
    """With the backend reported as tpu, a shape the kernels refuse is an
    error naming the shape — never the scan under the kernel's name."""
    import jax.sharding as shd

    from minips_tpu.ops.flash_attention import ring_flash_attention_local
    from minips_tpu.parallel.mesh import make_mesh

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q, k, v = _qkv(T=48, D=12)  # D % 8 != 0 -> no kernel
    with pytest.raises(ValueError, match=r"q\(2, 48, 2, 12\)"):
        flash_attention(q, k, v, causal=True)
    spec = shd.PartitionSpec(None, "data")
    with pytest.raises(ValueError, match=r"refuse q\(2, 6, 2, 12\)"):
        shard_map(lambda q_, k_, v_: ring_flash_attention_local(
            q_, k_, v_, axis_name="data", causal=True),
            mesh=make_mesh(8), in_specs=(spec,) * 3, out_specs=spec)(q, k, v)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_oracle(causal):
    """Ring flash attention (flash kernel per ring step, logsumexp merge)
    equals full attention over the gathered sequence — the sequence axis
    sharded over the 8-device CPU mesh, kernels in interpret mode."""
    import jax.sharding as shd

    from minips_tpu.ops.flash_attention import ring_flash_attention_local
    from minips_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    P = shd.PartitionSpec
    spec = P(None, "data")
    q, k, v = _qkv(B=2, T=64, H=2, D=16, seed=3)

    # check_vma=False: the interpret-mode pallas interpreter can't track
    # varying-manual-axes through its internal dynamic_slices (JAX issue);
    # the compiled TPU path carries real vma via ShapeDtypeStruct
    out = jax.jit(shard_map(
        lambda q_, k_, v_: ring_flash_attention_local(
            q_, k_, v_, axis_name="data", causal=causal, block_q=8,
            block_k=8, interpret=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False))(q, k, v)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


def test_ring_flash_gradients_match_oracle():
    """Ring flash grads through the default path (the one sp training
    uses off-TPU) equal full-attention grads — logsumexp-merge AD
    included."""
    import jax.sharding as shd

    from minips_tpu.ops.flash_attention import ring_flash_attention_local
    from minips_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    P = shd.PartitionSpec
    spec = P(None, "data")
    q, k, v = _qkv(B=1, T=64, H=2, D=16, seed=4)

    def loss_ring(q, k, v):
        out = shard_map(
            lambda q_, k_, v_: ring_flash_attention_local(
                q_, k_, v_, axis_name="data", causal=True, block_k=8),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)
        return jnp.sum(out ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_kernel_lse_cotangent_matches_jnp():
    """The kernels' custom VJP must propagate the lse output's cotangent
    (the ring merge differentiates through lse). Compare against the
    pure-jnp offset twin under a loss that uses BOTH outputs."""
    from minips_tpu.ops.flash_attention import _flash_with_lse

    q, k, v = _qkv(B=1, T=32, H=2, D=16, seed=7)
    q_off = jnp.int32(16)
    k_off = jnp.int32(0)

    def loss_kernel(q, k, v):
        out, lse = _flash_with_lse(q, k, v, q_off, k_off, True,
                                   16 ** -0.5, 16, 16, True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse[..., 0]))

    def loss_jnp(q, k, v):
        out, lse = blockwise_attention(q, k, v, causal=True,
                                       scale=16 ** -0.5, block_k=16,
                                       q_off=q_off, k_off=k_off,
                                       return_lse=True)
        # jnp twin returns lse as [B, Tq, H]; kernel as [B, H, Tq, 1]
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(
            lse.transpose(0, 2, 1)))

    g_k = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_j = jax.grad(loss_jnp, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_k, g_j):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_ring_flash_default_path_off_tpu():
    """With interpret unset, off-TPU the ring uses the pure-jnp offset
    blockwise path — full VMA checking on, ordinary AD, same numerics.
    This is the path the sp training layout takes on the CPU mesh."""
    import jax.sharding as shd

    from minips_tpu.ops.flash_attention import ring_flash_attention_local
    from minips_tpu.parallel.mesh import make_mesh

    mesh = make_mesh(8)
    P = shd.PartitionSpec
    spec = P(None, "data")
    q, k, v = _qkv(B=2, T=64, H=2, D=16, seed=6)
    out = jax.jit(shard_map(
        lambda q_, k_, v_: ring_flash_attention_local(
            q_, k_, v_, axis_name="data", causal=True),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec))(q, k, v)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-5)


@pytest.mark.slow  # fast tier: test_transformer_apply_flash_matches_reference
def test_lm_sp_flash_trajectory_matches_reference():
    """lm_example --layout sp --attn flash trains to the same losses as
    --attn reference (ring flash is a drop-in inside the fused PS step)."""
    import argparse

    from minips_tpu.apps import lm_example as app
    from minips_tpu.core.config import Config, TableConfig, TrainConfig
    from minips_tpu.utils.metrics import MetricsLogger

    cfg = Config(
        table=TableConfig(name="lm", kind="dense", updater="adam", lr=3e-3),
        train=TrainConfig(batch_size=16, num_iters=8, log_every=100),
    )
    outs = {}
    for attn in ("reference", "flash"):
        args = argparse.Namespace(layout="sp", seq_len=32, tp=2,
                                  microbatches=2, attn=attn)
        outs[attn] = app.run(cfg, args, MetricsLogger(None, verbose=False))
    np.testing.assert_allclose(outs["flash"]["losses"],
                               outs["reference"]["losses"],
                               atol=2e-3, rtol=2e-3)


def test_transformer_apply_flash_matches_reference():
    """attn_impl='flash' is a drop-in for the LM forward/backward."""
    from minips_tpu.models import transformer as tfm

    p = tfm.init(jax.random.PRNGKey(0), vocab=64, dim=32, heads=2, depth=2,
                 max_len=64)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 33), 0, 64)
    batch = {"tokens": toks}
    l_ref, g_ref = tfm.grad_fn(p, batch, heads=2)
    l_fl, g_fl = tfm.grad_fn(p, batch, heads=2, attn_impl="flash")
    np.testing.assert_allclose(l_ref, l_fl, atol=2e-3, rtol=2e-3)
    for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_fl)):
        np.testing.assert_allclose(a, b, atol=5e-3, rtol=5e-2)


def test_bfloat16_inputs():
    q, k, v = _qkv(dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=32, block_k=32,
                          interpret=True)
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=2e-2,
                               rtol=2e-2)
