"""Flash/blockwise attention vs the O(T^2) oracle — forward and gradients.

The Pallas kernel runs in interpret mode here (no TPU in CI; compiled path
is exercised by bench.py on the real chip). Oracle equality is the same
test discipline as ring attention (test_ring_attention.py)."""

import functools

import jax

import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from minips_tpu.ops import flash_attention as fa
from minips_tpu.ops.flash_attention import (blockwise_attention,
                                            computed_share,
                                            flash_attention, flash_plan,
                                            kernel_supported)
from minips_tpu.parallel.ring_attention import reference_attention
from minips_tpu.utils import profiling as prof
from tests.conftest import pallas_call_names


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


# Shapes that exercise the in-kernel sweep: (qkv kwargs, block_q, block_k,
# rows of a head that fit the resident budget, or None for the module's own).
SWEEPS = {
    "q_tile_over_k_tile": (dict(), 32, 16, None),
    "k_tile_over_q_tile": (dict(), 16, 32, None),
    "one_block": (dict(T=32), 32, 32, None),
    "d64_odd_heads": (dict(B=1, H=3, D=64), 16, 16, None),
    "scale_stays_on_scores": (dict(B=1, D=24), 16, 16, None),  # 24**-.5
    "major_blocks": (dict(B=1, T=128), 8, 8, 64),
    # tiles of 256: the tile ON the diagonal is computed as 3 of its 4
    # 128-wide groups
    "diagonal_groups": (dict(B=1, T=512, H=1), 256, 256, None),
    # forward tiles of 128 under backward tiles of 256: a backward tile
    # spans two logsumexp rows (the module's own tiles do at T 1024)
    "two_lse_rows_a_tile": (dict(B=1, T=512, H=1), None, None, None),
}


def _sweep_case(monkeypatch, name, **over):
    kw, bq, bk, rows = SWEEPS[name]
    q, k, v = _qkv(**{**kw, **over})
    if name == "two_lse_rows_a_tile":
        monkeypatch.setattr(fa, "_FWD_TILE", (128, 128))
        monkeypatch.setattr(fa, "_BWD_TILE", (256, 256))
        plan = flash_plan(512, 512, q.shape[3], q.dtype.itemsize)
        assert plan[:4] == (128, 128, 256, 256)
    if rows is not None:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES",
                            rows * q.shape[3] * q.dtype.itemsize)
        plan = flash_plan(q.shape[1], k.shape[1], q.shape[3],
                          q.dtype.itemsize, bq, bk)
        assert plan.major_q < q.shape[1] and plan.major_k < k.shape[1]
    return q, k, v, bq, bk


@pytest.mark.parametrize("sweep", list(SWEEPS))
@pytest.mark.parametrize("causal", [False, True])
def test_pallas_kernel_matches_oracle_interpret(causal, sweep, monkeypatch):
    q, k, v, bq, bk = _sweep_case(monkeypatch, sweep)
    assert kernel_supported(q.shape, k.shape, bq, bk)
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
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


@pytest.mark.parametrize("major", [False, True])
@pytest.mark.parametrize("hk", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_gqa_gradients_match_repeat_oracle(causal, hk, major, monkeypatch):
    """dK/dV under GQA must aggregate over every q-head in the group —
    the kernel's combined (group-head, Q-major) sweep vs AD through the
    explicit repeat (whose transpose is exactly that group-sum). With
    ``major`` the sequence is walked in two major blocks as well."""
    q, k, v = _qkv_gqa(T=32, Hk=hk)
    bq = bk = 16
    if major:
        q, k, v = _qkv_gqa(B=1, T=64, Hk=hk)
        bq = bk = 4
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", 32 * 16 * 4)
        plan = flash_plan(64, 64, 16, 4, bq, bk)
        assert (plan.major_q, plan.major_k) == (32, 32)

    def loss_ref(q, k, v):
        return jnp.sum(_gqa_oracle(q, k, v, causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=bq,
                                       block_k=bk, interpret=True) ** 2)

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


@pytest.mark.parametrize("sweep", list(SWEEPS))
@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(causal, sweep, monkeypatch):
    q, k, v, bq, bk = _sweep_case(monkeypatch, sweep)
    if sweep not in ("major_blocks", "diagonal_groups",
                     "two_lse_rows_a_tile"):
        # as before: half the sequence, 16 x 16
        q, k, v, bq, bk = q[:, :32], k[:, :32], v[:, :32], 16, min(bk, 16)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=causal, block_q=bq,
                                       block_k=bk, interpret=True) ** 2)

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


# One ring step seen from a shard of T rows (32 where not given):
# (q_off, k_off, block, budget[, T]).
RING_STEPS = {
    "crosses_diagonal": (16, 0, 16, None),
    "diagonal": (32, 32, 16, None),
    "wholly_kept": (64, 0, 16, None),
    "wholly_masked": (0, 32, 16, None),
    "kept_tiles_q_over_k": (40, 0, 8, None),
    "major_blocks": (16, 0, 2, 16 * 16 * 4),
    "diagonal_in_groups": (512, 512, 256, None, 512),
    "tiles_of_256_off_the_diagonal": (128, 0, 256, None, 512),
}


@pytest.mark.parametrize("step", list(RING_STEPS))
def test_kernel_lse_cotangent_matches_jnp(step, monkeypatch):
    """The kernels' custom VJP must propagate the lse output's cotangent
    (the ring merge differentiates through lse). Compare against the
    pure-jnp offset twin under a loss that uses BOTH outputs, for every
    kind of ring step: the sweep's bounds come from these offsets."""
    from minips_tpu.ops.flash_attention import _flash_with_lse

    off_q, off_k, blk, budget, T = (RING_STEPS[step] + (32,))[:5]
    q, k, v = _qkv(B=1, T=T, H=2, D=16, seed=7)
    q_off, k_off = jnp.int32(off_q), jnp.int32(off_k)
    if budget is not None:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", budget)
        plan = flash_plan(32, 32, 16, 4, blk, blk)
        assert (plan.major_q, plan.major_k) == (16, 16)

    def kernel(q, k, v):
        return _flash_with_lse(q, k, v, q_off, k_off, True, 16 ** -0.5,
                               2 * blk if step.endswith("q_over_k") else blk,
                               blk, True)

    def twin(q, k, v):
        return blockwise_attention(q, k, v, causal=True, scale=16 ** -0.5,
                                   block_k=16, q_off=q_off, k_off=k_off,
                                   return_lse=True)

    def loss_kernel(q, k, v):
        out, lse = kernel(q, k, v)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_jnp(q, k, v):
        out, lse = twin(q, k, v)
        # jnp twin returns lse as [B, Tq, H]; the kernel tile by tile,
        # [B, H, Q majors, tiles, tile_q]: the same rows in the same order
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(
            lse.transpose(0, 2, 1)))

    (out_k, lse_k), (out_j, lse_j) = kernel(q, k, v), twin(q, k, v)
    if step == "wholly_masked":   # no live tile: zeros, at weight exp(-1e30)
        assert not np.any(np.asarray(out_k)) and np.all(lse_k < -1e29)
    else:
        np.testing.assert_allclose(out_k, out_j, atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(lse_k.reshape(1, 2, T),
                               lse_j.transpose(0, 2, 1), rtol=1e-5)
    g_k = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_j = jax.grad(loss_jnp, argnums=(0, 1, 2))(q, k, v)
    if step == "wholly_masked":   # (the twin averages V over masked keys)
        g_j = [jnp.zeros_like(x) for x in g_j]
    for a, b in zip(g_k, g_j):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


# The one backward kernel where both sequence axes are walked in major
# blocks, so that dQ crosses the K majors in scratch and dK/dV the
# (group q-head, Q major) axis: (H, Hk, D, Dv, q_off, k_off, rows of a
# head that fit the resident budget, VMEM the backward may take or None).
FUSED = {
    "unequal_heads_majors": (2, 2, 24, 16, 0, 0, 16, None),
    "unequal_heads_gqa_majors": (4, 2, 24, 16, 0, 0, 16, None),
    "gqa4_majors": (4, 1, 16, 16, 0, 0, 16, None),
    "gqa4_majors_ring_crossing": (4, 1, 16, 16, 48, 16, 16, None),
    "gqa4_majors_ring_unaligned": (4, 1, 16, 16, 40, 12, 16, None),
    "gqa4_majors_ring_kept": (4, 1, 16, 16, 128, 0, 16, None),
    "gqa4_majors_ring_masked": (4, 1, 16, 16, 0, 64, 16, None),
    # a budget that holds the dQ of a quarter of the sequence: the kernel
    # runs once a span of Q rows and the spans' dK/dV are summed outside
    "gqa4_majors_in_spans": (4, 1, 16, 16, 48, 16, 16, 200_000),
    "unequal_heads_in_spans": (2, 2, 24, 16, 0, 0, 16, 160_000),
}


@pytest.mark.parametrize("case", list(FUSED))
def test_fused_backward_matches_the_blockwise_twin(case, monkeypatch):
    """dQ, dK and dV of the one backward kernel (interpreted) against AD
    through the blockwise scan at the same global offsets, under a loss
    that uses ``out`` AND ``lse``: several majors on both axes, v's own
    head size, a group of four q-heads on one kv head, ring offsets."""
    from minips_tpu.ops.flash_attention import _flash_with_lse

    H, Hk, D, Dv, off_q, off_k, rows, vmem = FUSED[case]
    T, blk = 64, 8
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (1, T, H, D))
    k = jax.random.normal(ks[1], (1, T, Hk, D))
    v = jax.random.normal(ks[2], (1, T, Hk, Dv))
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", rows * max(D, Dv) * 4)
    if vmem is not None:
        monkeypatch.setattr(fa, "_VMEM_BYTES", vmem)
    plan = flash_plan(T, T, D, 4, blk, blk, Dv, H // Hk)
    assert (plan.major_q, plan.major_k) == (rows, rows)     # 4 x 4 majors
    assert plan.span_q == (T if vmem is None else rows)
    assert plan.bwd_vmem <= fa._VMEM_BYTES
    q_off, k_off = jnp.int32(off_q), jnp.int32(off_k)
    scale = D ** -0.5

    def loss_kernel(q, k, v):
        out, lse = _flash_with_lse(q, k, v, q_off, k_off, True, scale,
                                   blk, blk, True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    def loss_twin(q, k, v):
        out, lse = blockwise_attention(q, k, v, causal=True, scale=scale,
                                       block_k=16, q_off=q_off,
                                       k_off=k_off, return_lse=True)
        return jnp.sum(out ** 2) + jnp.sum(jnp.sin(lse))

    names = pallas_call_names(jax.make_jaxpr(
        jax.grad(loss_kernel, (0, 1, 2)))(q, k, v).jaxpr)
    assert names.count(prof.FLASH_BWD) == T // plan.span_q
    g_k = jax.grad(loss_kernel, argnums=(0, 1, 2))(q, k, v)
    g_t = jax.grad(loss_twin, argnums=(0, 1, 2))(q, k, v)
    if case.endswith("masked"):   # (the twin averages V over masked keys)
        g_t = [jnp.zeros_like(x) for x in g_t]
    for a, b in zip(g_k, g_t):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("H, Hk, D, Dv", [(25, 25, 64, 64), (8, 2, 128, 128),
                                          (4, 4, 192, 128)])
def test_a_layers_backward_lowers_to_one_flash_bwd_custom_call(H, Hk, D, Dv):
    """Lowered for the TPU (no chip is needed to lower), the gradient of
    two layers of attention holds one ``flash_fwd`` and one ``flash_bwd``
    custom call a layer, and neither kernel of the pair it replaced."""
    import re

    q = jax.ShapeDtypeStruct((1, 1024, H, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, Hk, D), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 1024, Hk, Dv), jnp.bfloat16)

    def loss(q, k, v):
        a = fa._flash(q, k, v, True, D ** -0.5, None, None, False)
        b = fa._flash(q * 2, k, v, True, D ** -0.5, None, None, False)
        return jnp.sum((a + b).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(q, k, v).lower(
        lowering_platforms=("tpu",)).as_text()
    assert text.count("@tpu_custom_call") == 4
    kernels = re.findall(r'kernel_name\s*=\s*"(\w+)"', text)
    assert sorted(kernels) == [prof.FLASH_BWD] * 2 + [prof.FLASH_FWD] * 2
    assert "flash_dq" not in text and "flash_dkv" not in text


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


@pytest.mark.parametrize("sweep", ["q_tile_over_k_tile", "one_block",
                                   "d64_odd_heads", "major_blocks"])
def test_bfloat16_inputs(sweep, monkeypatch):
    q, k, v, bq, bk = _sweep_case(monkeypatch, sweep, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, block_q=bq, block_k=bk,
                          interpret=True)
    ref = reference_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                              v.astype(jnp.float32), causal=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(out.astype(np.float32), ref, atol=2e-2,
                               rtol=2e-2)


def test_bfloat16_gradients_keep_the_input_dtype():
    q, k, v = _qkv(T=32, dtype=jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]

    def loss(attn, **kw):
        return lambda q, k, v: jnp.sum(
            attn(q, k, v, causal=True, **kw).astype(jnp.float32) ** 2)

    g_ref = jax.grad(loss(reference_attention), argnums=(0, 1, 2))(*f32)
    g_fl = jax.grad(loss(flash_attention, block_q=16, block_k=16,
                         interpret=True), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        assert a.dtype == jnp.bfloat16
        err = np.linalg.norm(a.astype(np.float32) - b) / np.linalg.norm(b)
        assert err < 2e-2, err


# ---- the plan: what the kernels and these tests both call
@pytest.mark.parametrize("tiles, share", [
    ((512, 512), 0.75),          # the grid-per-pair kernels' blocks: to beat
    ((256, 256), 0.625), ((128, 128), 0.5625), ((1024, 1024), 1.0),
    ((256, 512), 0.75), ((512, 256), 0.75), ((128, 256), 0.625),
])
def test_computed_share_of_the_causal_scores(tiles, share):
    assert computed_share(1024, 1024, *tiles) == share


@pytest.mark.parametrize("tile, share", [
    (256, 0.5625), (512, 0.5625), (1024, 0.5625), (128, 0.5625)])
def test_a_cut_diagonal_computes_the_share_of_128_blocks(tile, share):
    """The backward's tiles on the diagonal count their live 128-wide
    groups only: whatever the tile, (8 + 1) / 16 of a 1,024 square."""
    assert computed_share(1024, 1024, tile, tile, cut=True) == share


def test_plan_at_the_benchmark_shape_beats_the_old_blocks():
    plan = flash_plan(1024, 1024, 64, 2)     # GPT-2 XL's head, bf16
    assert plan.causal_share == computed_share(
        1024, 1024, plan.tile_q, plan.tile_k) <= 0.75
    assert plan.bwd_share == computed_share(
        1024, 1024, plan.bwd_q, plan.bwd_k, cut=True) < 0.75
    assert (plan.major_q, plan.major_k) == (1024, 1024)   # a head resident
    for tile in plan[:4]:
        assert tile % 128 == 0
    assert plan.bwd_q % plan.tile_q == 0   # whole logsumexp rows a tile


# (Tq, D, Dv, q-heads a kv head): majors, the Q rows whose dQ a backward call
# keeps, and the VMEM it is sized at, at the benchmark's three head shapes
CELL_PLANS = {
    "gpt2-xl": ((1024, 64, 64, 1), (1024, 1024, 1024, 30_146_560)),
    "zaya1-8b": ((8192, 128, 128, 4), (2048, 2048, 8192, 51_380_224)),
    "joyai-llm-flash": ((8192, 192, 128, 1), (1024, 1024, 8192, 38_797_312)),
}


@pytest.mark.parametrize("cell", list(CELL_PLANS))
def test_plan_of_the_fused_backward_at_the_cells_shapes(cell):
    """The group's whole dQ stays in VMEM at every shape a cell runs (one
    call a layer), in what the v5e has; the float32 accumulators are the
    larger part of it only where the sequence is long."""
    (T, D, Dv, g), want = CELL_PLANS[cell]
    plan = flash_plan(T, T, D, 2, Dv=Dv, g=g)
    assert (plan.major_q, plan.major_k, plan.span_q, plan.bwd_vmem) == want
    assert plan.bwd_vmem <= fa._VMEM_BYTES < 128 * 2 ** 20
    assert plan.bwd_vmem > 4 * g * plan.span_q * D      # dQ^T, float32


def test_plan_cuts_the_backward_into_spans_where_a_groups_dq_does_not_fit():
    # 64 q-heads on one kv head at T 32,768, D 128: 1 GiB of float32 dQ
    plan = flash_plan(32768, 32768, 128, 2, g=64)
    assert plan.major_q <= plan.span_q < 32768
    assert 32768 % plan.span_q == 0 and plan.span_q % plan.major_q == 0
    assert plan.bwd_vmem <= fa._VMEM_BYTES
    # half the group keeps twice the rows
    assert flash_plan(32768, 32768, 128, 2, g=32).span_q == 2 * plan.span_q


@pytest.mark.parametrize("T, D, itemsize", [
    (32768, 64, 2), (8192, 128, 2), (16384, 64, 4)])
def test_plan_walks_a_long_sequence_in_major_blocks(T, D, itemsize):
    plan = flash_plan(T, T, D, itemsize)
    for major, tiles in ((plan.major_q, (plan.tile_q, plan.bwd_q)),
                         (plan.major_k, (plan.tile_k, plan.bwd_k))):
        assert major < T and T % major == 0
        assert major * D * itemsize <= fa._RESIDENT_BYTES
        assert all(major % t == 0 for t in tiles)


@pytest.mark.parametrize("T, bq, bk, fwd, bwd", [
    (64, 32, 16, (32, 16), (32, 16)),   # a caller's blocks bound every tile
    (64, None, None, (64, 64), (64, 64)),   # a short sequence is one tile
    (192, None, None, (96, 96), (96, 96)),  # no 128-multiple divides: <= 128
    (384, None, None, (384, 384), (384, 384)),
    (768, None, None, (384, 384), (768, 768)),  # bwd_q: whole lse rows
    (1024, 128, None, (128, fa._FWD_TILE[1]), (128, fa._BWD_TILE[1])),
    (4096, None, None, fa._FWD_TILE, fa._BWD_TILE),
])
def test_plan_tiles_divide_and_stay_within_the_blocks(T, bq, bk, fwd, bwd):
    plan = flash_plan(T, T, 64, 2, bq, bk)
    assert (plan.tile_q, plan.tile_k) == fwd
    assert (plan.bwd_q, plan.bwd_k) == bwd
    assert all(T % t == 0 for t in plan[:4])


# ------------------------------- the forward's residuals under jax.checkpoint
_NAMES = jax.checkpoint_policies.save_only_these_names
# a checkpoint policy around the kernels, and the forward kernel's calls in
# the whole of grad under it
RESIDUAL_POLICIES = {
    "both_names": (_NAMES(*prof.FLASH_RESIDUALS), 1),
    # either alone leaves the backward a reader of the kernel
    "out_alone": (_NAMES(prof.FLASH_OUT), 2),
    "lse_alone": (_NAMES(prof.FLASH_LSE), 2),
    # the kernel is a custom call, no dot_general: the reason the block
    # checkpoint's "dots" adds the names
    "dots_alone": (jax.checkpoint_policies.checkpoint_dots, 2),
    "nothing": (None, 2),
}


@pytest.mark.parametrize("policy", list(RESIDUAL_POLICIES))
def test_checkpoint_that_saves_both_residuals_runs_the_forward_once(policy):
    saved, forward_calls = RESIDUAL_POLICIES[policy]
    q, k, v = _qkv(B=1, T=64)

    def attn(*qkv):
        return flash_attention(*qkv, causal=True, interpret=True,
                               block_q=32, block_k=32).sum()

    grad = jax.grad(jax.checkpoint(attn, policy=saved), argnums=(0, 1, 2))
    names = pallas_call_names(jax.make_jaxpr(grad)(q, k, v).jaxpr)
    assert names.count(prof.FLASH_FWD) == forward_calls
    assert names.count(prof.FLASH_BWD) == 1
    assert set(names) == {prof.FLASH_FWD, prof.FLASH_BWD}   # no pair
    for a, b in zip(grad(q, k, v),
                    jax.grad(attn, argnums=(0, 1, 2))(q, k, v)):
        assert np.asarray(a).tobytes() == np.asarray(b).tobytes()


def test_checkpointed_ring_keeps_one_out_and_lse_a_ring_step(capsys):
    """What ``ring_flash_attention_local``'s docstring says a caller who
    wraps it in ``jax.checkpoint`` with the two names keeps: each ring
    step's partial ``out`` and ``lse``, stacked over the steps."""
    import jax.sharding as shd

    from minips_tpu.ops.flash_attention import ring_flash_attention_local
    from minips_tpu.parallel.mesh import make_mesh

    n = 4
    mesh = make_mesh(n)
    spec = shd.PartitionSpec(None, "data")
    q, k, v = _qkv(B=1, T=64, H=2, D=16, seed=5)

    @functools.partial(jax.checkpoint,
                       policy=jax.checkpoint_policies.save_only_these_names(
                           *prof.FLASH_RESIDUALS))
    def local(q_, k_, v_):
        return ring_flash_attention_local(
            q_, k_, v_, axis_name="data", causal=True, block_q=8,
            block_k=8, interpret=True)

    # (the interpreter needs check_vma=False, under which this jax cannot
    # transpose the ring's shard_map: what is kept is read, no gradient)
    jax.ad_checkpoint.print_saved_residuals(
        lambda q, k, v: jnp.sum(shard_map(
            local, mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
            check_vma=False)(q, k, v) ** 2), q, k, v)
    lines = capsys.readouterr().out.splitlines()
    T_local = 64 // n
    # seen from outside the shard_map: n steps a device, n devices
    kept = [l.split(" ")[0] for l in lines if "output of shard_map" in l]
    assert kept == [f"f32[{n * n},1,{T_local},{2 * 16}]",   # out, as named
                    f"f32[{n * n},1,2,1,2,8]"]              # lse rows
