"""The windowed flash kernels (``flash_attention(window=)``: the query at t
sees the keys t - W < s <= t) in the Pallas interpreter, forward and all
three gradients, against the blockwise twin and against plain attention
under an explicit mask: windows under a tile, of a tile, and of the whole
sequence (which is causal attention); grouped queries of 9 and 6 a key
head; offsets that are not zero; sequences walked in major blocks, whose
band the grid walks alone. And ``window=None``: the plan and the programs
are as without the argument."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.ops import flash_attention as fa
from minips_tpu.ops.flash_attention import (blockwise_attention,
                                            computed_share, flash_attention,
                                            flash_plan)
from minips_tpu.utils import profiling as prof
from tests.conftest import jaxpr_eqns


def _masked(q, k, v, window, q_off=0, k_off=0):
    """Plain attention: full scores, the window's mask, grouped heads by
    an explicit repeat."""
    g = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    t = q_off + jnp.arange(q.shape[1])[:, None]
    u = k_off + jnp.arange(k.shape[1])[None, :]
    s = jnp.where((u <= t) & (u > t - window), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


def _qkv(T, H, Hk, D=16, seed=0, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (1, T, H, D), dtype),
            jax.random.normal(ks[1], (1, T, Hk, D), dtype),
            jax.random.normal(ks[2], (1, T, Hk, D), dtype))


# (T, q-heads, kv heads, window, block, rows resident a major or None,
#  q_off, k_off): blocks of 8 give tiles of 8; a budget of 16 rows walks
#  the sequence in majors of 16, so that a band spans two or three of them
CASES = {
    "window_under_a_tile": (64, 4, 2, 3, 8, None, 0, 0),
    "window_of_a_tile": (64, 4, 2, 8, 8, None, 0, 0),
    "window_of_the_sequence": (64, 4, 2, 64, 8, None, 0, 0),
    "gqa_9_in_majors": (64, 9, 1, 12, 8, 16, 0, 0),
    "gqa_6_in_majors_offsets": (64, 6, 1, 20, 4, 8, 5, 0),
    "equal_offsets_in_majors": (64, 4, 2, 40, 8, 16, 3, 3),
    "band_of_two_majors": (64, 4, 2, 5, 8, 16, 0, 0),
}


def _case(monkeypatch, name):
    T, H, Hk, W, blk, rows, qo, ko = CASES[name]
    q, k, v = _qkv(T, H, Hk, seed=len(name))
    if rows:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", rows * 16 * 4)
        plan = flash_plan(T, T, 16, 4, blk, blk, window=W)
        assert plan.major_q == plan.major_k == rows
    return q, k, v, W, blk, qo, ko


def _kernels(q, k, v, W, blk, qo, ko):
    return fa._flash_with_lse(q, k, v, jnp.int32(qo), jnp.int32(ko), True,
                              q.shape[-1] ** -0.5, blk, blk, True, W)[0]


@pytest.mark.parametrize("name", list(CASES))
def test_the_window_kernels_match_masked_attention(name, monkeypatch):
    q, k, v, W, blk, qo, ko = _case(monkeypatch, name)
    want = _masked(q, k, v, W, qo, ko)
    np.testing.assert_allclose(_kernels(q, k, v, W, blk, qo, ko), want,
                               atol=1e-5, rtol=1e-5)
    twin = blockwise_attention(q, k, v, causal=True, block_k=blk, q_off=qo,
                               k_off=ko, window=W)
    np.testing.assert_allclose(twin, want, atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("name", list(CASES))
def test_the_window_kernels_gradients_match(name, monkeypatch):
    """dQ, dK and dV of ``flash_bwd_win`` against AD through the masked
    attention; dK and dV at the key heads' count."""
    q, k, v, W, blk, qo, ko = _case(monkeypatch, name)
    w = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def loss(f):
        return lambda q, k, v: jnp.sum(w * f(q, k, v))

    want = jax.grad(loss(functools.partial(_masked, window=W, q_off=qo,
                                           k_off=ko)), (0, 1, 2))(q, k, v)
    got = jax.grad(loss(functools.partial(
        _kernels, W=W, blk=blk, qo=qo, ko=ko)), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T, W, rows", [(512, 100, None), (512, 256, None),
                                         (512, 512, None), (1024, 512, None),
                                         (1024, 256, 512)])
def test_the_diagonal_tiles_groups_under_a_window(T, W, rows, monkeypatch):
    """Tiles of 256, whose edge tiles the backward computes as their
    128-wide groups where the window spans a tile (256, 512: the tile on
    the diagonal, and the one the far edge enters on its first query, one
    or two tiles on) and masked whole where it does not (100); in majors of
    512 rows the far edge's tile lies in the major after the diagonal's."""
    if rows:
        monkeypatch.setattr(fa, "_RESIDENT_BYTES", rows * 16 * 4)
    q, k, v = _qkv(T, 2, 1, seed=W)
    out = flash_attention(q, k, v, causal=True, window=W, block_q=256,
                          block_k=256, interpret=True)
    np.testing.assert_allclose(out, _masked(q, k, v, W), atol=1e-5,
                               rtol=1e-5)
    want = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(_masked(q, k, v, W))),
                    (0, 1, 2))(q, k, v)
    got = jax.grad(lambda q, k, v: jnp.sum(jnp.sin(flash_attention(
        q, k, v, causal=True, window=W, block_q=256, block_k=256,
        interpret=True))), (0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_bfloat16_window_kernels_keep_the_input_dtype():
    q, k, v = _qkv(64, 4, 2, dtype=jnp.bfloat16)
    out = flash_attention(q, k, v, causal=True, window=10, block_q=16,
                          block_k=16, interpret=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        out.astype(jnp.float32),
        _masked(*(x.astype(jnp.float32) for x in (q, k, v)), 10),
        atol=3e-2, rtol=3e-2)


def test_a_window_of_the_sequence_is_causal_attention():
    q, k, v = _qkv(64, 4, 2, seed=3)
    causal = flash_attention(q, k, v, causal=True, block_q=8, block_k=8,
                             interpret=True)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=64, block_q=8,
                        block_k=8, interpret=True), causal, atol=1e-6)


def test_off_the_kernels_the_window_runs_the_blockwise_twin():
    q, k, v = _qkv(64, 6, 2, seed=4)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, window=7),
        _masked(q, k, v, 7), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("kw", [dict(causal=False, window=8),
                                dict(causal=True, window=0)])
def test_a_window_without_causal_masking_is_refused(kw):
    q, k, v = _qkv(16, 2, 2)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, **kw)


# ------------------------------------------------ the band, on the grid
def _grids(fn, *args):
    jaxpr = jax.make_jaxpr(fn)(*args)
    return {str(e.params["name"]): e.params["grid_mapping"].grid
            for e in jaxpr_eqns(jaxpr) if e.primitive.name == "pallas_call"}


def test_the_grid_walks_the_band_and_not_every_major(monkeypatch):
    """Sixteen majors of 16 rows, a window of 5: the forward's last grid
    axis walks the K majors a Q major's band of 20 positions can reach at
    any alignment, three, the backward's inner axis the three Q majors a
    K major's band can reach for each of the group's q-heads; the causal
    kernels walk all sixteen."""
    monkeypatch.setattr(fa, "_RESIDENT_BYTES", 16 * 16 * 4)
    q, k, v = _qkv(256, 4, 2)

    def loss(W):
        return lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, window=W, block_q=16, block_k=16,
            interpret=True))

    win = _grids(jax.grad(loss(5), (0, 1, 2)), q, k, v)
    assert win[prof.FLASH_FWD_WIN] == (1, 4, 16, 3)
    assert win[prof.FLASH_BWD_WIN] == (1, 2, 16, 2 * 3)
    full = _grids(jax.grad(loss(None), (0, 1, 2)), q, k, v)
    assert full[prof.FLASH_FWD] == (1, 4, 16, 16)
    assert full[prof.FLASH_BWD] == (1, 2, 16, 2 * 16)


def test_a_dead_band_step_names_the_block_before_it():
    """The forward's index map at Q major 0, where the band holds one K
    major and the grid two steps: the second step names the first's
    block, so no copy is made, and is not live."""
    band = functools.partial(fa._fwd_band, bq=16, bk=16, nkm=16, window=5)
    assert [int(x) for x in band(0, 0, 0, 0)] == [0, 1]
    assert [int(x) for x in band(0, 1, 0, 0)] == [0, 0]
    assert [int(x) for x in band(3, 0, 0, 0)] == [2, 1]
    assert [int(x) for x in band(3, 1, 0, 0)] == [3, 1]
    assert fa._band_steps(16, 16, 5, 16) == 3    # 20 positions from 15
    assert fa._band_steps(2048, 2048, 512, 8) == 3   # at any alignment


def test_the_live_share_at_the_cells_shape():
    """T 16,384 in tiles of 512 and a window of 512: a Q tile visits its
    own K tile and the one before it, 63 pairs where the causal kernel
    visits 528: 11.9%; the plan's shares are of the whole score
    matrix."""
    share = fa.window_live_share(16384, 16384, 512)
    assert share == pytest.approx((2 * 32 - 1) / (32 * 33 / 2))
    plan = flash_plan(16384, 16384, 128, 2, window=512)
    assert (plan.tile_q, plan.bwd_q, plan.major_q) == (512, 512, 2048)
    assert plan.causal_share == pytest.approx(63 / 32 ** 2)
    assert computed_share(16384, 16384, 512, 512, window=16384) == \
        computed_share(16384, 16384, 512, 512)


def test_the_live_share_is_recorded_as_the_kernel_is_built():
    prof.clear()
    q, k, v = _qkv(64, 2, 1)
    flash_attention(q, k, v, causal=True, window=8, block_q=8, block_k=8,
                    interpret=True)
    _, counters = prof.snapshot()
    count, total = counters[prof.FLASH_WIN_LIVE_SHARE]
    assert count == 1 and total == pytest.approx(
        fa.window_live_share(64, 64, 8, 8, 8))


# ------------------------------------------------ window=None: as before
def test_without_a_window_the_plan_is_as_before():
    for shape in ((1024, 1024, 64, 2), (8192, 8192, 128, 2),
                  (8192, 8192, 192, 2)):
        assert flash_plan(*shape, window=None) == flash_plan(*shape)


def _tpu_program(fn, *structs) -> str:
    """The program as traced for the TPU backend, the kernels' own jaxprs,
    grids and index maps inside it, with functions' addresses taken out
    (the Mosaic text a lowering holds carries locations that differ from
    one lowering of the same function to the next)."""
    import re
    import unittest.mock as mock
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        text = str(jax.make_jaxpr(fn)(*structs))
    return re.sub(r" at 0x[0-9a-f]+", "", text)


@pytest.mark.parametrize("H, Hk, D", [(25, 25, 64), (8, 2, 128)])
def test_without_a_window_the_kernels_trace_to_the_same_program(H, Hk, D):
    """For the TPU backend, the causal layer's forward and backward with
    ``window=None`` are the program they are without the argument, and a
    window makes kernels of their own names."""
    q = jax.ShapeDtypeStruct((1, 1024, H, D), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 1024, Hk, D), jnp.bfloat16)

    def grad(**kw):
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=True, **kw).astype(jnp.float32)), (0, 1, 2))

    plain = _tpu_program(grad(), q, k, k)
    assert prof.FLASH_FWD in plain and prof.FLASH_BWD in plain
    assert _tpu_program(grad(window=None), q, k, k) == plain
    win = _tpu_program(grad(window=128), q, k, k)
    assert prof.FLASH_FWD_WIN not in plain and prof.FLASH_BWD_WIN in win
