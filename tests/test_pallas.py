"""Pallas gather kernel — interpret-mode correctness (SURVEY.md §4: the
TPU-free test story; compiled-mode numbers live in ops/pallas_kernels.py's
docstring, measured on the real chip)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from minips_tpu.ops import pallas_kernels as pk


@pytest.fixture
def rng():
    return np.random.default_rng(7)


def test_gather_matches_xla(rng):
    S, D, N = 512, 128, 64
    emb = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, S, N), jnp.int32)
    out = pk.gather_rows(emb, slots, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(emb)[np.asarray(slots)], rtol=1e-6)


def test_gather_repeated_and_boundary_rows(rng):
    S, D = 256, 128
    emb = jnp.asarray(rng.normal(size=(S, D)), jnp.float32)
    slots = jnp.asarray([0, 0, S - 1, S - 1, 3, 3, 0, S - 1], jnp.int32)
    out = pk.gather_rows(emb, slots, interpret=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(emb)[np.asarray(slots)], rtol=1e-6)


def test_unsupported_shapes_raise(rng):
    # D=8 (not lane-aligned) and N=7 (not chunk-aligned): whoever calls
    # gather_rows asked for the kernel, so it refuses instead of quietly
    # returning XLA's gather
    emb = jnp.asarray(rng.normal(size=(64, 8)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, 64, 7), jnp.int32)
    assert not pk.gather_supported(8, 56)    # lane-misaligned dim
    assert not pk.gather_supported(128, 7)   # chunk-misaligned n
    with pytest.raises(ValueError, match="dim=8, n=7"):
        pk.gather_rows(emb, slots, interpret=True)


def test_compiled_kernel_refuses_off_tpu(rng):
    # aligned shapes (D=128, N=64) with interpret=False: on this CPU test
    # session the compiled pltpu kernel can't lower — a named error, not
    # the XLA path under the kernel's name
    assert pk.gather_supported(128, 64)
    assert not pk.backend_supported()
    emb = jnp.asarray(rng.normal(size=(256, 128)), jnp.float32)
    slots = jnp.asarray(rng.integers(0, 256, 64), jnp.int32)
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        pk.gather_rows(emb, slots)


def test_opt_in_is_off_by_default_and_off_tpu(monkeypatch):
    assert not pk.pallas_enabled()  # default: no env flag
    monkeypatch.setenv("MINIPS_PALLAS", "1")
    # CPU test session: still disabled (TPU-only switch)
    assert not pk.pallas_enabled()
