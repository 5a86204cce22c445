"""The flash kernels compiled for a DESCRIBED TPU v5e chip at the benchmark
cells' head shapes: no chip is attached and nothing runs, but the chip's
own compiler is what accepts or refuses a kernel's VMEM (the backward keeps
a group's whole dQ there, under ``vmem_limit_bytes``), its tiling and its
slices, which the Pallas interpreter cannot. All such compiles live in this
one file: the process that describes the topology holds the TPU library
until it exits."""

import jax
import jax.numpy as jnp
import pytest

from jax.sharding import SingleDeviceSharding
from minips_tpu.ops import flash_attention as fa
from minips_tpu.utils import profiling as prof


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: every later run would warn
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


# (B, T, q-heads, kv heads, D, Dv) as the cells run them; B cut to 1 where
# it only repeats the grid
CELLS = {
    "gpt2-xl": (1, 1024, 25, 25, 64, 64),
    "zaya1-8b": (1, 8192, 8, 2, 128, 128),
    "joyai-llm-flash": (1, 8192, 32, 32, 192, 128),
}


@pytest.mark.parametrize("cell", list(CELLS))
def test_the_kernels_compile_for_the_v5e_at_a_cells_shape(
        cell, one_chip, no_compile_cache):
    """Forward and the one backward kernel, causal, bfloat16, the plan's
    own tiles: the compiled text holds ``flash_fwd`` and ``flash_bwd`` and
    nothing of the pair the backward replaced."""
    B, T, H, Hk, D, Dv = CELLS[cell]
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=one_chip)
               for s in ((B, T, H, D), (B, T, Hk, D), (B, T, Hk, Dv)))

    def loss(q, k, v):
        return jnp.sum(fa._flash(q, k, v, True, D ** -0.5, None, None,
                                 False).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, k, v).compile(
        ).as_text()
    for name, there in ((prof.FLASH_FWD, True), (prof.FLASH_BWD, True),
                        ("flash_dq", False), ("flash_dkv", False)):
        assert (name in text) == there
