"""The flash kernels compiled for a DESCRIBED TPU v5e chip at the benchmark
cells' head shapes: no chip is attached and nothing runs, but the chip's
own compiler is what accepts or refuses a kernel's VMEM (the backward keeps
a group's whole dQ there, under ``vmem_limit_bytes``), its tiling and its
slices, which the Pallas interpreter cannot. And the fused PS step compiled
for the four chips of the described host: which collectives the chip's
compiler builds for the pull and the push is in the compiled text alone.
All such compiles live in this one file: the process that describes the
topology holds the TPU library until it exits."""

import re

import jax
import jax.numpy as jnp
import pytest

from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P
from minips_tpu.ops import flash_attention as fa
from minips_tpu.parallel.mesh import DATA_AXIS, make_mesh
from minips_tpu.tables.dense import DenseTable
from minips_tpu.utils import profiling as prof


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # no TPU compiler here, or another holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
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


# ------------------------------------------- the fused PS step's collectives
def _compiled_step(topo, shards: int, comm: str):
    """``(table, compiled text)`` of a small fused step for ``shards`` of
    the described chips: the table is built on as many CPU devices (its
    own constructor decides the padding), then stands on the described
    mesh, where only shapes can be handed in."""
    def grad_fn(p, b):
        def loss(p):
            return jnp.mean(
                (b["x"] @ p["w"] + jnp.sum(p["b"]) - b["y"]) ** 2)
        return jax.value_and_grad(loss)(p)

    t = DenseTable({"w": jnp.zeros(3000), "b": jnp.zeros(7)},
                   make_mesh(shards, devices=jax.devices()[:shards]),
                   updater="adam", lr=0.1)
    t.mesh = make_mesh(shards, devices=topo.devices[:shards])

    def on(spec):
        return NamedSharding(t.mesh, spec)

    def shape_on(x, spec):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=on(spec))

    opt = jax.tree.map(shape_on, t.opt_state, t._opt_specs)
    batch = {"x": jax.ShapeDtypeStruct((8, 3000), jnp.float32,
                                       sharding=on(P(DATA_AXIS))),
             "y": jax.ShapeDtypeStruct((8,), jnp.float32,
                                       sharding=on(P(DATA_AXIS)))}
    step = t.make_step(grad_fn, compute_dtype=jnp.bfloat16, comm=comm)
    return t, step.lower(shape_on(t.params, P(DATA_AXIS)), opt,
                         batch).compile().as_text()


_COLLECTIVE = re.compile(
    r" (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")


@pytest.mark.parametrize("comm", ["float32", "bfloat16"])
def test_the_pull_of_a_sharded_table_compiles_to_an_all_gather(
        comm, topo, no_compile_cache):
    """3,007 keys over four chips: shards of 1,024, so that the pull is
    the ``all-gather`` the step asks for. (A shard of 752, the length
    divided by four, is what the compiler gathers as an ``all-reduce`` of
    zero-padded shards in the workers' dtype, twice the bytes on the
    wire.) The push of ``comm`` float32 stays the all-reduce this compiler
    builds for a ``psum_scatter``; the compressed tier's is an
    all-to-all."""
    t, text = _compiled_step(topo, 4, comm)
    assert t.padded == 4096
    lines = [ln for ln in text.splitlines() if _COLLECTIVE.search(ln)]
    assert any(re.search(r"= bf16\[4096\]\S* all-gather\(", ln)
               for ln in lines), lines
    assert not any(re.search(r"bf16\[\d+\]\S* all-reduce\(", ln)
                   for ln in lines), lines
    pushes = [ln for ln in lines if "f32[4096]" in ln and "all-reduce(" in ln]
    assert len(pushes) == (comm == "float32"), lines
    assert any("all-to-all(" in ln for ln in lines) == (comm == "bfloat16")


@pytest.mark.parametrize("comm", ["float32", "bfloat16"])
def test_a_table_on_one_shard_compiles_to_no_collective(
        comm, topo, no_compile_cache):
    t, text = _compiled_step(topo, 1, comm)
    assert t.padded == t.num_keys == 3007
    assert not [ln for ln in text.splitlines() if _COLLECTIVE.search(ln)]


def test_the_steps_account_places_what_the_chips_compiler_built(
        topo, no_compile_cache):
    """The text the chip's compiler writes for a step over four chips,
    through the rule the step's own account uses
    (``trace_analysis.instruction_phases``): the pull's all-gather by its
    scope; the push's all-reduce, which the compiler leaves without an
    ``op_name`` and whose scatter it fuses into Adam, and the shard's cast
    before the gather, which reads the step's arguments alone, by their
    neighbours; all four PS phases are there."""
    from minips_tpu.utils import profiling as prof
    from minips_tpu.utils.comm_analysis import collective_ops
    from minips_tpu.utils.trace_analysis import instruction_phases

    _, text = _compiled_step(topo, 4, "float32")
    placed = instruction_phases(text)
    assert {v.ps_phase for v in placed.values()} >= set(prof.PS_PHASES)
    (pull,) = [placed[op.name] for op in collective_ops(text)
               if op.kind == "all-gather"]
    assert (pull.ps_phase, pull.how) == (prof.PULL, "scope")
    # one variadic all-reduce: the gradient with the loss's mean beside
    # it, its result a tuple in the chip's layouts
    (push,) = [placed[op.name] for op in collective_ops(text)
               if op.kind == "all-reduce" and op.has_dim(4096)]
    assert (push.ps_phase, push.how) == (prof.PUSH, "neighbours")
    casts = [v for n, v in placed.items() if n.startswith("convert")
             and v.how == "neighbours"]
    assert casts and {v.ps_phase for v in casts} <= set(prof.PS_PHASES)
