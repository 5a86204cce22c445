"""Pallas TPU kernels for the sparse embedding hot path (SURVEY.md §7.4.2).

The sparse PS traffic is row gather (pull) and row update (push) against a
``[num_slots, dim]`` table. The survey's stance is "pallas kernel only if
profiling demands" — this module is that profiling, plus the kernel:

``gather_rows`` is a hand-scheduled embedding gather: slot ids are scalar-
prefetched into SMEM, the table stays in HBM (``pl.ANY`` — never copied),
and each grid step issues per-row async DMAs straight from ``emb[slot]``
into its VMEM output block; Pallas pipelines output write-back across grid
steps. This is the canonical TPU embedding-lookup pattern (double-buffered
row DMA), usable when ``dim % 128 == 0`` (lane width) and ``n % 8 == 0``.

Measured on the one real chip in this sandbox (2026-07-29, jax 0.9):

    gather  S=2^18 D=128 N=65536:  pallas-dma ~4.9ms   xla ~2.3ms
    gather  S=2^18 D=8   N=425984: pallas fails to lower (tiny lanes)
    row-blocked BlockSpec variant:  rejected (blocks must tile (8,128))

XLA's native gather wins on this toolchain — its scatter/gather emitter
already overlaps HBM reads — so **SparseTable keeps XLA by default** and
the kernel is opt-in via ``MINIPS_PALLAS=1`` or
``SparseTable(..., use_pallas=True)``, and only on single-device meshes
(pallas_call has no GSPMD partitioning rule — on a sharded table it would
replicate the whole embedding matrix to every chip, defeating the
sharding). Kept in-tree with its tests because the DMA scheduling is the
foundation for the quantized / fused variants (SNIPPETS.md EQuARX-style)
where hand scheduling does pay; honest accounting beats dead weight.

Scatter (push) stays on XLA: a Pallas in-place row update would need
read-modify-write DMA fencing between grid steps that touch the same row;
after dedup (ops.sparse_update.dedup_segment_sum) rows are unique so the
hazard vanishes, but with gather already slower there is no case for it.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from minips_tpu.utils import profiling as prof

_CHUNK = 8  # rows per grid step = output sublane tile


def backend_supported() -> bool:
    """The compiled (non-interpret) kernels use pltpu primitives — TPU only;
    off-TPU they exist solely in interpret mode (tests)."""
    return jax.default_backend() == "tpu"


def pallas_enabled() -> bool:
    """Opt-in switch consulted by SparseTable (see module docstring)."""
    return backend_supported() and os.environ.get("MINIPS_PALLAS", "") == "1"


def gather_supported(dim: int, n: int) -> bool:
    return dim % 128 == 0 and n % _CHUNK == 0


def _gather_kernel(slots_ref, emb_ref, out_ref, sems):
    i = pl.program_id(0)
    # start all row DMAs for this block, then drain — overlap within the
    # block; across blocks the grid pipeline overlaps write-back.
    for k in range(_CHUNK):
        pltpu.make_async_copy(
            emb_ref.at[slots_ref[i * _CHUNK + k]],
            out_ref.at[k], sems.at[k]).start()
    for k in range(_CHUNK):
        pltpu.make_async_copy(
            emb_ref.at[slots_ref[i * _CHUNK + k]],
            out_ref.at[k], sems.at[k]).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def gather_rows(emb: jnp.ndarray, slots: jnp.ndarray,
                interpret: bool = False) -> jnp.ndarray:
    """``emb[slots]`` via scalar-prefetch + per-row HBM→VMEM DMA.

    emb: [S, D] with D % 128 == 0; slots: [N] int32, N % 8 == 0. Whoever
    calls this asked for the kernel: a shape it cannot take, or a compiled
    call off TPU, raises — callers that want XLA's gather for such inputs
    choose it themselves (``SparseTable.pull`` does).
    """
    slots = slots.reshape(-1).astype(jnp.int32)
    n, d = slots.shape[0], emb.shape[1]
    if not gather_supported(d, n):
        raise ValueError(
            f"gather_rows needs dim % 128 == 0 and n % {_CHUNK} == 0, got "
            f"dim={d}, n={n}")
    if not interpret and not backend_supported():
        # pltpu primitives fail Mosaic lowering anywhere else; interpret
        # mode runs anywhere
        raise RuntimeError(
            "gather_rows: the compiled kernel needs a TPU backend, found "
            f"{jax.default_backend()!r} (tests pass interpret=True)")
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(n // _CHUNK,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],  # table stays in HBM
        out_specs=pl.BlockSpec((_CHUNK, d), lambda i, s: (i, 0)),
        scratch_shapes=[pltpu.SemaphoreType.DMA((_CHUNK,))],
    )
    return pl.pallas_call(
        _gather_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, d), emb.dtype),
        interpret=interpret,
        name=prof.GATHER_ROWS,
    )(slots, emb)
