"""GPipe-style pipeline parallelism over a mesh axis.

Beyond parity (the reference has no pipeline parallelism, SURVEY.md §2.2).
Mechanics: layer weights are STACKED along a leading depth axis and that
axis is sharded over ``axis_name`` — each device owns ``depth/k``
consecutive layers (one pipeline stage). Microbatches flow stage-to-stage
with ``ppermute`` under one ``lax.scan`` over ``M + k - 1`` ticks (the
GPipe schedule: k-1 bubble ticks); every tick each stage applies its local
layers to whatever activation just arrived. Devices in the bubble compute
on don't-care values that are never read — on TPU a predicated skip would
break the static schedule, so the waste is the standard (k-1)/(M+k-1)
bubble fraction, amortized by more microbatches.

Autodiff: take ``jax.grad`` OUTSIDE the shard_map — scan and ppermute both
transpose, so the backward pipeline (activations flowing in reverse) is
derived automatically; tests prove exact grad parity with the unsharded
model.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp


def gpipe(
    stage_fn: Callable[[jnp.ndarray], jnp.ndarray],
    x_microbatches: jnp.ndarray,
    *,
    axis_name: str,
) -> jnp.ndarray:
    """Run [M, ...] microbatches through the k-stage pipeline.

    ``stage_fn`` must already be bound (via shard_map slicing) to THIS
    device's layers, and must map one microbatch activation [mb, ...] to
    the same shape. Stage 0 consumes ``x_microbatches[t]`` at tick t; the
    last stage's outputs are collected and broadcast, so the return value
    [M, ...] is valid on every device (replicated).
    """
    k = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    M = x_microbatches.shape[0]
    # stage i sends to stage i+1; the wrap edge (k-1 -> 0) carries values
    # stage 0 never reads
    perm = [(i, (i + 1) % k) for i in range(k)]
    # fresh zeros are axis-invariant; the scan carry becomes varying after
    # one tick, so pre-cast both (shard_map VMA tracking)
    out0 = jax.lax.pcast(jnp.zeros_like(x_microbatches), axis_name,
                         to="varying")
    buf0 = jax.lax.pcast(jnp.zeros_like(x_microbatches[0]), axis_name,
                         to="varying")

    def tick(carry, t):
        buf_in, outputs = carry
        mb = jax.lax.dynamic_index_in_dim(
            x_microbatches, jnp.clip(t, 0, M - 1), axis=0, keepdims=False)
        x = jnp.where(idx == 0, mb, buf_in)
        y = stage_fn(x)
        # last stage files microbatch (t - k + 1) when it is in range
        o = t - (k - 1)
        upd = jax.lax.dynamic_update_index_in_dim(
            outputs, y, jnp.clip(o, 0, M - 1), axis=0)
        outputs = jnp.where((o >= 0) & (idx == k - 1), upd, outputs)
        return (jax.lax.ppermute(y, axis_name, perm), outputs), None

    (_, outputs), _ = jax.lax.scan(tick, (buf0, out0),
                                   jnp.arange(M + k - 1))
    # broadcast the last stage's collected outputs to every device
    return jax.lax.psum(
        jnp.where(idx == k - 1, outputs, jnp.zeros_like(outputs)),
        axis_name)


def stack_layers(layers: list) -> dict:
    """Stack a list of identically-structured layer pytrees into one pytree
    with a leading depth axis per leaf — the shardable layout ``gpipe``
    wants (shard dim 0 over the pipeline axis)."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def unstack_layers(stacked: dict) -> list:
    """Inverse of ``stack_layers``."""
    depth = jax.tree.leaves(stacked)[0].shape[0]
    return [jax.tree.map(lambda x: x[i], stacked) for i in range(depth)]
