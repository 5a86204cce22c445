"""All-to-all (Ulysses-style) sequence parallelism — ring attention's twin.

The reference has no attention anywhere (SURVEY.md §2.2, §5.7); like
parallel/ring_attention.py this is deliberately beyond parity — the brief
names BOTH long-context strategies ("ring attention or all-to-all
sequence/context parallelism"), and they trade differently on TPU:

- **ring**: K/V shards rotate over ``ppermute`` (N-1 ICI hops), attention
  is blockwise-online per hop; per-device memory O(T/N) for scores AND
  K/V. Wins when T is huge (K/V never materialize whole) or heads < N.
- **all-to-all** (DeepSpeed-Ulysses lineage, PAPERS.md — public recipe,
  reimplemented): ONE ``all_to_all`` re-shards [B, T/N, H, D] from
  sequence-sharded to head-sharded-full-sequence [B, T, H/N, D], each
  device runs a completely LOCAL causal attention over the full sequence
  for its head group (any single-device impl — including the fused flash
  kernel at full MXU rate, with none of the ring's per-hop bookkeeping),
  and one ``all_to_all`` brings the output back. Two collectives per
  attention regardless of N; needs ``heads % N == 0`` and K/V whole on
  each device (memory O(T·H/N) for K/V — fine until T is extreme).

RoPE composes for free: the rotation is per-row by GLOBAL position and is
applied to the sequence-sharded q/k BEFORE the exchange (each shard knows
its global offset), so the reassembled sequence arrives already rotated.

GQA: if ``kv_heads % N == 0`` the K/V exchange carries only the small kv
head count and the local attention expands groups locally (the cheap
case); otherwise K/V are expanded to the full head count BEFORE the
exchange — correct but the wire grows by the group factor, so prefer
``kv_heads`` divisible by the mesh axis (loudly documented, not hidden).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from minips_tpu.ops.flash_attention import _expand_kv
from minips_tpu.parallel.mesh import DATA_AXIS
from minips_tpu.parallel.ring_attention import reference_attention


def a2a_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = DATA_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
    inner: Optional[Callable] = None,
) -> jnp.ndarray:
    """Per-shard body — call INSIDE shard_map with the sequence axis of
    q/k/v ([B, T_local, H, D]) sharded along ``axis_name``. Returns the
    same sequence-sharded layout, exactly equal to full attention on the
    gathered sequence.

    ``inner(q, k, v, causal=..., scale=...)`` is the single-device
    attention run on the head-sharded full sequence ([B, T, H/N, D]);
    ``causal``/``scale`` are ALWAYS threaded into it (a custom inner
    must not silently run with its own defaults while the caller's
    kwargs are dropped). Default inner is the f32 reference; pass
    ``ops.flash_attention.flash_attention`` for full fused-kernel rate.
    """
    n = jax.lax.axis_size(axis_name)
    H, Hk = q.shape[2], k.shape[2]
    if H % n:
        raise ValueError(
            f"a2a sequence parallelism needs heads ({H}) divisible by "
            f"the '{axis_name}' axis size ({n}) — head-group sharding")
    if Hk % n:
        # MQA/GQA with fewer kv heads than devices: expand before the
        # exchange (wire grows to H; the divisible case ships only Hk)
        k, v = _expand_kv(q, k, v)
    if inner is None:
        inner = reference_attention

    def to_heads(x):   # [B, T/N, h, D] -> [B, T, h/N, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    out = inner(to_heads(q), to_heads(k), to_heads(v), causal=causal,
                scale=scale)
    # [B, T, H/N, D] -> [B, T/N, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1,
                              concat_axis=2, tiled=True).astype(q.dtype)
