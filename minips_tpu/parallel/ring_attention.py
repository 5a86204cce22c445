"""Ring attention — sequence-parallel exact attention over the device mesh.

The reference has no attention and no sequence dimension anywhere (SURVEY.md
§2.2, §5.7) — this module is deliberately beyond parity: it makes
long-context sequence parallelism a first-class capability of the rebuild so
the mesh design is demonstrably not precluding it.

Mechanics (blockwise ring attention, cf. PAPERS.md lineage: Liu et al.,
"Ring Attention with Blockwise Transformers"): the sequence axis of Q/K/V is
sharded across the mesh's ``data`` axis; each device keeps its Q shard
resident and the K/V shards rotate around the ring with
``jax.lax.ppermute`` (one ICI hop per step, N-1 steps on an N-way ring).
Attention is accumulated with the numerically-stable online softmax (running
max ``m``, normalizer ``l``, accumulator ``o``) so the result is EXACT —
identical to full attention on the gathered sequence, but with O(T/N)
per-device memory instead of O(T). XLA overlaps the ppermute of step s+1's
K/V with the matmuls of step s (both live inside one fori_loop body).

Causal masking is resolved from *global* positions: Q rows on device ``r``
cover ``[r*Tq, (r+1)*Tq)``; after ``s`` ring hops a device holds the K/V
shard originally owned by ring neighbour ``(r - s) mod N``. Whole-block
skips (fully-masked K blocks in the causal case) still compute — on TPU a
predicated skip would break the static schedule — but contribute zeros.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from minips_tpu.parallel.mesh import DATA_AXIS
# GQA head expansion shared with the kernel module (ONE implementation of
# the repeat + divisibility check). NOTE: under ring attention the repeat
# happens AFTER each shard arrives, so the ppermute wire still carries
# only the small kv heads.
from minips_tpu.ops.flash_attention import _expand_kv

_NEG_INF = -1e30  # mask value; avoids -inf NaNs in (m - m_new) when a whole
                  # row is masked at an early ring step


def _online_block(o, m, l, q, k, v, mask, scale):
    """Fold one K/V block into the (o, m, l) online-softmax state.

    q: [T_q, H, D]; k/v: [T_k, H, D]; mask: [T_q, T_k] bool or None.
    o: [T_q, H, D]; m, l: [T_q, H] — all f32: the online-softmax state
    accumulates in f32 whatever the input dtype. With bf16 inputs the
    QK^T einsum keeps bf16 operands (f32 accumulation); the PV einsum
    still runs f32 because p is f32 — only the fused kernel casts p back
    down for full bf16-rate attention.
    """
    # scores [T_q, T_k, H] — batched over heads via einsum (MXU-shaped)
    s = jnp.einsum("qhd,khd->qkh", q, k,
                   preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[:, :, None], s, _NEG_INF)
    m_blk = jnp.max(s, axis=1)                        # [T_q, H]
    m_new = jnp.maximum(m, m_blk)
    # guard: rows with every key masked so far keep m at -inf-ish; exp(0)=1
    # would pollute l, so clamp the correction to 0 there via the mask value
    p = jnp.exp(s - m_new[:, None, :])                # [T_q, T_k, H]
    if mask is not None:
        p = jnp.where(mask[:, :, None], p, 0.0)
    alpha = jnp.exp(m - m_new)                        # [T_q, H]
    l = l * alpha + jnp.sum(p, axis=1)
    o = o * alpha[:, :, None] + jnp.einsum("qkh,khd->qhd", p, v)
    return o, m_new, l


def ring_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str = DATA_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Per-shard body — call INSIDE shard_map with the sequence axis sharded
    along ``axis_name``.

    q/k/v: [B, T_local, H, D] local sequence shards. Returns [B, T_local,
    H, D] attention output, exactly equal to softmax(QK^T)V over the full
    gathered sequence.
    """
    n = jax.lax.axis_size(axis_name)
    r = jax.lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = D ** -0.5

    perm = [(i, (i + 1) % n) for i in range(n)]

    def block_mask(step):
        """[Tq, Tk] bool mask of this ring step's K block, or None.
        Pure jnp arithmetic on the (possibly traced) step index, so it
        works inside fori_loop."""
        if not causal:
            return None
        src = (r - step) % n                      # original owner of k block
        q_pos = r * Tq + jnp.arange(Tq)
        k_pos = src * Tk + jnp.arange(Tk)
        return q_pos[:, None] >= k_pos[None, :]

    def body(step, carry):
        o, m, l, k_cur, v_cur = carry
        mask = block_mask(step)
        # GQA: expand the VISITING shard only — the rotating carry (and so
        # the ppermute wire) stays at the small kv head count
        k_exp, v_exp = _expand_kv(q, k_cur, v_cur)
        o, m, l = jax.vmap(
            lambda o_, m_, l_, q_, k_, v_: _online_block(
                o_, m_, l_, q_, k_, v_, mask, scale)
        )(o, m, l, q, k_exp, v_exp)
        # rotate K/V one hop for the next step (last rotation is redundant
        # but keeps the loop body uniform; XLA overlaps it with the matmuls)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return o, m, l, k_nxt, v_nxt

    # f32 carries regardless of input dtype — the running max/normalizer/
    # accumulator must not round at bf16 across ring steps
    o = jnp.zeros(q.shape, jnp.float32)
    # fresh arrays are axis-invariant; mark them varying over the ring axis
    # so the fori_loop carry type stays fixed (shard_map VMA tracking)
    m = jax.lax.pcast(jnp.full((B, Tq, H), _NEG_INF, jnp.float32),
                      axis_name, to="varying")
    l = jax.lax.pcast(jnp.zeros((B, Tq, H), jnp.float32),
                      axis_name, to="varying")
    o = jax.lax.pcast(o, axis_name, to="varying")

    o, m, l, _, _ = jax.lax.fori_loop(0, n, body, (o, m, l, k, v))

    return (o / jnp.maximum(l, 1e-30)[:, :, :, None]).astype(q.dtype)


def make_ring_attention(
    mesh: Mesh,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    axis_name: str = DATA_AXIS,
):
    """Jitted sequence-parallel attention: [B, T, H, D] global arrays with T
    sharded over ``axis_name``; output sharded the same way."""
    spec = P(None, axis_name)

    @jax.jit
    def attn(q, k, v):
        f = functools.partial(ring_attention_local, axis_name=axis_name,
                              causal=causal, scale=scale)
        return jax.shard_map(
            f, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)(q, k, v)

    def sharded(x):
        return jax.device_put(x, NamedSharding(mesh, spec))

    attn.shard = sharded  # type: ignore[attr-defined]
    return attn


def reference_attention(q, k, v, *, causal=False, scale=None):
    """O(T^2)-memory oracle for tests: plain softmax(QK^T)V. Scores and
    softmax run in f32 whatever the input dtype; output is q.dtype.
    K/V with fewer heads (GQA) are repeated up to Q's head count."""
    D = q.shape[-1]
    k, v = _expand_kv(q, k, v)
    if scale is None:
        scale = D ** -0.5
    s = jnp.einsum("bqhd,bkhd->bqkh", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        T, S = q.shape[1], k.shape[1]
        mask = jnp.arange(T)[:, None] >= jnp.arange(S)[None, :]
        s = jnp.where(mask[None, :, :, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=2)
    return jnp.einsum("bqkh,bkhd->bqhd", p, v.astype(jnp.float32)
                      ).astype(q.dtype)
