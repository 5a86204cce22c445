"""Flash attention — fused blockwise causal attention for the LM family.

The reference has no attention at all (SURVEY.md §2.2: LR/MLP/MF/W&D/w2v);
the LM/transformer family is this rebuild's beyond-parity long-context
capability, and this module is its single-chip hot op. Two implementations
of the same exact math (softmax(QK^T)V, never materializing the [T, T]
score matrix in HBM):

- ``blockwise_attention`` — pure jnp, ``lax.scan`` over K/V chunks with
  online-softmax carry. Runs anywhere, differentiable by AD through the
  scan, O(T·block_k) live scores. This is the kernels' platform twin: what
  ``flash_attention`` runs OFF a TPU backend, and the tests' oracle. On a
  TPU backend ``flash_attention`` runs the compiled kernels or raises —
  never this scan.

- ``flash_attention`` — Pallas TPU kernels. Forward: grid (batch, head,
  Q blocks, K blocks) with the K sweep innermost; the float32 online-
  softmax state (running max m, normalizer l, accumulator acc) lives in
  VMEM scratch across the sweep, blocks are pipelined HBM→VMEM by Pallas,
  scores exist only in VMEM, and the per-row logsumexp is written out for
  the backward. Backward (``jax.custom_vjp``): two kernels that recompute
  p = exp(s − lse) per block — dQ accumulates over the K sweep, dK/dV over
  the transposed Q sweep — so training memory stays O(T) and the [T, T]
  matrix never exists in either pass. Causal runs skip fully-masked blocks
  in all three kernels.

Measured on the one real chip here (2026-07-29, bf16, B=2 H=8 D=64,
T=8192): forward 5.8ms vs 12.4ms XLA full-scores; fwd+bwd 21ms vs 40ms;
end-to-end LM training (apps/lm_example --attn flash) 1.5x tokens/sec at
T=8192, and T=32768 works where full scores OOM HBM.

Layout matches the rest of the stack: q/k/v are ``[B, T, H, D]`` (the
ring-attention convention, parallel/ring_attention.py). The kernel wants
the sequence contiguous per (batch, head), so it transposes to
``[B, H, T, D]`` at the jit boundary — XLA fuses the transposes into the
surrounding program.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from minips_tpu.utils import profiling as prof

_NEG_INF = -1e30  # finite mask value (matches ring_attention) — avoids
                  # -inf arithmetic NaNs on fully-masked rows


def _pcast_varying(x, axes):
    """pcast x to varying over exactly the axes it isn't already varying
    over (pcast rejects varying→varying)."""
    have = jax.typeof(x).vma
    need = tuple(a for a in axes if a not in have)
    return jax.lax.pcast(x, need, to="varying") if need else x


def gqa_group_size(num_q_heads: int, num_kv_heads: int) -> int:
    """Q-heads per KV head (grouped-query attention). 1 = classic MHA,
    num_q_heads = MQA. Raises unless kv divides q."""
    if num_q_heads % num_kv_heads:
        raise ValueError(
            f"GQA needs kv_heads ({num_kv_heads}) to divide q heads "
            f"({num_q_heads})")
    return num_q_heads // num_kv_heads


def _expand_kv(q, k, v):
    """Repeat K/V heads up to Q's head count for the pure-jnp paths.
    This forfeits GQA's memory saving (it exists only for oracle/twin
    exactness off-TPU); the Pallas kernels instead map each q-head's
    block index onto its kv head and never materialize the repeat."""
    g = gqa_group_size(q.shape[2], k.shape[2])
    if g == 1:
        return k, v
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


# --------------------------------------------------------------- blockwise
def blockwise_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_k: int = 256,
    q_off=0,
    k_off=0,
    return_lse: bool = False,
):
    """Exact attention, scanning K/V in chunks of ``block_k``.

    q/k/v: [B, T, H, D]. Equals softmax(QK^T·scale)V to float tolerance;
    peak score memory is [B, Tq, block_k, H] instead of [B, Tq, Tk, H].
    Ragged K tails are padded and masked, preserving that bound.

    ``q_off``/``k_off`` shift causal masking to global positions (the ring
    path passes each shard's sequence offset); ``return_lse=True`` also
    returns the per-row logsumexp [B, Tq, H] for shard merging. This is
    the pure-jnp twin of the Pallas kernels.
    """
    B, Tq, H, D = q.shape
    k, v = _expand_kv(q, k, v)   # GQA: exact repeat on this oracle path
    Tk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    bk = min(block_k, Tk)
    pad = (-Tk) % bk  # ragged tail: pad K/V and mask — never one full-width
    if pad:           # chunk, which would void the O(T*block_k) bound
        zeros = jnp.zeros((B, pad, H, D), k.dtype)
        k = jnp.concatenate([k, zeros], axis=1)
        v = jnp.concatenate([v, zeros], axis=1)
    masked = causal or pad
    nk = (Tk + pad) // bk
    qf = q.astype(jnp.float32)
    kc = k.astype(jnp.float32).reshape(B, nk, bk, H, D)
    vc = v.astype(jnp.float32).reshape(B, nk, bk, H, D)
    q_pos = q_off + jnp.arange(Tq)

    def fold(carry, blk):
        o, m, l = carry
        k_blk, v_blk, j = blk
        s = jnp.einsum("bqhd,bkhd->bqkh", qf, k_blk) * scale
        if masked:
            k_local = j * bk + jnp.arange(bk)
            keep = k_local[None, :] < Tk  # padding keys attend to nothing
            if causal:
                keep = keep & (q_pos[:, None] >= (k_off + k_local)[None, :])
            s = jnp.where(keep[None, :, :, None], s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=2))        # [B, Tq, H]
        p = jnp.exp(s - m_new[:, :, None, :])
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=2)
        o = o * alpha[:, :, :, None] + jnp.einsum("bqkh,bkhd->bqhd", p, v_blk)
        return (o, m_new, l), None

    o0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    m0 = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Tq, H), jnp.float32)
    # Inside shard_map, fresh carries are axis-invariant while the folded
    # values vary over the mesh — pcast keeps the scan carry type fixed
    # (same VMA discipline as ring_attention_local).
    vma = tuple(sorted(_vma_of(q, k, v, q_off, k_off)))
    o0, m0, l0 = (_pcast_varying(x, vma) for x in (o0, m0, l0))
    (o, m, l), _ = jax.lax.scan(
        fold, (o0, m0, l0),
        (kc.swapaxes(0, 1), vc.swapaxes(0, 1), jnp.arange(nk)))
    l_safe = jnp.maximum(l, 1e-30)
    out = (o / l_safe[..., None]).astype(q.dtype)
    if return_lse:
        return out, m + jnp.log(l_safe)
    return out


# ----------------------------------------------------------- pallas kernel
#
# All three kernels mask by GLOBAL positions: row q_off + (local index),
# col k_off + (local index). Plain causal attention passes offsets (0, 0);
# ring flash attention (ring_flash_attention_local) passes each shard's
# sequence offsets so the same kernels compute the diagonal, kept, and
# fully-masked ring steps. Offsets arrive as (1,) int32 arrays in SMEM.

def _mask_scores(s, masked, i, j, bq, bk, q_off, k_off):
    if not masked:
        return s
    q_pos = q_off + i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = k_off + j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _block_live(masked, i, j, bq, bk, q_off, k_off):
    """False only for blocks that the global causal mask kills entirely —
    skip their matmuls (the block DMA still happens; compute dominates)."""
    if not masked:
        return True
    return k_off + j * bk <= q_off + (i + 1) * bq - 1


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  acc_ref, m_ref, l_ref, *, scale, masked, num_k):
    # Grid (B, H, nQ, nK), K innermost and sequential on TPU: the online-
    # softmax state for one Q block lives in VMEM scratch across the nK
    # sweep. Blocks: q/o [1, 1, bq, D]; k/v [1, 1, bk, D]; lse [1, 1, bq, 1].
    bq = q_ref.shape[2]
    bk = k_ref.shape[2]
    i = pl.program_id(2)
    j = pl.program_id(3)
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    @pl.when(_block_live(masked, i, j, bq, bk, q_off, k_off))
    def _fold():
        # dots run in the INPUT dtype (bf16 inputs → bf16 MXU rate, half
        # the VMEM traffic) with f32 accumulation; all online-softmax
        # state stays f32. f32 inputs behave exactly as before.
        qb = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, masked, i, j, bq, bk, q_off, k_off)
        m = m_ref[:]
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))  # [bq, 1]
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[:] = (acc_ref[:] * alpha
                      + jnp.dot(p.astype(vb.dtype), vb,
                                preferred_element_type=jnp.float32))
        m_ref[:] = m_new

    @pl.when(j == num_k - 1)
    def _write():
        l_safe = jnp.maximum(l_ref[:], 1e-30)
        o_ref[0, 0, :, :] = (acc_ref[:] / l_safe).astype(o_ref.dtype)
        # true logsumexp per row — the backward recomputes p = exp(s - lse),
        # and the ring merge weights shards by exp(lse_s - lse_total)
        lse_ref[0, 0, :, 0] = (m_ref[:] + jnp.log(l_safe))[:, 0]


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _vma_of(*xs):
    # Inside shard_map the output type must declare which mesh axes it
    # varies over (VMA tracking); it varies exactly where the inputs do.
    vma = frozenset()
    for x in xs:
        vma = vma | jax.typeof(x).vma
    return vma


def _flash_forward(q, k, v, q_off, k_off, masked, scale, block_q, block_k,
                   interpret):
    """[B, T, H, D] in/out; kernel runs on [B, H, T, D]. K/V may carry
    fewer heads (GQA): each q-head's K/V block index maps onto kv head
    h // g — the repeat never materializes, so KV HBM traffic shrinks by
    the group factor."""
    B, Tq, H, D = q.shape
    g = gqa_group_size(H, k.shape[2])
    Tk = k.shape[1]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    grid = (B, H, Tq // bq, Tk // bk)
    vma = _vma_of(q, k, v, q_off, k_off)
    offs = (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, masked=masked,
                          num_k=Tk // bk),
        grid=grid,
        in_specs=[
            _smem_spec(), _smem_spec(),
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // g, j, 0)),
            pl.BlockSpec((1, 1, bk, D),
                         lambda b, h, i, j: (b, h // g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, H, Tq, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq, 1), jnp.float32),   # running max m
            pltpu.VMEM((bq, 1), jnp.float32),   # normalizer l
        ],
        interpret=interpret,
        name=prof.FLASH_FWD,
    )(*offs, qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _flash_bwd_dq_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                         lse_ref, dvec_ref, dq_ref, dq_acc, *, scale,
                         masked, num_k):
    # Grid (B, H, nQ, nK), K innermost; dQ for one Q block accumulates in
    # scratch across the K sweep. p is recomputed from the saved
    # logsumexp — the [T, T] matrix never exists.
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    i, j = pl.program_id(2), pl.program_id(3)
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    @pl.when(_block_live(masked, i, j, bq, bk, q_off, k_off))
    def _fold():
        # native-dtype dots, f32 accumulation/softmax state (see _fold in
        # _flash_kernel); ds is cast back to the input dtype for its dot
        qb = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        dob = do_ref[0, 0, :, :]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, masked, i, j, bq, bk, q_off, k_off)
        p = jnp.exp(s - lse_ref[0, 0, :, :])            # [bq, bk] f32
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0, :, :]) * scale
        dq_acc[:] = dq_acc[:] + jnp.dot(
            ds.astype(kb.dtype), kb, preferred_element_type=jnp.float32)

    @pl.when(j == num_k - 1)
    def _write():
        dq_ref[0, 0, :, :] = dq_acc[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                          lse_ref, dvec_ref, dk_ref, dv_ref, dk_acc,
                          dv_acc, *, scale, masked, num_q, q_per_kv):
    # Grid (B, Hk, nK, q_per_kv*nQ), the combined (group q-head, Q block)
    # sweep innermost; dK/dV for one KV-head K block accumulate in scratch
    # across BOTH — under GQA every kv head receives gradient from all
    # q_per_kv q-heads of its group (the transposed iteration of dq).
    bq, bk = q_ref.shape[2], k_ref.shape[2]
    j, t = pl.program_id(2), pl.program_id(3)   # j: K block
    i = jax.lax.rem(t, num_q)                   # i: Q block within head
    q_off, k_off = qoff_ref[0], koff_ref[0]

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    @pl.when(_block_live(masked, i, j, bq, bk, q_off, k_off))
    def _fold():
        qb = q_ref[0, 0, :, :]
        kb = k_ref[0, 0, :, :]
        vb = v_ref[0, 0, :, :]
        dob = do_ref[0, 0, :, :]
        s = jnp.dot(qb, kb.T, preferred_element_type=jnp.float32) * scale
        s = _mask_scores(s, masked, i, j, bq, bk, q_off, k_off)
        p = jnp.exp(s - lse_ref[0, 0, :, :])            # [bq, bk] f32
        dv_acc[:] = dv_acc[:] + jnp.dot(
            p.T.astype(dob.dtype), dob, preferred_element_type=jnp.float32)
        dp = jnp.dot(dob, vb.T, preferred_element_type=jnp.float32)
        ds = p * (dp - dvec_ref[0, 0, :, :]) * scale
        dk_acc[:] = dk_acc[:] + jnp.dot(
            ds.T.astype(qb.dtype), qb, preferred_element_type=jnp.float32)

    @pl.when(t == num_q * q_per_kv - 1)
    def _write():
        dk_ref[0, 0, :, :] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0, 0, :, :] = dv_acc[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, q_off, k_off, g_out, lse, dvec, masked, scale,
                    block_q, block_k, interpret):
    """dQ/dK/dV via the two backward kernels; [B, T, H, D] layout.
    ``dvec`` is [B, H, Tq, 1] — rowsum(dO*O) minus the lse cotangent.
    Under GQA dk/dv come back at the kv head count."""
    B, Tq, H, D = q.shape
    Hk = k.shape[2]
    g = gqa_group_size(H, Hk)
    Tk = k.shape[1]
    bq = min(block_q, Tq)
    bk = min(block_k, Tk)
    qt, kt, vt, dot = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g_out))
    vma = _vma_of(q, k, v, q_off, k_off, g_out)
    offs = (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))

    q_spec = pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0))
    kv_spec = pl.BlockSpec((1, 1, bk, D),
                           lambda b, h, i, j: (b, h // g, j, 0))
    row_spec = pl.BlockSpec((1, 1, bq, 1), lambda b, h, i, j: (b, h, i, 0))
    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, scale=scale, masked=masked,
                          num_k=Tk // bk),
        grid=(B, H, Tq // bq, Tk // bk),
        in_specs=[_smem_spec(), _smem_spec(),
                  q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, Tq, D), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bq, D), jnp.float32)],
        interpret=interpret,
        name=prof.FLASH_DQ,
    )(*offs, qt, kt, vt, dot, lse, dvec)

    # transposed grid: K outer, (group q-head, Q block) inner — grid dim 1
    # walks KV heads, the q-head within the group rides the inner sweep
    nq = Tq // bq
    q_spec_t = pl.BlockSpec(
        (1, 1, bq, D), lambda b, hk, j, t: (b, hk * g + t // nq, t % nq, 0))
    kv_spec_t = pl.BlockSpec((1, 1, bk, D),
                             lambda b, hk, j, t: (b, hk, j, 0))
    row_spec_t = pl.BlockSpec(
        (1, 1, bq, 1), lambda b, hk, j, t: (b, hk * g + t // nq, t % nq, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, scale=scale,
                          masked=masked, num_q=nq, q_per_kv=g),
        grid=(B, Hk, Tk // bk, g * nq),
        in_specs=[_smem_spec(), _smem_spec(),
                  q_spec_t, kv_spec_t, kv_spec_t, q_spec_t, row_spec_t,
                  row_spec_t],
        out_specs=[kv_spec_t, kv_spec_t],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hk, Tk, D), k.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, Hk, Tk, D), v.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32)],
        interpret=interpret,
        name=prof.FLASH_DKV,
    )(*offs, qt, kt, vt, dot, lse, dvec)
    return (dq.transpose(0, 2, 1, 3), dk.transpose(0, 2, 1, 3),
            dv.transpose(0, 2, 1, 3))


def _int_zero_cotangent(x):
    import numpy as np

    return np.zeros(jnp.shape(x), jax.dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _flash_with_lse(q, k, v, q_off, k_off, masked, scale, block_q, block_k,
                    interpret):
    """Core primitive: (out, lse) with global-offset causal masking.
    The lse output is a first-class differentiable result — the ring merge
    consumes it, so its cotangent must flow (see _flash_with_lse_bwd)."""
    return _flash_forward(q, k, v, q_off, k_off, masked, scale, block_q,
                          block_k, interpret)


def _flash_with_lse_fwd(q, k, v, q_off, k_off, masked, scale, block_q,
                        block_k, interpret):
    out, lse = _flash_forward(q, k, v, q_off, k_off, masked, scale,
                              block_q, block_k, interpret)
    return (out, lse), (q, k, v, q_off, k_off, out, lse)


def _flash_with_lse_bwd(masked, scale, block_q, block_k, interpret, res,
                        gs):
    q, k, v, q_off, k_off, out, lse = res
    g, g_lse = gs
    # ds = p * (dp - rowsum(dO*O) + g_lse): the lse cotangent enters the
    # softmax-jacobian row term with opposite sign to D_i, so both ride
    # the same dvec input of the kernels (d lse / d s_k = p_k).
    dvec = (jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)[..., None]
            - g_lse.astype(jnp.float32))                 # [B, H, Tq, 1]
    dq, dk, dv = _flash_backward(
        q, k, v, q_off, k_off, g, lse, dvec, masked, scale, block_q,
        block_k, interpret)
    return (dq, dk, dv, _int_zero_cotangent(q_off),
            _int_zero_cotangent(k_off))


_flash_with_lse.defvjp(_flash_with_lse_fwd, _flash_with_lse_bwd)


def _flash(q, k, v, causal, scale, block_q, block_k, interpret):
    zero = jnp.zeros((), jnp.int32)
    return _flash_with_lse(q, k, v, zero, zero, causal, scale, block_q,
                           block_k, interpret)[0]


def kernel_supported(q_shape, k_shape, block_q: int, block_k: int) -> bool:
    """Static shape gate for the Pallas path: block sizes must tile the
    sequence (no ragged tails in the kernel) and D should be lane-friendly."""
    B, Tq, H, D = q_shape
    Tk = k_shape[1]
    bq, bk = min(block_q, Tq), min(block_k, Tk)
    if q_shape[2] % k_shape[2]:   # GQA: kv heads must divide q heads
        return False
    return Tq % bq == 0 and Tk % bk == 0 and D % 8 == 0


def _use_kernel(interpret: Optional[bool], q_shape, k_shape, block_q: int,
                block_k: int) -> bool:
    """The one platform rule both entry points share. ``interpret=None``
    (every production caller): the compiled kernels on a TPU backend, the
    blockwise scan — their documented platform twin — anywhere else. An
    explicit ``interpret`` (tests: ``True`` runs the Pallas interpreter)
    always means the kernels. Whoever gets the kernels gets them or a
    ValueError naming the shape ``kernel_supported`` refused: the scan is
    never a silent stand-in for a kernel that was asked for."""
    if interpret is None and jax.default_backend() != "tpu":
        return False
    if not kernel_supported(q_shape, k_shape, block_q, block_k):
        raise ValueError(
            f"flash attention kernels refuse q{tuple(q_shape)} "
            f"k{tuple(k_shape)} at blocks ({block_q}, {block_k}): the "
            "blocks must tile both sequences, kv heads must divide q "
            "heads, and the head dim must be a multiple of 8 — pick "
            "tiling blocks or attn_impl='reference' for this shape")
    return True


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused attention; same signature/semantics as
    ``ring_attention.reference_attention`` but never materializes the full
    score matrix. On a TPU backend: the compiled Pallas kernels, or a
    ValueError for a shape they refuse. Off TPU: the blockwise scan, same
    math (``interpret=True`` runs the kernels in the Pallas interpreter
    anywhere — tests only). See :func:`_use_kernel`.

    Grouped-query attention: K/V may carry fewer heads than Q (kv divides
    q, q-head h reads kv head h // group). The kernel path streams the
    small K/V straight from HBM — traffic and ring wire bytes shrink by
    the group factor; the scan repeats heads (exact, memory-expanded).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _use_kernel(interpret, q.shape, k.shape, block_q, block_k):
        return _flash(q, k, v, causal, scale, block_q, block_k,
                      bool(interpret))
    return blockwise_attention(q, k, v, causal=causal, scale=scale,
                               block_k=block_k)


# -------------------------------------------------------- ring flash attn
def ring_flash_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Ring attention with the flash kernel doing each step's blockwise
    math — call INSIDE shard_map with the sequence axis sharded along
    ``axis_name`` (drop-in for ring_attention.ring_attention_local).

    Each of the N ring steps runs the offset-masked flash kernel on the
    resident Q shard against the visiting K/V shard (global positions via
    q_off/k_off, so diagonal steps are causal, earlier shards fully kept,
    later shards fully skipped) and returns (out_s, lse_s). Shards merge by
    logsumexp weighting — exact attention over the full sequence. Forward
    per-device memory is O(T/N); training stores each step's visiting K/V
    shard as AD residuals (O(T) per device across the n steps) — wrap the
    caller in jax.checkpoint (the LM family's ``remat=True``) to trade
    that back to O(T/N). K/V rotate one ICI hop per step (ppermute); XLA
    overlaps the hop with the kernel. Gradients flow through the kernels'
    custom VJP at every step.
    """
    n = jax.lax.axis_size(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    if scale is None:
        scale = D ** -0.5
    # Pallas path: compiled on TPU (or a ValueError), interpreter only if
    # explicitly asked (it can't track varying-manual-axes, so it only
    # works under check_vma=False — kernel-level tests). Off TPU the
    # per-step math runs as the pure-jnp offset blockwise scan: same
    # algorithm and f32 softmax state, ordinary AD, no pallas involved.
    # Numerics match exactly for f32 inputs; for bf16 inputs the scan
    # upcasts q/k/v to f32 before its dots while the kernel runs
    # bf16-input dots with f32 accumulation (≤ bf16-rounding apart).
    use_kernel = _use_kernel(interpret, q.shape, k.shape, block_q, block_k)
    interpret = bool(interpret)
    perm = [(i, (i + 1) % n) for i in range(n)]
    # With causal=False no step masks, so the global offsets cannot affect
    # the math — and materializing axis_index here would leave an orphaned
    # partition-id in the lowered module (no path to a manual-sharded
    # operand for sharding propagation to infer {manual} from), which the
    # SPMD partitioner rejects. Only mint r when masking consumes it.
    if causal:
        r = jax.lax.axis_index(axis_name)
        q_off = (r * Tq).astype(jnp.int32)
    else:
        r = jnp.zeros((), jnp.int32)
        q_off = jnp.zeros((), jnp.int32)

    def step_fn(carry, s):
        acc, lse_run, k_cur, v_cur = carry
        src = ((r - s) % n).astype(jnp.int32)     # original owner of k_cur
        if use_kernel:
            o_s, lse_s = _flash_with_lse(
                q, k_cur, v_cur, q_off, src * Tk, causal, scale,
                min(block_q, Tq), min(block_k, Tk), interpret)
            lse_s = lse_s[..., 0].transpose(0, 2, 1)   # -> [B, Tq, H]
        else:
            o_s, lse_s = blockwise_attention(
                q, k_cur, v_cur, causal=causal, scale=scale,
                block_k=block_k, q_off=q_off, k_off=src * Tk,
                return_lse=True)
        lse_new = jnp.logaddexp(lse_run, lse_s)
        acc = (acc * jnp.exp(lse_run - lse_new)[..., None]
               + o_s.astype(jnp.float32)
               * jnp.exp(lse_s - lse_new)[..., None])
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, lse_new, k_nxt, v_nxt), None

    acc0 = jnp.zeros((B, Tq, H, D), jnp.float32)
    lse0 = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    # the visiting K/V shards (and, under causal, the axis index r) make
    # every step output vary over the ring axis, so ALL carries must be
    # varying — even when the inputs arrive replicated
    acc0, lse0, k, v = (_pcast_varying(x, (axis_name,))
                        for x in (acc0, lse0, k, v))
    (acc, _, _, _), _ = jax.lax.scan(
        step_fn, (acc0, lse0, k, v), jnp.arange(n))
    return acc.astype(q.dtype)
