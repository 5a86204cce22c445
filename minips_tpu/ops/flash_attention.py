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
  Q majors, K majors); a grid step owns a major block of Q rows (the
  whole sequence where a head fits ``_RESIDENT_BYTES``) with the head's
  K/V resident in VMEM, and walks score tiles itself, ``lax.fori_loop``
  inside ``lax.fori_loop``, over the live tiles only: the bounds come
  from the global offsets, tiles under the diagonal skip the mask, tiles
  above it are never visited. Scores are computed transposed (k q^T, Q on
  the lanes), so the float32 online-softmax state (running max m,
  normalizer l: lane-dense rows; accumulator acc^T) is a small loop
  carry; scores exist only in VMEM, and the per-row logsumexp is written
  out for the backward as the rows it is,
  [B, H, Q majors, tiles, tile_q]. Backward (``jax.custom_vjp``): ONE
  kernel, ``flash_bwd``, that owns K rows and sweeps the live Q tiles,
  transposed like the forward. For each tile it recomputes
  p = exp(s − lse) and forms ds once and takes all three gradients from
  them — five score-sized products a tile (s, dp, dV, dK, dQ), seven a
  layer with the forward's two — accumulating in float32 VMEM scratch,
  and computing the tile ON the diagonal as its live 128-wide groups
  only. dK/dV of a K major cross the inner grid axis (the group's
  q-heads and Q majors); the dQ of a kv head's WHOLE group crosses the K
  majors, as [D, tile] tiles (dQ^T += k^T ds is a plain product of the
  transposed scores), and leaves transposed back at the last of them:
  0.25 MiB at GPT-2 XL's head (T 1,024, D 64: a head a grid step), 6 MiB
  at 32 heads of 192 over T 8,192, 16 MiB at 8 q-heads on 2 kv heads of
  128 over T 8,192. The kernel asks Mosaic for ``_VMEM_BYTES`` of the
  chip's 128 MiB; where a group's dQ would not fit that
  (``FlashPlan.span_q`` under the sequence) it runs once a span of Q
  rows and the spans' dK/dV are summed outside. Training memory stays
  O(T) and the [T, T] matrix never exists in either pass. ``flash_plan``
  is the one place tiles, resident extents and spans are chosen, from
  the shape. What the forward leaves for the backward, ``out`` and the
  logsumexp, carries checkpoint names (``profiling.FLASH_RESIDUALS``):
  the kernel is a custom call, which no ``jax.checkpoint`` policy that
  goes by primitive (``checkpoint_dots``) would keep, so a checkpoint
  around a caller ran it a second time for the backward, bit for bit the
  same; a policy that saves both names (every mode of
  ``transformer._remat_policy`` but ``True``) keeps them, and the second
  run, dead code then, is dropped. Outside a checkpoint a name is the
  identity.

Speeds: PERF.md section 5 (the benchmark cells' traces by kernel) and
section 6, PR 27 and PR 34 (what each part of this design brought on the
v5e; the pair of backward kernels that PR 34 made one).

Layout matches the rest of the stack: q/k/v are ``[B, T, H, D]`` (the
ring-attention convention, parallel/ring_attention.py); v may have a head
size of its own, which the output then has (``[B, T, H, Dv]``). The kernel wants
the sequence contiguous per (batch, head), so it transposes to
``[B, H, T, D]`` at the jit boundary — XLA fuses the transposes into the
surrounding program.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from minips_tpu.parallel.mesh import pcast_varying
from minips_tpu.utils import profiling as prof

_NEG_INF = -1e30  # finite mask value (matches ring_attention) — avoids
                  # -inf arithmetic NaNs on fully-masked rows


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
    Tk, Dv = k.shape[1], v.shape[3]   # v's head size is its own
    if scale is None:
        scale = D ** -0.5
    bk = min(block_k, Tk)
    pad = (-Tk) % bk  # ragged tail: pad K/V and mask — never one full-width
    if pad:           # chunk, which would void the O(T*block_k) bound
        k = jnp.concatenate([k, jnp.zeros((B, pad, H, D), k.dtype)], axis=1)
        v = jnp.concatenate([v, jnp.zeros((B, pad, H, Dv), v.dtype)], axis=1)
    masked = causal or pad
    nk = (Tk + pad) // bk
    qf = q.astype(jnp.float32)
    kc = k.astype(jnp.float32).reshape(B, nk, bk, H, D)
    vc = v.astype(jnp.float32).reshape(B, nk, bk, H, Dv)
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

    o0 = jnp.zeros((B, Tq, H, Dv), jnp.float32)
    m0 = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Tq, H), jnp.float32)
    # Inside shard_map, fresh carries are axis-invariant while the folded
    # values vary over the mesh — pcast keeps the scan carry type fixed
    # (same VMA discipline as ring_attention_local).
    vma = tuple(sorted(_vma_of(q, k, v, q_off, k_off)))
    o0, m0, l0 = (pcast_varying(x, vma) for x in (o0, m0, l0))
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
# Both kernels mask by GLOBAL positions: row q_off + (local index),
# col k_off + (local index). Plain causal attention passes offsets (0, 0);
# ring flash attention (ring_flash_attention_local) passes each shard's
# sequence offsets so the same kernels compute the diagonal, kept, and
# fully-masked ring steps. Offsets arrive as (1,) int32 arrays in SMEM.
#
# A grid step owns a MAJOR block of one sequence axis for one (batch,
# head) and walks score TILES of it itself: the forward owns Q rows and
# sweeps the K tiles of the resident K/V, the backward owns K rows and
# sweeps the Q tiles of the resident Q/dO. The sweep's bounds come from
# the offsets, so a tile the causal mask kills costs nothing, and only
# tiles the diagonal crosses build the mask. A sequence whose head does
# not fit ``_RESIDENT_BYTES`` is walked in major blocks by sequential grid
# axes, the accumulators crossing them in VMEM scratch (the backward's
# dQ, which belongs to the OTHER axis, crosses the outer one whole).
#
# Both compute their scores TRANSPOSED, [K rows, Q lanes] = k q^T: the
# softmax statistics are then lane-dense rows [1, tile_q] (two vregs
# where a column [tile_q, 1] takes tile_q / 8), their reductions run down
# the sublanes on the VPU instead of across lanes on the XLU, p^T and
# ds^T feed every dot as they are (the output and dQ accumulate
# transposed for it), and the logsumexp leaves and enters the kernels as
# the rows it is stored in.

_LANES = 128
# VMEM budget for ONE resident operand of a grid step (a head's K, V, Q or
# dO; Pallas double-buffers each). Past it the sequence goes in major blocks.
_RESIDENT_BYTES = 512 * 1024
# Upper bounds (Q, K) on a computed score tile, tuned on the v5e at GPT-2
# XL's head shape (PERF.md section 6, PR 27). A smaller tile computes less
# of the causal matrix and loses more than that to the latency of its
# dots, so the tiles are large and the backward cuts the tile ON the
# diagonal into groups instead (_diag_groups); fused, the backward still
# reads best at 1,024 x 1,024 (PR 34). block_q / block_k bound
# every tile too.
_FWD_TILE = (512, 512)
_BWD_TILE = (1024, 1024)

# VMEM the backward kernel may take in all (the v5e has 128 MiB; Mosaic
# hands a kernel 16 MiB of it unless asked): its limit, and what
# ``flash_plan`` fits the group's dQ accumulator into.
_VMEM_BYTES = 100 * 1024 * 1024

_NT = (((1,), (1,)), ((), ()))   # a @ b.T, contraction on both minor dims


class FlashPlan(NamedTuple):
    """What :func:`flash_plan` chose for a shape."""
    tile_q: int    # Q extent of a forward score tile, and the width of a
    tile_k: int    # logsumexp row; K extent of a forward tile
    bwd_q: int     # the backward kernel's tile (bwd_q a multiple of tile_q)
    bwd_k: int
    major_q: int   # Q rows resident in a grid step
    major_k: int   # K rows resident in a grid step
    causal_share: float  # share of the causal scores the forward computes
    bwd_share: float     # ... and the backward
    span_q: int    # Q rows whose dQ, a whole group's, a backward call keeps
    bwd_vmem: int  # bytes of VMEM that call is sized at


def _pick_tile(T: int, cap: int, unit: int = 1) -> int:
    """Largest divisor of T within cap (a multiple of unit) that is a
    multiple of 128 lanes; a sequence with none gets its largest such
    divisor of at most 128. So a tile is at most 128 or a multiple of it."""
    divs = [d for d in range(unit, min(T, max(cap, unit)) + 1, unit)
            if T % d == 0]
    wide = [d for d in divs if d % _LANES == 0]
    return max(wide) if wide else max(d for d in divs if d <= _LANES)


def _pick_major(T: int, unit: int, row_bytes: int, budget: int) -> int:
    """Whole sequence when it fits the budget, else its largest divisor
    that does and is a multiple of unit; a sequence with no such divisor
    stays whole."""
    if T * row_bytes <= budget:
        return T
    fits = [d for d in range(unit, T, unit)
            if T % d == 0 and d * row_bytes <= budget]
    return max(fits) if fits else T


def _diag_groups(tq: int, tk: int) -> int:
    """How many 128-wide groups the backward cuts the tile ON the diagonal
    into (0: it is not cut): a square tile that the diagonal halves is
    computed as its (n + 1) / 2n live share, group by group, inside one
    loop body: the groups are independent, so their dots overlap."""
    return tq // _LANES if tq == tk and tq % _LANES == 0 and tq > _LANES \
        else 0


def computed_share(Tq: int, Tk: int, tile_q: int, tile_k: int,
                   cut: bool = False) -> float:
    """Share of the Tq x Tk score matrix inside tiles that causal masking
    (offsets 0) leaves live — what the kernels compute; 0.5 is the floor.
    ``cut``: the tiles on the diagonal count their live groups only."""
    live = sum(min(-(-((i + 1) * tile_q) // tile_k), Tk // tile_k)
               for i in range(Tq // tile_q))
    n = _diag_groups(tile_q, tile_k) if cut else 0
    if n:   # min(Tq, Tk) / tile tiles lie on the diagonal
        live -= min(Tq, Tk) // tile_q * (n - 1) / (2 * n)
    return live * tile_q * tile_k / (Tq * Tk)


def _bwd_vmem(rows_q, major_q, major_k, tq, tk, D, Dv, itemsize):
    """Bytes of VMEM the backward kernel is sized at when it keeps
    ``rows_q`` rows of dQ: the three float32 accumulators (dQ's as [D, tq]
    tiles), every operand and result block twice (Pallas double-buffers
    them), and six float32 copies of a score tile for what Mosaic keeps
    of s, p, dp and ds. A row of VMEM is whole 128-lane tiles."""
    d, dv, lanes = (-(-x // _LANES) * _LANES for x in (D, Dv, tq))
    return (4 * (rows_q // tq * D * lanes + major_k * (d + dv))
            + 2 * itemsize * (major_q * (2 * d + dv)
                              + 2 * major_k * (d + dv))
            + 6 * 4 * tq * tk)


def flash_plan(Tq: int, Tk: int, D: int, itemsize: int,
               block_q: Optional[int] = None,
               block_k: Optional[int] = None,
               Dv: Optional[int] = None, g: int = 1) -> FlashPlan:
    """Tiles and resident extents for a shape: pure, and the one place the
    kernels take them from. ``block_q`` / ``block_k``, where given, are
    upper bounds on every tile. ``D`` is the head size of q and k, ``Dv``
    that of v and the output where it is another (latent attention: 192
    and 128); the wider of the two is what a resident operand is sized
    by. ``g`` is the number of q-heads a kv head serves: the backward
    keeps the dQ of a whole group, ``g * span_q`` rows, in VMEM, and
    ``span_q`` is the whole sequence wherever that fits ``_VMEM_BYTES``."""
    Dv = Dv or D
    wide = max(D, Dv)
    cap_q, cap_k = block_q or Tq, block_k or Tk
    tq = _pick_tile(Tq, min(cap_q, _FWD_TILE[0]))
    tk = _pick_tile(Tk, min(cap_k, _FWD_TILE[1]))
    bq = _pick_tile(Tq, min(cap_q, _BWD_TILE[0]), tq)   # whole lse rows
    bk = _pick_tile(Tk, min(cap_k, _BWD_TILE[1]))
    major_q = _pick_major(Tq, bq, wide * itemsize, _RESIDENT_BYTES)
    major_k = _pick_major(Tk, math.lcm(tk, bk), wide * itemsize,
                          _RESIDENT_BYTES)
    vmem = functools.partial(_bwd_vmem, major_q=major_q, major_k=major_k,
                             tq=bq, tk=bk, D=D, Dv=Dv, itemsize=itemsize)
    span = max((s for s in range(major_q, Tq + 1, major_q)
                if Tq % s == 0 and vmem(g * s) <= _VMEM_BYTES),
               default=major_q)
    return FlashPlan(
        tq, tk, bq, bk, major_q, major_k,
        computed_share(Tq, Tk, tq, tk),
        computed_share(Tq, Tk, bq, bk, cut=True), span, vmem(g * span))


def _scale_folds(scale: float) -> bool:
    """A power-of-two scale (64 ** -0.5) commutes with every rounding, so
    it is applied to the [tile_q, D] Q tile (and dQ) instead of the
    scores; any other scale stays on the scores."""
    return math.frexp(scale)[0] == 0.5


def _scaled(qb, scale):
    return qb * jnp.asarray(scale, qb.dtype) if _scale_folds(scale) else qb


def _scores(a, b, scale):
    """a b^T in float32, times the scale where it did not go onto Q."""
    s = jax.lax.dot_general(a, b, _NT, preferred_element_type=jnp.float32)
    return s if _scale_folds(scale) else s * scale


def _get_row(blk, t):
    """Row t (traced) of a small [rows, n] block as [1, n]: a select and
    a sublane sum, since a single dynamic row cannot be loaded or stored."""
    pick = jax.lax.broadcasted_iota(jnp.int32, blk.shape, 0) == t
    return jnp.sum(jnp.where(pick, blk, 0.0), axis=0, keepdims=True)


def _set_row(row_ref, t, row):
    pick = jax.lax.broadcasted_iota(jnp.int32, row_ref.shape[3:], 0) == t
    row_ref[0, 0, 0] = jnp.where(pick, row, row_ref[0, 0, 0])


def _get_rows(row_ref, t, n, start=0):
    """Rows [t * n, (t + 1) * n) of a [1, 1, 1, rows, w] ref side by side,
    from lane ``start`` (static) of the n * w on: [1, n * w - start]."""
    w = row_ref.shape[4]
    return jnp.concatenate(
        [_get_row(row_ref[0, 0, 0, :, max(start - j * w, 0):], t * n + j)
         for j in range(n) if start < (j + 1) * w], axis=1)


def _tile_diff(rows, cols):
    # local row index minus local column index of a score tile
    return (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))


def _cdiv_clip(x, d, n):
    """ceil(max(x, 0) / d) clipped to [0, n], on int32 scalars."""
    return jnp.clip(jax.lax.div(jnp.maximum(x, 0) + (d - 1), d), 0, n)


def _live_k_tiles(masked, q_lo, k_base, tq, tk, nk):
    """For the Q tile whose first global row is q_lo: K tiles [0, full)
    lie wholly under the diagonal, [full, live) cross it, the rest are
    dead. Unmasked: every tile is kept."""
    if not masked:
        return nk, nk
    full = jnp.clip(jax.lax.div(jnp.maximum(q_lo - k_base + 1, 0), tk),
                    0, nk)
    return full, _cdiv_clip(q_lo + tq - k_base, tk, nk)


def _live_q_tiles(masked, k_lo, q_base, tq, tk, nq):
    """For the K tile whose first global column is k_lo: Q tiles [0, lo)
    are dead, [lo, full) cross the diagonal, [full, nq) are wholly kept."""
    if not masked:
        return 0, 0
    lo = jnp.clip(jax.lax.div(jnp.maximum(k_lo - q_base, 0), tq), 0, nq)
    return lo, _cdiv_clip(k_lo + tk - 1 - q_base, tq, nq)


def _loop(lo, hi, tile, carry, **kw):
    if isinstance(lo, int) and isinstance(hi, int) and lo >= hi:
        return carry      # statically empty: costs nothing
    return jax.lax.fori_loop(lo, hi, functools.partial(tile, **kw), carry)


def _crossing(lo, hi, off, n_tiles, size, groups, tile, diag, carry):
    """The tiles [lo, hi) of a sweep (tiles of ``size``) that the diagonal
    crosses. ``off`` is where the diagonal enters the swept axis (this
    side's first position minus the swept side's base): where tile lo
    starts exactly there (and is resident) it is the only crossing tile,
    and ``diag`` computes its live groups; else every crossing tile is
    computed whole, and masked."""
    masked = functools.partial(_loop, lo, hi, tile, mask_it=True)
    if not groups:
        return masked(carry)
    aligned = jnp.logical_and(off == lo * size, lo < n_tiles)
    return jax.lax.cond(aligned, functools.partial(diag, lo), masked, carry)


def _rows(i, size):
    return pl.ds(pl.multiple_of(i * size, size), size)


def _span(m, size):
    return slice(m * size, (m + 1) * size)


def _flash_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                  vt_s, *scratch, scale, masked, tq, tk):
    # Grid (B, H, Q majors, K majors), K majors sequential. Blocks: q/o
    # [1, 1, major_q, D]; k/v [1, 1, major_k, D], resident across the Q
    # tiles; lse [1, 1, 1, major_q // tq, tq], one lane-dense row a Q tile.
    # Scores are [tk, tq] = k q^T; the online-softmax state (m, l rows
    # [1, tq]; acc^T [Dv, tq] += v^T p^T) is the K sweep's loop carry,
    # float32, and the output tile is transposed back once, at the end.
    # v and the output have v's own head size Dv, q and k theirs.
    bq, Dv = q_ref.shape[2], v_ref.shape[3]
    bk = k_ref.shape[2]
    kmaj, num_major = pl.program_id(3), pl.num_programs(3)
    q_base = qoff_ref[0] + pl.program_id(2) * bq
    k_base = koff_ref[0] + kmaj * bk
    diff = _tile_diff(tk, tq) if masked else None
    for c in range(bk // tk):       # v^T of the resident V, tile by tile
        vt_s[c] = v_ref[0, 0, c * tk:(c + 1) * tk, :].T

    if scratch:   # the state crosses the K majors in VMEM
        m_s, l_s, acc_s = scratch

        @pl.when(kmaj == 0)
        def _init():
            m_s[:] = jnp.full_like(m_s, _NEG_INF)
            l_s[:] = jnp.zeros_like(l_s)
            acc_s[:] = jnp.zeros_like(acc_s)

    def q_tile(t, _):
        rows = _rows(t, tq)
        q_lo = q_base + t * tq
        # dots run in the INPUT dtype (bf16 inputs -> bf16 MXU rate) with
        # f32 accumulation; all online-softmax state stays f32
        qb = _scaled(q_ref[0, 0, rows, :], scale)

        def k_tile(j, carry, mask_it):
            m, l, acc = carry
            s = _scores(k_ref[0, 0, _rows(j, tk), :], qb, scale)
            if mask_it:
                s = jnp.where(diff <= q_lo - (k_base + j * tk), s, _NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=0, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=0, keepdims=True)
            vt = vt_s[j]
            acc = acc * alpha + jnp.dot(
                vt, p.astype(vt.dtype), preferred_element_type=jnp.float32)
            return m_new, l, acc

        if scratch:
            carry = m_s[t], l_s[t], acc_s[t]
        else:
            carry = (jnp.full((1, tq), _NEG_INF, jnp.float32),
                     jnp.zeros((1, tq), jnp.float32),
                     jnp.zeros((Dv, tq), jnp.float32))
        full, live = _live_k_tiles(masked, q_lo, k_base, tq, tk, bk // tk)
        carry = _loop(0, full, k_tile, carry, mask_it=False)
        carry = _loop(full, live, k_tile, carry, mask_it=True)
        m, l, acc = carry

        def write():
            l_safe = jnp.maximum(l, 1e-30)
            o_ref[0, 0, rows, :] = (acc / l_safe).T.astype(o_ref.dtype)
            # true logsumexp per row — the backward recomputes
            # p = exp(s - lse), and the ring merge weights shards by
            # exp(lse_s - lse_total)
            _set_row(lse_ref, t, m + jnp.log(l_safe))

        if scratch:
            m_s[t], l_s[t], acc_s[t] = m, l, acc
            pl.when(kmaj == num_major - 1)(write)
        else:
            write()

    jax.lax.fori_loop(0, bq // tq, q_tile, None)


def _smem_spec():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


def _vma_of(*xs):
    # Inside shard_map the output type must declare which mesh axes it
    # varies over (VMA tracking); it varies exactly where the inputs do.
    vma = frozenset()
    for x in xs:
        vma = vma | jax.typeof(x).vma
    return vma


def _offsets(q_off, k_off):
    return (jnp.asarray(q_off, jnp.int32).reshape(1),
            jnp.asarray(k_off, jnp.int32).reshape(1))


_SWEEP_LAST = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"))


def _head_specs(bq, bk, D, g):
    """Blocks of the forward's grid (B, H, Q majors, K majors) at head
    size ``D``: (a Q-side operand's, a K-side operand's)."""
    return (pl.BlockSpec((1, 1, bq, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk, D), lambda b, h, i, j: (b, h // g, j, 0)))


def _flash_forward(q, k, v, q_off, k_off, masked, scale, block_q, block_k,
                   interpret):
    """[B, T, H, D] in/out; kernel runs on [B, H, T, D]. K/V may carry
    fewer heads (GQA): each q-head's K/V block index maps onto kv head
    h // g — the repeat never materializes, so KV HBM traffic shrinks by
    the group factor. Returns (out, lse [B, H, Q majors, tiles, tile_q]):
    the logsumexp row-major over the sequence, a lane-dense row a tile."""
    B, Tq, H, D = q.shape
    g = gqa_group_size(H, k.shape[2])
    Tk, Dv = k.shape[1], v.shape[3]
    plan = flash_plan(Tq, Tk, D, q.dtype.itemsize, block_q, block_k, Dv)
    tq, tk, bq, bk = plan.tile_q, plan.tile_k, plan.major_q, plan.major_k
    qt, kt, vt = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
    vma = _vma_of(q, k, v, q_off, k_off)
    num_major = Tk // bk
    q_spec, kv_spec = _head_specs(bq, bk, D, g)
    o_spec, v_spec = _head_specs(bq, bk, Dv, g)
    out, lse = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, masked=masked,
                          tq=tq, tk=tk),
        grid=(B, H, Tq // bq, num_major),
        in_specs=[_smem_spec(), _smem_spec(), q_spec, kv_spec, v_spec],
        out_specs=[
            o_spec,
            pl.BlockSpec((1, 1, 1, bq // tq, tq),
                         lambda b, h, i, j: (b, h, i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Tq, Dv), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, H, Tq // bq, bq // tq, tq),
                                 jnp.float32, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((bk // tk, Dv, tk), v.dtype)] + (
            [] if num_major == 1 else [
                pltpu.VMEM((bq // tq, 1, tq), jnp.float32),   # running max
                pltpu.VMEM((bq // tq, 1, tq), jnp.float32),   # normalizer
                pltpu.VMEM((bq // tq, Dv, tq), jnp.float32),  # acc^T
            ]),
        compiler_params=_SWEEP_LAST,
        interpret=interpret,
        name=prof.FLASH_FWD,
    )(*_offsets(q_off, k_off), qt, kt, vt)
    return out.transpose(0, 2, 1, 3), lse


def _flash_bwd_kernel(qoff_ref, koff_ref, q_ref, k_ref, v_ref, do_ref,
                      lse_ref, dvec_ref, dq_ref, dk_ref, dv_ref, dq_s, dk_s,
                      dv_s, *, scale, masked, tq, tk, num_q_major):
    # Grid (B, Hk, K majors, q_per_kv * Q majors), the last two sequential;
    # the combined (group q-head, Q major) axis is the inner one: under
    # GQA every kv head receives gradient from all q-heads of its group. A
    # grid step walks its K tiles, and for each the live Q tiles of the
    # resident Q/dO; scores [tk, tq] = k q^T as in the forward, logsumexp
    # and dvec the rows they are stored as. p and ds of a tile are formed
    # ONCE, [keys, q lanes], and all three gradients taken from them as
    # plain products: dV += p dO, dK += ds q, and dQ transposed,
    # dQ^T [D, tq] += k^T ds, with k^T formed once a K tile (what the
    # forward does with acc^T += v^T p). All three accumulate in float32
    # VMEM scratch, in place (as loop carries their vregs spill at every
    # bound): dK/dV of the K major across the inner axis; dQ^T of the
    # call's whole group, a [D, tq] tile a Q tile, across the K majors,
    # transposed back once as it is written out at the last of them.
    bq, bk, D = q_ref.shape[2], k_ref.shape[2], k_ref.shape[3]
    nq = bq // tq
    per_tile = tq // lse_ref.shape[4]    # logsumexp rows a Q tile spans
    kmaj, num_major = pl.program_id(2), pl.num_programs(2)
    t, num_t = pl.program_id(3), pl.num_programs(3)
    q_base = qoff_ref[0] + jax.lax.rem(t, num_q_major) * bq
    k_base = koff_ref[0] + kmaj * bk
    fold = _scale_folds(scale)
    diff = _tile_diff(tk, tq) if masked else None
    mine = pl.ds(t * nq, nq)    # this step's tiles of the group's dQ

    @pl.when(kmaj == 0)
    def _init_dq():
        dq_s[mine] = jnp.zeros((nq, D, tq), jnp.float32)

    @pl.when(t == 0)
    def _init_dkv():
        dk_s[:] = jnp.zeros_like(dk_s)
        dv_s[:] = jnp.zeros_like(dv_s)

    def grad(s, qb, dob, kt, vb, lse, dvec):
        p = jnp.exp(s - lse)                             # [keys, q] f32
        dv = jnp.dot(p.astype(dob.dtype), dob,
                     preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(vb, dob, _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dvec)
        if not fold:
            ds = ds * scale
        ds = ds.astype(qb.dtype)
        # with the scale folded into qb, ds^T qb carries it; dQ takes it
        # when it is written
        dk = jnp.dot(ds, qb, preferred_element_type=jnp.float32)
        dqt = jnp.dot(kt, ds, preferred_element_type=jnp.float32)
        return dqt, dk, dv

    def k_tile(c, _):
        rows = _rows(c, tk)
        k_lo = k_base + c * tk
        kb, vb = k_ref[0, 0, rows, :], v_ref[0, 0, rows, :]
        kt = kb.T

        def q_tile(i, _, mask_it):
            cols = _rows(i, tq)
            qb = _scaled(q_ref[0, 0, cols, :], scale)
            s = _scores(kb, qb, scale)
            if mask_it:
                s = jnp.where(diff <= q_base + i * tq - k_lo, s, _NEG_INF)
            dqt, dk, dv = grad(s, qb, do_ref[0, 0, cols, :], kt, vb,
                               _get_rows(lse_ref, i, per_tile),
                               _get_rows(dvec_ref, i, per_tile))
            dq_s[t * nq + i] += dqt
            dk_s[rows, :] += dk
            dv_s[rows, :] += dv

        def diag_tile(i, _):
            # K row group r of the tile ON the diagonal: queries from
            # r * 128 on
            u = _LANES
            for r in range(tk // u):
                grp = pl.ds(pl.multiple_of(c * tk + r * u, u), u)
                suf = pl.ds(pl.multiple_of(i * tq + r * u, u), tq - r * u)
                qb = _scaled(q_ref[0, 0, suf, :], scale)
                own = slice(r * u, (r + 1) * u)
                s = _scores(kb[own, :], qb, scale)
                s = jnp.where(diff[:u, :tq - r * u] <= 0, s, _NEG_INF)
                dqt, dk, dv = grad(
                    s, qb, do_ref[0, 0, suf, :], kt[:, own], vb[own, :],
                    _get_rows(lse_ref, i, per_tile, r * u),
                    _get_rows(dvec_ref, i, per_tile, r * u))
                dq_s[t * nq + i, :, r * u:] += dqt
                dk_s[grp, :] += dk
                dv_s[grp, :] += dv

        lo, full = _live_q_tiles(masked, k_lo, q_base, tq, tk, nq)
        if masked:
            _crossing(lo, full, k_lo - q_base, nq, tq,
                      _diag_groups(tq, tk), q_tile, diag_tile, None)
        _loop(full, nq, q_tile, None, mask_it=False)

    jax.lax.fori_loop(0, bk // tk, k_tile, None)

    @pl.when(t == num_t - 1)
    def _write_dkv():
        dk_ref[0, 0] = dk_s[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_s[:].astype(dv_ref.dtype)

    @pl.when(kmaj == num_major - 1)
    def _write_dq():
        for i in range(nq):     # transposed back once, as it leaves
            dq = dq_s[t * nq + i].T
            dq_ref[0, 0, i * tq:(i + 1) * tq, :] = (
                dq * scale if fold else dq).astype(dq_ref.dtype)


def _flash_backward(q, k, v, q_off, k_off, g_out, lse, dvec, masked, scale,
                    block_q, block_k, interpret):
    """dQ/dK/dV via the one backward kernel; [B, T, H, D] layout.
    ``lse`` and ``dvec`` (rowsum(dO*O) minus the lse cotangent) are
    [B, H, Q majors, tiles, tile_q] as the forward leaves the logsumexp.
    Under GQA dk/dv come back at the kv head count. Where the plan's
    ``span_q`` is less than the sequence, the kernel runs once a span of Q
    rows (their offset added to ``q_off``) and the spans' dK/dV, float32,
    are summed here."""
    B, Tq, H, D = q.shape
    Hk = k.shape[2]
    g = gqa_group_size(H, Hk)
    Tk, Dv = k.shape[1], v.shape[3]
    plan = flash_plan(Tq, Tk, D, q.dtype.itemsize, block_q, block_k, Dv, g)
    tq, tk, bq, bk = plan.bwd_q, plan.bwd_k, plan.major_q, plan.major_k
    w, span = plan.tile_q, plan.span_q
    nqm, nkm = span // bq, Tk // bk
    qt, kt, vt, dot = (x.transpose(0, 2, 1, 3) for x in (q, k, v, g_out))
    vma = _vma_of(q, k, v, q_off, k_off, g_out)
    q_off, k_off = _offsets(q_off, k_off)
    part = jnp.float32 if span < Tq else None    # dK/dV of a span

    # grid dim 1 walks KV heads; the q-head within the group and its Q
    # major ride the inner axis t
    def q_at(b, hk, j, t):
        return b, hk * g + t // nqm, t % nqm

    def specs(D):
        return (pl.BlockSpec((1, 1, bq, D),
                             lambda b, hk, j, t: (*q_at(b, hk, j, t), 0)),
                pl.BlockSpec((1, 1, bk, D),
                             lambda b, hk, j, t: (b, hk, j, 0)))

    def dq_at(b, hk, j, t):
        # dQ is written out at the last K major only; until then its
        # block stays on the one that is written first, so that nothing
        # goes back to HBM for it
        return (*q_at(b, hk, j, jnp.where(j == nkm - 1, t, 0)), 0)

    q_spec, kv_spec = specs(D)
    do_spec, v_spec = specs(Dv)
    row_spec = pl.BlockSpec(
        (1, 1, 1, bq // w, w),
        lambda b, hk, j, t: (*q_at(b, hk, j, t), 0, 0))
    call = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, scale=scale, masked=masked,
                          tq=tq, tk=tk, num_q_major=nqm),
        grid=(B, Hk, nkm, g * nqm),
        in_specs=[_smem_spec(), _smem_spec(),
                  q_spec, kv_spec, v_spec, do_spec, row_spec, row_spec],
        out_specs=[pl.BlockSpec((1, 1, bq, D), dq_at), kv_spec, v_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, span, D), q.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, Hk, Tk, D), part or k.dtype, vma=vma),
            jax.ShapeDtypeStruct((B, Hk, Tk, Dv), part or v.dtype, vma=vma),
        ],
        scratch_shapes=[pltpu.VMEM((g * span // tq, D, tq), jnp.float32),
                        pltpu.VMEM((bk, D), jnp.float32),
                        pltpu.VMEM((bk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary"),
            vmem_limit_bytes=_VMEM_BYTES),
        interpret=interpret,
        name=prof.FLASH_BWD,
    )
    dq, dk, dv = zip(*(
        call(q_off + m * span, k_off, qt[:, :, _span(m, span)], kt, vt,
             dot[:, :, _span(m, span)], lse[:, :, _span(m, nqm)],
             dvec[:, :, _span(m, nqm)])
        for m in range(Tq // span)))
    dq = jnp.concatenate(dq, axis=2)
    dk = functools.reduce(jnp.add, dk).astype(k.dtype)
    dv = functools.reduce(jnp.add, dv).astype(v.dtype)
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
    # named BEFORE they part into outputs and residuals, so that a
    # checkpoint policy that saves both names leaves the rematted forward
    # no reader of the kernel: its second run is dead code and is dropped
    # ``out`` is named as [B, T, H D]: what a policy keeps has the shape
    # of what is named, and a last dimension of D 64 is padded to the 128
    # lanes of an HBM tile (105 MB a layer at GPT-2 XL's shape, not 52)
    B, T, H, D = out.shape
    out = checkpoint_name(out.reshape(B, T, H * D),
                          prof.FLASH_OUT).reshape(B, T, H, D)
    lse = checkpoint_name(lse, prof.FLASH_LSE)
    return (out, lse), (q, k, v, q_off, k_off, out, lse)


def _flash_with_lse_bwd(masked, scale, block_q, block_k, interpret, res,
                        gs):
    q, k, v, q_off, k_off, out, lse = res
    g, g_lse = gs
    # ds = p * (dp - rowsum(dO*O) + g_lse): the lse cotangent enters the
    # softmax-jacobian row term with opposite sign to D_i, so both ride
    # the same dvec input of the kernels (d lse / d s_k = p_k).
    dvec = (jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1).reshape(lse.shape)
            - g_lse.astype(jnp.float32))      # row-major over Tq, as lse
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


def kernel_supported(q_shape, k_shape, block_q: Optional[int] = None,
                     block_k: Optional[int] = None,
                     v_head: Optional[int] = None) -> bool:
    """Static shape gate for the Pallas path: block sizes, where given,
    must tile the sequence (no ragged tails in the kernel) and D (and v's
    head size ``v_head``, where it is another) should be lane-friendly."""
    B, Tq, H, D = q_shape
    Tk = k_shape[1]
    bq, bk = min(block_q or Tq, Tq), min(block_k or Tk, Tk)
    if q_shape[2] % k_shape[2]:   # GQA: kv heads must divide q heads
        return False
    return (Tq % bq == 0 and Tk % bk == 0 and D % 8 == 0
            and (v_head or D) % 8 == 0)


def _use_kernel(interpret: Optional[bool], q_shape, k_shape,
                block_q: Optional[int], block_k: Optional[int],
                v_head: Optional[int] = None) -> bool:
    """The one platform rule both entry points share. ``interpret=None``
    (every production caller): the compiled kernels on a TPU backend, the
    blockwise scan — their documented platform twin — anywhere else. An
    explicit ``interpret`` (tests: ``True`` runs the Pallas interpreter)
    always means the kernels. Whoever gets the kernels gets them or a
    ValueError naming the shape ``kernel_supported`` refused: the scan is
    never a silent stand-in for a kernel that was asked for."""
    if interpret is None and jax.default_backend() != "tpu":
        return False
    if not kernel_supported(q_shape, k_shape, block_q, block_k, v_head):
        raise ValueError(
            f"flash attention kernels refuse q{tuple(q_shape)} "
            f"k{tuple(k_shape)} (v heads of {v_head or q_shape[3]}) at "
            f"blocks ({block_q}, {block_k}): the "
            "blocks must tile both sequences, kv heads must divide q "
            "heads, and the head dims must be multiples of 8 — pick "
            "tiling blocks or attn_impl='reference' for this shape")
    return True


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Fused attention; same signature/semantics as
    ``ring_attention.reference_attention`` but never materializes the full
    score matrix. On a TPU backend: the compiled Pallas kernels, or a
    ValueError for a shape they refuse. Off TPU: the blockwise scan, same
    math (``interpret=True`` runs the kernels in the Pallas interpreter
    anywhere — tests only). See :func:`_use_kernel`. ``block_q`` /
    ``block_k`` bound the kernels' score tiles from above; left out, the
    tiles are :func:`flash_plan`'s for the shape.

    v's head size is v's own: q and k share one (scores are q k^T over
    it), v and the output another where they differ (latent attention:
    192 and 128); no padded copy of v is made, every p v, dP and dV
    product runs at v's size. For equal sizes nothing changes.

    Grouped-query attention: K/V may carry fewer heads than Q (kv divides
    q, q-head h reads kv head h // group). The kernel path streams the
    small K/V straight from HBM — traffic and ring wire bytes shrink by
    the group factor; the scan repeats heads (exact, memory-expanded).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if _use_kernel(interpret, q.shape, k.shape, block_q, block_k,
                   v.shape[3]):
        return _flash(q, k, v, causal, scale, block_q, block_k,
                      bool(interpret))
    return blockwise_attention(q, k, v, causal=causal, scale=scale,
                               block_k=block_k or 512)


# -------------------------------------------------------- ring flash attn
def ring_flash_attention_local(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
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
    that back to O(T/N). No caller wires the ring to the other remat
    modes (``lm_example`` refuses ``--remat`` off ``dp``); one who wraps
    it in a checkpoint whose policy saves ``profiling.FLASH_RESIDUALS``
    keeps one partial ``out`` ``[B, T/N, H D]`` and ``lse`` a ring step,
    n of each a device, and runs the forward kernel n times, not 2n. K/V
    rotate one ICI hop per step (ppermute); XLA overlaps the hop with the
    kernel. Gradients flow through the kernels' custom VJP at every step.
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
    use_kernel = _use_kernel(interpret, q.shape, k.shape, block_q, block_k,
                             v.shape[3])
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
                q, k_cur, v_cur, q_off, src * Tk, causal, scale, block_q,
                block_k, interpret)
            lse_s = lse_s.reshape(B, H, Tq).transpose(0, 2, 1)  # [B, Tq, H]
        else:
            o_s, lse_s = blockwise_attention(
                q, k_cur, v_cur, causal=causal, scale=scale,
                block_k=block_k or 512, q_off=q_off, k_off=src * Tk,
                return_lse=True)
        lse_new = jnp.logaddexp(lse_run, lse_s)
        acc = (acc * jnp.exp(lse_run - lse_new)[..., None]
               + o_s.astype(jnp.float32)
               * jnp.exp(lse_s - lse_new)[..., None])
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (acc, lse_new, k_nxt, v_nxt), None

    acc0 = jnp.zeros((B, Tq, H, v.shape[3]), jnp.float32)
    lse0 = jnp.full((B, Tq, H), _NEG_INF, jnp.float32)
    # the visiting K/V shards (and, under causal, the axis index r) make
    # every step output vary over the ring axis, so ALL carries must be
    # varying — even when the inputs arrive replicated
    acc0, lse0, k, v = (pcast_varying(x, (axis_name,))
                        for x in (acc0, lse0, k, v))
    (acc, _, _, _), _ = jax.lax.scan(
        step_fn, (acc0, lse0, k, v), jnp.arange(n))
    return acc.astype(q.dtype)
