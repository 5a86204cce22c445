"""Row-wise sparse updates — the per-key server update, jit-safe.

The reference server applies ``updater->Update(keys, grads)`` touching only
the pushed keys (SURVEY.md §3.3). On TPU that becomes scatter-add (SGD) or a
dedup + row-wise accumulator step (Adagrad), with static shapes throughout:
duplicates are merged with a sorted-segment sum (O(B log B)) so the
accumulator sees each touched row exactly once per push — matching the
reference's "sum duplicate Adds, then update" semantics.

Shared by SparseTable.push and the fused GSPMD training steps so both paths
have identical numerics.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from minips_tpu.utils import profiling as prof


@jax.named_scope(prof.SPARSE_DEDUP)
def dedup_segment_sum(slots: jnp.ndarray, grads: jnp.ndarray):
    """Merge duplicate slots. Returns (rep_slots [B], summed [B, D], valid
    [B]) where only the first k entries (k = number of unique slots) are
    valid; invalid entries have summed == 0 so scatter-ADDs are no-ops.
    Shapes are static (B) for jit."""
    slots = slots.reshape(-1)
    grads = grads.reshape(slots.shape[0], -1)
    order = jnp.argsort(slots)
    s_sorted = slots[order]
    g_sorted = grads[order]
    first = jnp.concatenate(
        [jnp.ones(1, jnp.bool_), s_sorted[1:] != s_sorted[:-1]])
    seg_id = jnp.cumsum(first) - 1
    n = s_sorted.shape[0]
    g_sum = jnp.zeros_like(g_sorted).at[seg_id].add(g_sorted)
    rep = jnp.zeros(n, slots.dtype).at[seg_id].max(s_sorted)
    valid = jnp.arange(n) <= seg_id[-1]
    g_sum = jnp.where(valid[:, None], g_sum, 0)
    rep = jnp.where(valid, rep, 0)
    return rep, g_sum, valid


def row_sgd(emb: jnp.ndarray, slots: jnp.ndarray, grads: jnp.ndarray,
            lr: float) -> jnp.ndarray:
    """SGD scatter: duplicates accumulate natively under scatter-add."""
    return emb.at[slots.reshape(-1)].add(
        -lr * grads.reshape(slots.size, -1).astype(emb.dtype))


# Above this table size (elements), the dense-accumulate adagrad path's
# extra table-shaped scratch buffer (256 MB of f32 at the threshold) stops
# being worth it and the sort-dedup path takes over.
DENSE_ACCUM_MAX_ELEMS = 1 << 26


def row_adagrad(emb: jnp.ndarray, accum: jnp.ndarray, slots: jnp.ndarray,
                grads: jnp.ndarray, lr: float, eps: float = 1e-10,
                prefer_dense: bool | None = None):
    """Row-wise Adagrad on the touched rows only.

    Two numerically identical strategies, chosen by (static) table size:

    - **dense-accumulate** (default for tables <= DENSE_ACCUM_MAX_ELEMS):
      scatter-add the batch gradients into a table-shaped buffer, then a
      whole-table update. Streams O(S·D) but avoids any sort — measured
      on the real chip with chained donated state at the Criteo bench
      shapes (S=2^18, D=8, 426k keys/push): ~1ms vs ~20ms per push,
      because TPU sorts are slow and the scatter dominates either way.
    - **sort-dedup** (large tables): argsort + segment-sum so cost stays
      O(B log B + B·D), independent of table size, and no table-shaped
      scratch is allocated.
    """
    if eps <= 0:
        raise ValueError(f"eps must be > 0, got {eps}")  # dense path divides
    if prefer_dense is None:
        prefer_dense = emb.size <= DENSE_ACCUM_MAX_ELEMS
    if prefer_dense:
        return _row_adagrad_dense(emb, accum, slots, grads, lr, eps)
    return _row_adagrad_sorted(emb, accum, slots, grads, lr, eps)


@jax.named_scope(prof.SPARSE_ADAGRAD_DENSE)
def _row_adagrad_dense(emb, accum, slots, grads, lr, eps):
    # Untouched rows need no masking: their scattered g is exactly 0, so
    # g2 = 0 leaves accum bitwise unchanged (accum >= 0, no -0.0 case) and
    # step = 0/(sqrt(accum)+eps) = 0 as long as eps > 0.
    flat = slots.reshape(-1)
    g = (jnp.zeros_like(emb)
         .at[flat].add(grads.reshape(flat.shape[0], -1).astype(emb.dtype)))
    new_accum = accum + g * g
    return emb - lr * g / (jnp.sqrt(new_accum) + eps), new_accum


@jax.named_scope(prof.SPARSE_ADAGRAD_SORTED)
def _row_adagrad_sorted(emb, accum, slots, grads, lr, eps):
    rep, g_sum, _ = dedup_segment_sum(slots, grads.astype(emb.dtype))
    g2 = g_sum * g_sum
    acc_rows = accum[rep] + g2
    accum = accum.at[rep].add(g2)
    step = -lr * g_sum / (jnp.sqrt(acc_rows) + eps)
    emb = emb.at[rep].add(step)
    return emb, accum


def row_adam(emb: jnp.ndarray, m: jnp.ndarray, v: jnp.ndarray,
             steps: jnp.ndarray, slots: jnp.ndarray, grads: jnp.ndarray,
             lr: float, b1: float = 0.9, b2: float = 0.999,
             eps: float = 1e-8, prefer_dense: bool | None = None):
    """Row-wise LAZY Adam: touched rows get one full Adam step (moments,
    per-row bias correction via a per-row step counter) and untouched rows
    are left completely alone — no moment decay, the standard lazy-Adam
    semantics sparse/CTR systems use, and the sparse analog of the
    reference's per-key server update. Same two strategies as
    :func:`row_adagrad`, auto-picked by static table size."""
    if eps <= 0:
        raise ValueError(f"eps must be > 0, got {eps}")
    if prefer_dense is None:
        # Adam's dense path streams m and v whole-table and materializes
        # two extra table-shaped temporaries (~4x adagrad's scratch
        # traffic), so its crossover to sort-dedup sits 4x lower.
        prefer_dense = emb.size <= DENSE_ACCUM_MAX_ELEMS // 4
    if prefer_dense:
        return _row_adam_dense(emb, m, v, steps, slots, grads, lr, b1, b2,
                               eps)
    return _row_adam_sorted(emb, m, v, steps, slots, grads, lr, b1, b2,
                            eps)


@jax.named_scope(prof.SPARSE_ADAM_DENSE)
def _row_adam_dense(emb, m, v, steps, slots, grads, lr, b1, b2, eps):
    flat = slots.reshape(-1)
    g = (jnp.zeros_like(emb)
         .at[flat].add(grads.reshape(flat.shape[0], -1).astype(emb.dtype)))
    touched = jnp.zeros((emb.shape[0],), jnp.bool_).at[flat].set(True)
    tcol = touched[:, None]
    steps_new = steps + touched.astype(steps.dtype)
    m_new = jnp.where(tcol, b1 * m + (1 - b1) * g, m)
    v_new = jnp.where(tcol, b2 * v + (1 - b2) * g * g, v)
    tf = steps_new.astype(emb.dtype)
    bc1 = jnp.where(touched, 1 - b1 ** tf, 1.0)[:, None]
    bc2 = jnp.where(touched, 1 - b2 ** tf, 1.0)[:, None]
    update = lr * (m_new / bc1) / (jnp.sqrt(v_new / bc2) + eps)
    return (emb - jnp.where(tcol, update, 0.0), m_new, v_new, steps_new)


@jax.named_scope(prof.SPARSE_ADAM_SORTED)
def _row_adam_sorted(emb, m, v, steps, slots, grads, lr, b1, b2, eps):
    rep, g_sum, valid = dedup_segment_sum(slots, grads.astype(emb.dtype))
    vcol = valid[:, None]
    m_rows, v_rows = m[rep], v[rep]
    s_new = steps[rep] + valid.astype(steps.dtype)
    m_n = b1 * m_rows + (1 - b1) * g_sum
    v_n = b2 * v_rows + (1 - b2) * g_sum * g_sum
    tf = s_new.astype(emb.dtype)
    bc1 = jnp.where(valid, 1 - b1 ** tf, 1.0)[:, None]
    bc2 = jnp.where(valid, 1 - b2 ** tf, 1.0)[:, None]
    update = lr * (m_n / bc1) / (jnp.sqrt(v_n / bc2) + eps)
    # masked DELTA scatter-adds: invalid entries contribute exactly zero,
    # so the duplicate rep=0 rows of the invalid tail are harmless
    emb = emb.at[rep].add(jnp.where(vcol, -update, 0.0))
    m = m.at[rep].add(jnp.where(vcol, m_n - m_rows, 0.0))
    v = v.at[rep].add(jnp.where(vcol, v_n - v_rows, 0.0))
    steps = steps.at[rep].add(valid.astype(steps.dtype))
    return emb, m, v, steps
