"""Operations and bytes that the algorithm NEEDS, from shapes alone.

These count what the mathematics asks for, whatever implements it, so a
roofline share does not go stale when a later PR replaces a kernel: a
table-sized scatter that touches 400k rows is charged the 400k rows.
Recomputed operations (remat) are never counted.
"""

from __future__ import annotations


def mlp_flops_per_sample(sizes) -> float:
    """Forward + backward matmul FLOPs of an MLP: forward is 2 per
    multiply-add, backward twice the forward (dX and dW)."""
    fwd = sum(2.0 * a * b for a, b in zip(sizes[:-1], sizes[1:]))
    return 3.0 * fwd


def deepfm_flops_per_sample(num_dense: int, num_cat: int, emb_dim: int,
                            hidden) -> float:
    """DeepFM's model FLOPs per sample, forward + backward: the deep MLP
    over [dense ; flattened embeddings], the FM second-order term by the
    sum-square trick (per field and dimension: one add into the sum, one
    multiply-add into the sum of squares; then k squares and subtracts),
    and the first-order sum over the fields. Forward counted, times 3."""
    sizes = (num_dense + num_cat * emb_dim,) + tuple(hidden) + (1,)
    fm_fwd = 3.0 * num_cat * emb_dim + 3.0 * emb_dim
    wide_fwd = float(num_cat)
    return mlp_flops_per_sample(sizes) + 3.0 * (fm_fwd + wide_fwd)


def sparse_bytes_per_step(rows_touched: int, emb_dim: int,
                          updater: str = "adagrad",
                          bytes_per_el: int = 4) -> float:
    """HBM bytes one step's pull and push need for ``rows_touched`` rows of
    an ``emb_dim``-wide table: the pull reads each row once; the push reads
    and writes the row and each optimizer-state row once (Adagrad: one
    accumulator). Index traffic (4 bytes a row, pull and push) is counted;
    sorting and de-duplication are an implementation's choice and are not.
    """
    row = emb_dim * bytes_per_el
    state_rows = {"sgd": 0, "adagrad": 1, "adam": 2}[updater]
    pull = rows_touched * (row + 4)
    push = rows_touched * (2 * row * (1 + state_rows) + row + 4)
    return float(pull + push)


def lm_params(dim: int, depth: int, vocab: int, max_len: int,
              mlp_mult: int = 4) -> dict:
    """Parameter counts of the repo's GPT-2-shaped decoder (no linear
    biases, tied head): per block qkv 3d^2, proj d^2, MLP 2*mult*d^2, two
    LayerNorms 4d."""
    block = (4 + 2 * mlp_mult) * dim * dim + 4 * dim
    return {"block": block, "blocks": depth * block,
            "embed": vocab * dim, "pos": max_len * dim, "ln_f": 2 * dim,
            "total": depth * block + vocab * dim + max_len * dim + 2 * dim}


def lm_flops_per_token(dim: int, depth: int, vocab: int, seq_len: int,
                       mlp_mult: int = 4) -> float:
    """Model FLOPs per trained token, forward + backward: 6 per matmul
    parameter (block matrices and the tied head; the embedding look-up is
    a gather, not a matmul), plus causal attention's two T x T products:
    forward 2*2*T*d per token per layer halved by the causal mask,
    backward twice that."""
    matmul_params = depth * (4 + 2 * mlp_mult) * dim * dim + vocab * dim
    attn = depth * 3.0 * (2.0 * 2.0 * seq_len * dim) / 2.0
    return 6.0 * matmul_params + attn


def attention_flops_per_step(batch: int, seq_len: int, heads: int,
                             head_dim: int, depth: int) -> float:
    """Causal flash attention, forward + backward, one step: forward QK^T
    and PV are 2*T*T*hd each per head, halved by the mask; the backward
    needs four such products (dV, dP, dQ, dK), twice the forward. The
    scores that a flash backward recomputes, and the forward that remat
    runs again, are recomputation and are not counted: a kernel that does
    both can reach 6/11 of its roofline at the most."""
    fwd = batch * heads * 2.0 * (2.0 * seq_len * seq_len * head_dim) / 2.0
    return depth * fwd * 3.0
