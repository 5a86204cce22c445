"""Operations and bytes the Olmo-Hybrid-shaped decoder NEEDS, from shapes:
what the mathematics asks for, whatever implements it. A matmul parameter
costs 6 FLOPs a token that reaches it (forward 2, backward 4); recomputed
operations (the blocks' remat, the flash backward's scores, the chunk
terms the delta rule's backward forms again) are never counted. The delta
rule is charged by the RECURRENCE's own count, three products of a key
vector's size by a value vector's a token and head (the state's read-out
against k, its rank-one update, its read-out against q), not by the
chunked form's, which trades more products for fewer steps."""

from __future__ import annotations


def _kinds(c: dict) -> tuple:
    depth = int(c["num_hidden_layers"])
    kinds = c["layer_types"][:depth]
    return (sum(k == "linear_attention" for k in kinds),
            sum(k == "full_attention" for k in kinds))


def olmo_params(c: dict) -> dict:
    """Parameter counts by part, as ``models/olmo_hybrid.py`` builds the
    file's model: a linear mixer (six input projections, the output's,
    three convolutions, ``A_log``, ``dt_bias`` and the head norm's gain), a
    full mixer (four projections, two QK-norm gains), the SwiGLU MLP, a
    block's two norms; embedding, head and the final norm."""
    d, f = int(c["hidden_size"]), int(c["intermediate_size"])
    H = int(c["linear_num_key_heads"])
    qk = H * int(c["linear_key_head_dim"])
    v = int(c["linear_num_value_heads"]) * int(c["linear_value_head_dim"])
    taps = int(c["linear_conv_kernel_dim"])
    linear_mm = d * (2 * qk + 2 * v + 2 * H) + v * d
    linear = linear_mm + taps * (2 * qk + v) + 2 * H \
        + int(c["linear_value_head_dim"])
    full_mm = 4 * d * d
    full = full_mm + 2 * d
    mlp = 3 * d * f
    n_lin, n_full = _kinds(c)
    vocab = int(c["vocab_size"]) * d
    return {"linear_matmul": linear_mm, "linear_mixer": linear,
            "full_matmul": full_mm, "full_mixer": full, "mlp": mlp,
            "linear_block": linear + mlp + 2 * d,
            "full_block": full + mlp + 2 * d,
            "embed": vocab, "head": vocab,
            "total": n_lin * (linear + mlp + 2 * d)
            + n_full * (full + mlp + 2 * d) + 2 * vocab + d}


def attention_flops_per_step(c: dict, batch: int, seq_len: int) -> float:
    """Causal attention of one step over the full-attention layers,
    forward + dQ + dK/dV: forward q k^T and p v are 2 T T D a head each,
    both halved by the mask; the backward's four products are twice the
    forward. The scores a flash backward recomputes are not counted."""
    H = int(c["num_attention_heads"])
    D = int(c["hidden_size"]) // H
    fwd = batch * H * 2.0 * seq_len * seq_len * (D + D) / 2.0
    return _kinds(c)[1] * 3.0 * fwd


def delta_rule_flops_per_step(c: dict, batch: int, seq_len: int) -> float:
    """The gated delta rule of one step over the linear layers, by the
    recurrence: ``2 x (3 x Dk x Dv)`` a token and head forward, twice that
    backward."""
    per_token = 2.0 * 3 * int(c["linear_key_head_dim"]) \
        * int(c["linear_value_head_dim"]) * int(c["linear_num_key_heads"])
    return _kinds(c)[0] * 3.0 * per_token * batch * seq_len


def delta_rule_bytes_per_step(c: dict, batch: int, seq_len: int,
                              chunk: int = 64) -> float:
    """HBM bytes the rule cannot avoid a step over the linear layers: q,
    k, v and o (bfloat16) and g and beta (float32) move once in the
    forward pass and once, as themselves or as their cotangents, in the
    backward; a float32 state a chunk of ``chunk`` tokens is written by
    the forward pass and read by the backward."""
    H = int(c["linear_num_key_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    per_token = H * ((2 * dk + 2 * dv) * 2 + 2 * 4)
    states = (seq_len // chunk) * H * dk * dv * 4
    return _kinds(c)[0] * batch * 2.0 * (seq_len * per_token + states)


def olmo_flops_per_step(c: dict, batch: int, seq_len: int) -> dict:
    """Model FLOPs of one step by part, forward + backward: the mixers'
    projections, the MLPs and the head at 6 a parameter and token; causal
    attention; the delta rule. The embedding look-up is a gather, the
    convolutions, norms and gates element-wise: not counted."""
    p = olmo_params(c)
    n_lin, n_full = _kinds(c)
    tokens = batch * seq_len
    parts = {"linear_proj": 6.0 * n_lin * p["linear_matmul"] * tokens,
             "full_proj": 6.0 * n_full * p["full_matmul"] * tokens,
             "mlp": 6.0 * (n_lin + n_full) * p["mlp"] * tokens,
             "head": 6.0 * p["head"] * tokens,
             "attention": attention_flops_per_step(c, batch, seq_len),
             "delta_rule": delta_rule_flops_per_step(c, batch, seq_len)}
    parts["total"] = sum(parts.values())
    return parts
