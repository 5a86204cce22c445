"""Operations and bytes the JoyAI-LLM-Flash-shaped decoder NEEDS, from
shapes and from the routing: what the mathematics asks for, whatever
implements it. A matmul parameter costs 6 FLOPs a token that reaches it
(forward 2, backward 4); recomputed operations (the blocks' remat, the
flash backward's scores) are never counted. The routed experts are charged
the assignments really sent to the experts held, not an even share; the
head is charged both of its uses; attention's q·k (192 channels) and p·v
(128) are counted apart."""

from __future__ import annotations


def joyai_params(c: dict) -> dict:
    """Parameter counts by part, as ``models/mla_moe.py`` builds the file's
    model: a block's latent attention (five matrices, two latent norms),
    its two norms, the dense MLP or router + shared expert + experts held;
    embedding and head; the prediction module (W_eh, two norms, one expert
    block, a final norm); the final norm."""
    d, H = int(c["hidden_size"]), int(c["num_attention_heads"])
    q, kv = int(c["q_lora_rank"]), int(c["kv_lora_rank"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    v, f = int(c["v_head_dim"]), int(c["moe_intermediate_size"])
    held = int(c["n_routed_experts"])
    outputs = int(c.get("published", {}).get("n_routed_experts", held))
    mla_mm = d * q + q * H * (nope + rope) + d * (kv + rope) \
        + kv * H * (nope + v) + H * v * d
    mla = mla_mm + q + kv
    mlp = 3 * d * int(c["intermediate_size"])
    router, expert = d * outputs, 3 * d * f
    shared = int(c["n_shared_experts"]) * expert
    dense_block = mla + 2 * d + mlp
    expert_block = mla + 2 * d + router + shared + held * expert
    depth, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    vocab = int(c["vocab_size"]) * d
    mtp = int(c.get("num_nextn_predict_layers", 0)) \
        * (2 * d * d + 2 * d + expert_block + d)
    return {"mla_matmul": mla_mm, "mla": mla, "mlp": mlp, "router": router,
            "expert": expert, "shared": shared, "experts_held": held * expert,
            "dense_block": dense_block, "expert_block": expert_block,
            "embed": vocab, "head": vocab, "mtp": mtp,
            "total": dense * dense_block + (depth - dense) * expert_block
            + 2 * vocab + mtp + d}


def attention_flops_per_step(c: dict, batch: int, seq_len: int) -> float:
    """Causal attention of one step, forward + dQ + dK/dV, every call (a
    block each, the module's included): forward q k^T is 2 T T (nope +
    rope) a head and p v 2 T T v_dim, both halved by the mask; the
    backward needs dV and dP at v's size and dQ and dK at q's, twice the
    forward. The scores a flash backward recomputes are not counted."""
    H = int(c["num_attention_heads"])
    qk = int(c["qk_nope_head_dim"]) + int(c["qk_rope_head_dim"])
    calls = int(c["num_hidden_layers"]) \
        + int(c.get("num_nextn_predict_layers", 0))
    fwd = batch * H * 2.0 * seq_len * seq_len * (qk + int(c["v_head_dim"])) \
        / 2.0
    return calls * 3.0 * fwd


def moe_flops_per_step(c: dict, routed: int) -> float:
    """The three matrices of a routed expert, forward and backward, for
    ``routed`` assignments: those that reached an expert held here, summed
    over the expert layers (the module's included)."""
    return 6.0 * 3 * int(c["hidden_size"]) \
        * int(c["moe_intermediate_size"]) * routed


def moe_bytes_per_step(c: dict, bytes_per_el: int = 2) -> float:
    """HBM bytes the held experts' stacks cost a step whatever the load:
    each of a layer's three stacks is read by its forward product and by
    the product that gives the rows' gradient, and its own gradient is
    written once: 9 stack passes a layer."""
    layers = int(c["num_hidden_layers"]) - int(c["first_k_dense_replace"]) \
        + int(c.get("num_nextn_predict_layers", 0))
    stack = int(c["n_routed_experts"]) * int(c["hidden_size"]) \
        * int(c["moe_intermediate_size"]) * bytes_per_el
    return 9.0 * layers * stack


def joyai_flops_per_step(c: dict, batch: int, seq_len: int,
                         routed: int) -> float:
    """Model FLOPs of one step, forward + backward: per token and block
    the latent attention's five projections at 6 a parameter; the dense
    MLP, the router and the shared expert at 6 a parameter in the blocks
    that have them; causal attention (``attention_flops_per_step``); the
    head at 6 a parameter for each of its two uses (T targets a row, and
    the module's T - 1); W_eh at 6 a parameter; the routed experts by
    ``routed``. The embedding look-ups are gathers, not matmuls."""
    p = joyai_params(c)
    d, tokens = int(c["hidden_size"]), batch * seq_len
    depth, dense = int(c["num_hidden_layers"]), int(c["first_k_dense_replace"])
    mtp = int(c.get("num_nextn_predict_layers", 0))
    per_token = 6.0 * (
        (depth + mtp) * p["mla_matmul"] + dense * p["mlp"]
        + (depth - dense + mtp) * (p["router"] + p["shared"])
        + mtp * 2 * d * d + p["head"])
    return tokens * per_token \
        + mtp * 6.0 * p["head"] * batch * (seq_len - 1) \
        + attention_flops_per_step(c, batch, seq_len) \
        + moe_flops_per_step(c, routed)
