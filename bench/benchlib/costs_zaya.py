"""Operations the ZAYA1-shaped decoder NEEDS, from shapes and from the
routing: what the mathematics asks for, whatever implements it. A matmul
parameter costs 6 FLOPs a token that reaches it (forward 2, backward 4);
recomputed operations (remat, the flash backward's scores) are never
counted. The expert layer is charged the tokens really routed to the
experts held, not an even share."""

from __future__ import annotations


def zaya_params(c: dict) -> dict:
    """Parameter counts by part, as ``models/zaya.py`` builds the file's
    model: a layer's attention projections, convolutions, router, experts
    held, its norms and temperature; the tied embedding; the final norm."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    H, K = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    f, w = int(c["moe_intermediate_size"]), int(c["router_hidden_size"])
    held = int(c["num_experts"])
    outputs = int(c.get("published", {}).get("num_experts", held))
    dq, dk = H * hd, K * hd
    attn = d * dq + d * dk + 2 * d * (dk // 2) + dq * d
    conv = int(c["cca_time0"]) * (dq + dk) \
        + int(c["cca_time1"]) * (H + K) * hd * hd
    router = d * w + 2 * w * w + w * outputs + 2 * w   # + gamma, its norm
    experts = held * 3 * d * f
    layer = attn + conv + router + experts + 2 * d + K
    depth = int(c["num_hidden_layers"])
    embed = int(c["vocab_size"]) * d
    return {"attn": attn, "conv": conv, "router": router,
            "experts": experts, "layer": layer, "embed": embed,
            "total": depth * layer + embed + d}


def moe_flops_per_step(c: dict, routed_tokens: int) -> float:
    """The three expert matrices, forward and backward, for
    ``routed_tokens``: the tokens that reached an expert held here, summed
    over the layers."""
    return 6.0 * 3 * int(c["hidden_size"]) \
        * int(c["moe_intermediate_size"]) * routed_tokens


def zaya_flops_per_step(c: dict, batch: int, seq_len: int,
                        routed_tokens: int) -> float:
    """Model FLOPs of one step, forward + backward: per token and layer
    the attention projections, the convolutions' matmuls inside each head
    and the router's MLP at 6 a parameter (the depthwise taps at 6 a tap
    and channel); causal attention's two T x T products (forward 2 * 2 * T
    * heads * head_dim a token, halved by the mask, backward twice that);
    the tied head at 6 a parameter; the experts by ``routed_tokens``. The
    embedding look-up is a gather, not a matmul."""
    p = zaya_params(c)
    depth, tokens = int(c["num_hidden_layers"]), batch * seq_len
    H, hd = int(c["num_attention_heads"]), int(c["head_dim"])
    w = int(c["router_hidden_size"])
    per_token_layer = 6.0 * (p["attn"] + p["conv"] + p["router"] - 2 * w) \
        + 3.0 * (2.0 * 2.0 * seq_len * H * hd) / 2.0
    return tokens * (depth * per_token_layer + 6.0 * p["embed"]) \
        + moe_flops_per_step(c, routed_tokens)
