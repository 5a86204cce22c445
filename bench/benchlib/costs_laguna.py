"""Operations and bytes the Laguna-S-2.1-shaped decoder NEEDS, from shapes
and from the routing: what the mathematics asks for, whatever implements
it. A matmul parameter costs 6 FLOPs a token that reaches it (forward 2,
backward 4); recomputed operations (the blocks' remat, the flash
backward's scores) are never counted. Attention is charged the keys
really visible: ``sum_t (t + 1)`` a row on a full layer, ``sum_t min(t +
1, window)`` on a window layer. The routed experts are charged the
assignments really sent to the experts held, not an even share."""

from __future__ import annotations

WINDOW, FULL = "sliding_attention", "full_attention"


def _layers(c: dict) -> list:
    """(kind, query heads held, dense) of each layer built."""
    n = int(c["num_hidden_layers"])
    return [(k, int(h), m == "dense") for k, h, m in zip(
        c["layer_types"][:n], c["num_attention_heads_per_layer"][:n],
        c["mlp_layer_types"][:n])]


def laguna_params(c: dict) -> dict:
    """Parameter counts by part, as ``models/laguna.py`` builds the file's
    model: each layer's attention (q, k, v, gate, output), its two norms,
    the dense MLP or router + shared expert + experts held; embedding and
    head; the final norm."""
    d, hd = int(c["hidden_size"]), int(c["head_dim"])
    G, f = int(c["num_key_value_heads"]), int(c["moe_intermediate_size"])
    held = int(c["num_experts"])
    outputs = int(c.get("published", {}).get("num_experts", held))
    mlp = 3 * d * int(c["intermediate_size"])
    router, expert = d * outputs, 3 * d * f
    shared = 3 * d * int(c["shared_expert_intermediate_size"])
    layers = []
    for _, H, dense in _layers(c):
        attn = 2 * d * H * hd + 2 * d * G * hd + d * H
        layers.append({"attn": attn, "block": attn + 2 * d + (
            mlp if dense else router + shared + held * expert)})
    vocab = int(c["vocab_size"]) * d
    return {"mlp": mlp, "router": router, "shared": shared,
            "expert": expert, "experts_held": held * expert,
            "layers": layers, "embed": vocab, "head": vocab,
            "total": sum(x["block"] for x in layers) + 2 * vocab + d}


def visible_keys(seq_len: int, window: int | None) -> int:
    """Keys a row of ``seq_len`` queries sees, summed over its queries."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_flops_per_step(c: dict, batch: int, seq_len: int,
                             kind: str | None = None) -> float:
    """Attention of one step, forward + dQ + dK/dV, over the layers of
    ``kind`` (every layer where None): forward q k and p v are 2 hd a
    visible key each, a head; the backward twice the forward."""
    hd, out = int(c["head_dim"]), 0.0
    for k, H, _ in _layers(c):
        if kind is None or k == kind:
            win = int(c["sliding_window"]) if k == WINDOW else None
            out += 3.0 * batch * H * 4.0 * hd * visible_keys(seq_len, win)
    return out


def window_bytes_per_step(c: dict, batch: int, seq_len: int,
                          bytes_per_el: int = 2) -> float:
    """HBM bytes the window layers' kernels cannot avoid a step: q, k, v,
    the output, its logsumexp (float32), dO, dq, dk, dv once each."""
    hd, G, out = int(c["head_dim"]), int(c["num_key_value_heads"]), 0.0
    for k, H, _ in _layers(c):
        if k == WINDOW:
            rows = batch * seq_len
            out += rows * (4 * H * hd * bytes_per_el + H * 4
                           + 4 * G * hd * bytes_per_el)
    return out


def moe_flops_per_step(c: dict, routed: int) -> float:
    """The three matrices of a routed expert, forward and backward, for
    ``routed`` assignments: those that reached an expert held here, summed
    over the expert layers."""
    return 6.0 * 3 * int(c["hidden_size"]) \
        * int(c["moe_intermediate_size"]) * routed


def moe_bytes_per_step(c: dict, bytes_per_el: int = 2) -> float:
    """HBM bytes the held experts' stacks cost a step whatever the load:
    9 stack passes a layer, as ``costs_joyai.moe_bytes_per_step``."""
    layers = sum(not dense for _, _, dense in _layers(c))
    stack = int(c["num_experts"]) * int(c["hidden_size"]) \
        * int(c["moe_intermediate_size"]) * bytes_per_el
    return 9.0 * layers * stack


def laguna_flops_per_step(c: dict, batch: int, seq_len: int,
                          routed: int) -> float:
    """Model FLOPs of one step, forward + backward: per token the
    attention projections, the dense MLP, the routers and the shared
    experts and the head at 6 a parameter; attention by the visible keys
    (``attention_flops_per_step``); the routed experts by ``routed``. The
    embedding look-ups are gathers, not matmuls."""
    p = laguna_params(c)
    dense = sum(d for _, _, d in _layers(c))
    sparse = len(p["layers"]) - dense
    per_token = 6.0 * (sum(x["attn"] for x in p["layers"])
                       + dense * p["mlp"]
                       + sparse * (p["router"] + p["shared"]) + p["head"])
    return batch * seq_len * per_token \
        + attention_flops_per_step(c, batch, seq_len) \
        + moe_flops_per_step(c, routed)
