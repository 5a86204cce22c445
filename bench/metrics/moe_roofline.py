"""The grouped expert products' share of their roofline: the FLOPs the
routed tokens need in the three expert matrices, forward and backward
(``costs_zaya.moe_flops_per_step``, by the routing the adapter read after
the window), over the chip's bf16 peak, over the device time of the
operations that carry BOTH the expert stack's shape ``[held, d, f]`` and
the token rows ``[batch * seq_len, ...]`` among their result and operands:
the grouped products and their two transposes, whatever implements them.
Matched by shape as ``opkinds.is_sparse_op`` matches tables (the harness's
trace keeps no scopes yet); the weights' cast and the gradients' ravel
carry the stack's shape but no token rows and are left out. Bound by
FLOPs: a token's 3 x 2048 x 2048 matrices are read once a group."""

from benchlib import costs_zaya, opkinds


def read(run):
    routed = run.info.get("routed_tokens_held")
    if run.peaks is None or routed is None:
        return None
    c, mix = run.config, run.traffic
    held, d = int(c["num_experts"]), int(c["hidden_size"])
    f = int(c["moe_intermediate_size"])
    stacks = {f"[{held},{d},{f}]", f"[{held},{f},{d}]"}
    rows = f"[{int(mix['batch']) // run.chips * int(mix['seq_len'])},"
    took = opkinds.seconds_per_step(
        run, lambda op: rows in op.detail
        and any(s in op.detail for s in stacks))
    if not took:
        return None
    flops = costs_zaya.moe_flops_per_step(c, routed // run.chips)
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
