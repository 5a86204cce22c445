"""The grouped expert products' share of their roofline in the JoyAI cell.
The time they NEED is the larger of two: the FLOPs of the assignments
really routed to the experts held, forward and backward
(``costs_joyai.moe_flops_per_step``, by the routing the adapter read after
the window), over the chip's bf16 peak; and the bytes of the held experts'
stacks, which every product reads or writes whole whatever the load
(``costs_joyai.moe_bytes_per_step``), over the chip's HBM bandwidth: at 512
tokens an expert the layer stands at the chip's ridge. Over the device
time of the operations that carry BOTH the expert stack's shape ``[held,
d, f]`` / ``[held, f, d]`` and the rows of a window of sorted assignments
(as many as a chip has tokens a step: the traffic's) among their result
and operands: the grouped products and their transposes, whatever
implements them. Matched by shape as ``moe_roofline`` matches them for ZAYA (the
harness's trace keeps no scopes); the weights' cast, the gradients' ravel
and the sums of a window's weight gradients carry the stack's shape but no
window rows and are left out, and so is the loop that holds them all."""

from benchlib import costs_joyai, opkinds, traffic


def read(run):
    routed = run.info.get("routed_tokens_held")
    if run.peaks is None or routed is None:
        return None
    c = run.config
    rows = traffic.tokens_per_step(run.traffic) // run.chips
    held, d = int(c["n_routed_experts"]), int(c["hidden_size"])
    f = int(c["moe_intermediate_size"])
    stacks = {f"[{held},{d},{f}]", f"[{held},{f},{d}]"}
    took = opkinds.seconds_per_step(
        run, lambda op: op.category != "while" and f"[{rows}," in op.detail
        and any(s in op.detail for s in stacks))
    if not took:
        return None
    need = max(costs_joyai.moe_flops_per_step(c, routed // run.chips)
               / run.peaks["bf16_flops_per_s"],
               costs_joyai.moe_bytes_per_step(c)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / took
