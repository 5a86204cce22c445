"""The grouped expert products' share of their roofline in the Laguna
cell, as ``moe_roofline.joyai`` reads JoyAI's: the larger of the FLOPs of
the assignments really routed to the experts held, forward and backward
(``costs_laguna.moe_flops_per_step``), over the chip's bf16 peak, and the
bytes of the held experts' stacks (``costs_laguna.moe_bytes_per_step``)
over its HBM bandwidth; over the device time of the operations that carry
BOTH a stack's shape ``[held, d, f]`` / ``[held, f, d]`` and the rows of a
window of sorted assignments (as many as a chip has tokens a step) among
their result and operands, the loop that holds them left out."""

from benchlib import costs_laguna, opkinds, traffic


def read(run):
    routed = run.info.get("routed_tokens_held")
    if run.peaks is None or routed is None:
        return None
    c = run.config
    rows = traffic.tokens_per_step(run.traffic) // run.chips
    held, d = int(c["num_experts"]), int(c["hidden_size"])
    f = int(c["moe_intermediate_size"])
    stacks = {f"[{held},{d},{f}]", f"[{held},{f},{d}]"}
    took = opkinds.seconds_per_step(
        run, lambda op: op.category != "while" and f"[{rows}," in op.detail
        and any(s in op.detail for s in stacks))
    if not took:
        return None
    need = max(costs_laguna.moe_flops_per_step(c, routed // run.chips)
               / run.peaks["bf16_flops_per_s"],
               costs_laguna.moe_bytes_per_step(c)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / took
