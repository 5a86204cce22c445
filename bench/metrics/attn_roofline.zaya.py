"""The flash-attention kernels' share of their roofline in the ZAYA cell:
the FLOPs causal attention needs for the step (forward, dQ, dK/dV;
``costs.attention_flops_per_step`` for 8 heads of 128) over the chip's
bf16 peak, over the device time of the Pallas kernels the program names
``flash_*`` (``pl.pallas_call(name=...)`` names the instruction; the
grouped expert products are custom calls too, so the opcode alone does not
tell them apart). The scores a flash backward recomputes are not counted:
6/11 is the most kernels that recompute can reach."""

from benchlib import opkinds


def read(run):
    took = opkinds.seconds_per_step(
        run, lambda op: opkinds.is_kernel(op)
        and op.name.startswith("flash_"))
    if not took or run.peaks is None:
        return None
    c, mix = run.config, run.traffic
    flops = run.costs.attention_flops_per_step(
        int(mix["batch"]) // run.chips, int(mix["seq_len"]),
        int(c["num_attention_heads"]), int(c["head_dim"]),
        int(c["num_hidden_layers"]))
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
