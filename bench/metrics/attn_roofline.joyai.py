"""The flash-attention kernels' share of their roofline in the JoyAI cell:
the FLOPs causal attention needs for the step at 192 channels for q·k and
128 for p·v (forward, dQ, dK/dV, six calls: five blocks and the
prediction module; ``costs_joyai.attention_flops_per_step``) over the
chip's bf16 peak, over the device time of the Pallas kernels the program
names ``flash_fwd|dq|dkv`` (the grouped expert products are custom calls
too, so the opcode alone does not tell them apart). The scores a flash
backward recomputes are not counted: 6/9 is the most kernels that
recompute can reach."""

from benchlib import costs_joyai, opkinds


def read(run):
    took = opkinds.seconds_per_step(
        run, lambda op: opkinds.is_kernel(op)
        and op.name.startswith("flash_"))
    if not took or run.peaks is None:
        return None
    mix = run.traffic
    flops = costs_joyai.attention_flops_per_step(
        run.config, int(mix["batch"]) // run.chips, int(mix["seq_len"]))
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
