"""The flash-attention kernels' share of their roofline in the
Olmo-Hybrid cell: the FLOPs causal attention needs a step and chip at 30
heads of 128 (forward, dQ, dK/dV, one full-attention layer of the four;
``costs_olmo.attention_flops_per_step``) over the chip's bf16 peak, over
the device time of the Pallas kernels the program names ``flash_fwd`` and
``flash_bwd``, averaged over the chips. The scores a flash backward
recomputes are not counted: 6/7 is the most kernels that recompute them
once can reach."""

from benchlib import costs_olmo, opkinds


def read(run):
    took = opkinds.seconds_per_step(
        run, lambda op: opkinds.is_kernel(op)
        and op.name.startswith("flash_"))
    if not took or run.peaks is None:
        return None
    mix = run.traffic
    flops = costs_olmo.attention_flops_per_step(
        run.config, int(mix["batch"]) // run.chips, int(mix["seq_len"]))
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
