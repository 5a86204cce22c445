"""The flash-attention kernels' share of their roofline: the FLOPs causal
attention needs for the step (forward, dQ, dK/dV; from shapes,
``costs.attention_flops_per_step``) over the chip's bf16 peak, over the
device time of the Pallas kernels (``tpu_custom_call``s). Bound by FLOPs
at T=1024, head size 64."""

from benchlib import opkinds


def read(run):
    took = opkinds.seconds_per_step(run, opkinds.is_kernel)
    if not took or run.peaks is None:
        return None
    c, mix = run.config, run.traffic
    flops = run.costs.attention_flops_per_step(
        int(mix["batch"]) // run.chips, int(mix["seq_len"]),
        int(c["n_head"]), int(c["n_embd"]) // int(c["n_head"]),
        int(c["n_layer"]))
    return 100.0 * flops / run.peaks["bf16_flops_per_s"] / took
