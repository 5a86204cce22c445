"""The windowed flash kernels' share of their roofline in the Laguna cell.
The time they NEED is the larger of two: the FLOPs of the window layers'
band, forward + dQ + dK/dV over the keys each query really sees
(``costs_laguna.attention_flops_per_step`` of the window layers), over the
chip's bf16 peak; and the bytes the kernels cannot avoid (q, k, v, the
output and its logsumexp, dO, dq, dk, dv once each:
``costs_laguna.window_bytes_per_step``) over the chip's HBM bandwidth.
Over the device time of the custom calls the program names
``flash_fwd_win`` / ``flash_bwd_win`` (the full layers' ``flash_fwd|bwd``
and the grouped expert products are custom calls too). The scores a flash
backward recomputes are not counted: 6/7 is the most kernels that
recompute their scores once can reach."""

from benchlib import costs_laguna, opkinds


def read(run):
    took = opkinds.seconds_per_step(
        run, lambda op: opkinds.is_kernel(op)
        and op.name.startswith("flash_") and "_win" in op.name)
    if not took or run.peaks is None:
        return None
    c, mix = run.config, run.traffic
    batch, T = int(mix["batch"]) // run.chips, int(mix["seq_len"])
    need = max(costs_laguna.attention_flops_per_step(
        c, batch, T, costs_laguna.WINDOW) / run.peaks["bf16_flops_per_s"],
        costs_laguna.window_bytes_per_step(c, batch, T)
        / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / took
