"""The whole step's share of the chip's bf16 peak for the Olmo-Hybrid
cell: model FLOPs of a step a chip (``costs_olmo.olmo_flops_per_step`` for
the chip's share of the batch: projections, MLPs and head at 6 a parameter
and token, causal attention, the delta rule by the recurrence's count;
recomputation not counted) x steps/s over the peak. The collectives cost
time and no FLOPs: they show here as a lower share."""

from benchlib import costs_olmo


def read(run):
    if run.peaks is None:
        return None
    mix = run.traffic
    flops = costs_olmo.olmo_flops_per_step(
        run.config, int(mix["batch"]) // run.chips,
        int(mix["seq_len"]))["total"]
    return 100.0 * flops * run.n_steps / run.window_s \
        / run.peaks["bf16_flops_per_s"]
