"""The whole step's share of the chip's bf16 peak for the JoyAI cell: model
FLOPs of a step (``costs_joyai.joyai_flops_per_step``; the routed experts
by the assignments really sent to the experts held, which the adapter
reads once after the window from the program's routing observer; both head
uses; recomputation not counted) x steps/s per chip over the peak. A
program without the observer reports nothing."""

from benchlib import costs_joyai


def read(run):
    routed = run.info.get("routed_tokens_held")
    if run.peaks is None or routed is None:
        return None
    mix = run.traffic
    flops = costs_joyai.joyai_flops_per_step(
        run.config, int(mix["batch"]) // run.chips, int(mix["seq_len"]),
        routed // run.chips)
    return 100.0 * flops * run.n_steps / run.window_s \
        / run.peaks["bf16_flops_per_s"]
