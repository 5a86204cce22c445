"""The whole step's share of the chip's bf16 peak for the LM: model FLOPs
per token (6 per matmul parameter plus causal attention; forward +
backward, recomputation not counted) x tokens/s per chip over the peak."""


def read(run):
    if run.peaks is None or not run.tokens_per_step:
        return None
    c = run.config
    f = run.costs.lm_flops_per_token(
        int(c["n_embd"]), int(c["n_layer"]), int(c["vocab_size"]),
        int(run.traffic["seq_len"]))
    return (100.0 * f * run.per_s_chip(run.tokens_per_step)
            / run.peaks["bf16_flops_per_s"])
