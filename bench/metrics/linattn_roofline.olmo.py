"""The gated delta rule's share of its roofline in the Olmo-Hybrid cell.
The time it NEEDS a step and chip is the larger of two: its FLOPs by the
recurrence's own count (``costs_olmo.delta_rule_flops_per_step``) over the
chip's bf16 peak, and the bytes it cannot avoid (q, k, v, o, g and beta
once each way and a float32 state a chunk:
``costs_olmo.delta_rule_bytes_per_step``) over the chip's HBM bandwidth.
Over the device time of the operations that carry the chunked form's
shapes, averaged over the chips: the rule is no kernel but a scan over
batched products (``ops/delta_rule.py``), and the harness's trace keeps no
scopes, so an operation is the rule's if a shape among its result and
operands has the linear heads and a chunk's tokens beside a head size or a
second chunk (the chunk terms, the triangular solve, both scans' bodies),
or the heads and both head sizes (the state). The loops' own ``while`` ops
hold their bodies; the union of intervals counts a moment once. A program
without the rule has no such shapes and reports nothing."""

import re

from benchlib import costs_olmo, opkinds

try:                # the one chunk size the step runs
    from minips_tpu.ops.delta_rule import CHUNK
except ImportError:     # a program without the rule: nothing to read
    CHUNK = None
_SHAPE = re.compile(r"\[([0-9,]+)\]")


def carries_chunked_shape(detail: str, heads: int, dk: int, dv: int) -> bool:
    for dims in _SHAPE.findall(detail):
        dims = [int(x) for x in dims.split(",")]
        if heads not in dims:
            continue
        if dk in dims and dv in dims:
            return True
        if CHUNK in dims and (dk in dims or dv in dims
                              or dims.count(CHUNK) > 1):
            return True
    return False


def read(run):
    c = run.config
    if run.peaks is None or CHUNK is None \
            or "linear_num_key_heads" not in c:
        return None
    heads = int(c["linear_num_key_heads"])
    dk, dv = int(c["linear_key_head_dim"]), int(c["linear_value_head_dim"])
    took = opkinds.seconds_per_step(
        run, lambda op: carries_chunked_shape(op.detail, heads, dk, dv))
    if not took:
        return None
    mix = run.traffic
    batch, T = int(mix["batch"]) // run.chips, int(mix["seq_len"])
    need = max(costs_olmo.delta_rule_flops_per_step(c, batch, T)
               / run.peaks["bf16_flops_per_s"],
               costs_olmo.delta_rule_bytes_per_step(c, batch, T, CHUNK)
               / run.peaks["hbm_bytes_per_s"])
    return 100.0 * need / took
