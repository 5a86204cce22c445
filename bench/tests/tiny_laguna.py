"""A tiny copy of the Laguna cell's data files in a temporary root, for CPU
tests and rehearsals: the files of the repo with the sizes cut, every
mechanism kept (a dense full layer, three window layers of 6 heads and a
full layer of 4 over 2 key/value heads of 16, a window of 8 over 64
positions, YaRN with a pair on its ramp, top-3 of 16 experts with 4 held
beside a shared one, an untied head)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "laguna-s-2.1.t16384-b1"

CONFIG = {"hidden_size": 64, "intermediate_size": 96, "head_dim": 16,
          "num_key_value_heads": 2, "num_attention_heads": 4,
          "num_attention_heads_per_layer": [4, 6, 6, 6] * 12,
          "num_experts": 4, "held_experts": [0, 4], "num_experts_per_tok": 3,
          "moe_intermediate_size": 16, "shared_expert_intermediate_size": 16,
          "sliding_window": 8, "vocab_size": 128, "head_chunk": 16,
          "reference_rows": 2, "reference_query_rows": 16}
PUBLISHED = {"num_experts": 16}
ROPE_FULL = {"original_max_position_embeddings": 4096}
TRAFFIC = {"batch": 4, "seq_len": 64, "vocab": 128, "pool_batches": 4,
           "warmup_steps": 1, "trace_seconds": 0.3}
# limits between the tiny size's own readings on the CPU (6 seeds,
# bench/tools/check_faults.py --root; loss / grad / delta): sound <= 1.5e-5
# / 0.0153 / 0.0099 (the balancing bias, which program and reference each
# centre and move by their own loads, doubles the sound grad and delta of
# a size whose 16 scores a token lie within a few hundredths of each
# other); the control (scaled fp8, bfloat16 residual) loss >= 4.3e-5 on
# some step of every seed; YaRN left out grad >= 0.076; the window widened
# grad >= 0.118; the tenth expert left out grad >= 0.21; raw gates, half
# a batch, no gate, no shared expert grad >= 0.53
WORKLOAD = {"loss_steps": [3, 4],
            "limits": {"loss_step1": 2e-5, "loss_step2": 2e-5,
                       "loss_step3": 2e-5, "grad_worst_leaf": 0.025,
                       "delta_worst_leaf": 0.02}}


def make_root(tmp: str, limits=None, config=None) -> str:
    """BENCHMARK.json cut to the Laguna cell, its three data files at tiny
    size, the metric readers as they are; ``config`` overrides keys of
    the configuration. Returns ``tmp``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"] = [w for w in bm["workloads"] if w["name"] == CELL]
    bm["configs"] = [c for c in bm["configs"]
                     if c["name"] == bm["workloads"][0]["config"]]
    bdir = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bdir, "metrics"), dirs_exist_ok=True)
    w = bm["workloads"][0]
    for rel, cut in ((bm["configs"][0]["file"], CONFIG),
                     (f"bench/traffic/{w['traffic']}.json", TRAFFIC),
                     (f"bench/workloads/{CELL}.json", WORKLOAD)):
        with open(os.path.join(ROOT, rel)) as f:
            data = json.load(f)
        data.update(cut)
        if "published" in data:
            data["published"] = dict(data["published"], **PUBLISHED)
            rope = data["rope_parameters"]
            rope["full_attention"] = dict(rope["full_attention"],
                                          **ROPE_FULL)
            data.update(config or {})
        if limits and "limits" in data:
            data["limits"] = dict(data["limits"], **limits)
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp
