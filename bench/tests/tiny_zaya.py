"""A tiny copy of the ZAYA cell's data files in a temporary root, for CPU
tests and rehearsals (``tiny.py`` knows the ``lm`` and ``deepfm`` systems
only): the files of the repo with the sizes cut, every mechanism kept
(8/2 heads, 2 + 2 taps, half rotary, 2 of 4 experts held)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "zaya1-8b.t8192-b4"

CONFIG = {"hidden_size": 32, "head_dim": 8, "num_attention_heads": 8,
          "num_key_value_heads": 2, "moe_intermediate_size": 16,
          "router_hidden_size": 8, "num_hidden_layers": 2,
          "num_experts": 2, "held_experts": [0, 2], "vocab_size": 128,
          "head_chunk": 16, "reference_rows": 2, "router_bias_rate": 0.1}
TRAFFIC = {"batch": 4, "seq_len": 64, "vocab": 128, "pool_batches": 4,
           "warmup_steps": 1, "trace_seconds": 0.3}
# limits between the tiny size's own readings on the CPU (8 seeds,
# bench/tools/check_faults.py --root; grad / delta): sound <= 0.0112 /
# 0.0189 with at most 1 of 256 tokens routed otherwise than in the
# reference; capacity 1.0 drops >= 0.036 / 0.035 (the bias only centres
# the routers, so the first steps' loads are uneven), the control (scaled
# fp8) >= 0.045 / 0.016, half batch >= 0.59 / 0.066
WORKLOAD = {"loss_steps": [3, 4],
            "limits": {"loss_step1": 2e-4, "loss_step2": 2e-4,
                       "loss_step3": 2e-4, "grad_worst_leaf": 0.02,
                       "delta_worst_leaf": 0.03}}


def make_root(tmp: str, limits=None, config=None) -> str:
    """BENCHMARK.json cut to the ZAYA cell, its three data files at tiny
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
            data["published"] = dict(data["published"], num_experts=4)
            data.update(config or {})
        if limits and "limits" in data:
            data["limits"] = dict(data["limits"], **limits)
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp
