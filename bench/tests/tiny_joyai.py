"""A tiny copy of the JoyAI cell's data files in a temporary root, for CPU
tests and rehearsals (``tiny.py`` knows the ``lm`` and ``deepfm`` systems
only): the files of the repo with the sizes cut, every mechanism kept (two
heads of 16 + 8 against 16 channels through latents of 24 and 16, a dense
layer and two expert layers, top-2 of 8 experts with 4 held beside a
shared one, an untied head, the prediction module)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "joyai-llm-flash.t8192-b2"

CONFIG = {"hidden_size": 32, "num_attention_heads": 2, "q_lora_rank": 24,
          "kv_lora_rank": 16, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
          "qk_head_dim": 24, "v_head_dim": 16, "head_dim": 8,
          "intermediate_size": 48, "moe_intermediate_size": 16,
          "num_hidden_layers": 3, "n_routed_experts": 4,
          "held_experts": [0, 4], "num_experts_per_tok": 2,
          "vocab_size": 128, "head_chunk": 16, "reference_rows": 2}
PUBLISHED = {"n_routed_experts": 8}
TRAFFIC = {"batch": 4, "seq_len": 64, "vocab": 128, "pool_batches": 4,
           "warmup_steps": 1, "trace_seconds": 0.3}
# limits between the tiny size's own readings on the CPU (6 seeds,
# bench/tools/check_faults.py --root; loss / grad / delta): sound <= 7.6e-6
# / 0.0155 / 0.0088, on the three seeds the tests use <= 6.3e-6 / 0.0075 /
# 0.0049 (a token near a tie takes another second expert: the worst seed
# is left to the chip's limits); the control (scaled fp8) delta >= 0.0102;
# the shared rotated key given to each head apart delta >= 0.0198 (its
# gradient norms read like a sound run's); the bias left out of the choice
# grad >= 0.028, delta >= 0.0109; raw gates, top-1 of 2, half a batch grad
# >= 0.08; no shared expert, no prediction loss grad = 1
WORKLOAD = {"loss_steps": [3, 4],
            "limits": {"loss_step1": 2e-5, "loss_step2": 2e-5,
                       "loss_step3": 2e-5, "grad_worst_leaf": 0.02,
                       "delta_worst_leaf": 0.0075}}


def make_root(tmp: str, limits=None, config=None) -> str:
    """BENCHMARK.json cut to the JoyAI cell, its three data files at tiny
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
            data.update(config or {})
        if limits and "limits" in data:
            data["limits"] = dict(data["limits"], **limits)
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp
