"""A tiny copy of the Olmo-Hybrid cell's data files in a temporary root,
for CPU tests and rehearsals (``tiny.py`` knows the ``lm`` and ``deepfm``
systems only): the files of the repo with the sizes cut, every mechanism
kept (three linear-attention layers of two heads of 8 / 16 channels with 4
taps and a doubled beta to one full-attention layer of two heads of 16, an
untied head, 128 tokens a sequence: two chunks of the delta rule)."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "olmo-hybrid-7b.t8192-b4"

CONFIG = {"hidden_size": 32, "num_attention_heads": 2,
          "num_key_value_heads": 2, "intermediate_size": 48,
          "linear_num_key_heads": 2, "linear_num_value_heads": 2,
          "linear_key_head_dim": 8, "linear_value_head_dim": 16,
          "vocab_size": 128, "head_chunk": 16, "reference_rows": 1}
TRAFFIC = {"batch": 4, "seq_len": 128, "vocab": 128, "pool_batches": 4,
           "warmup_steps": 1, "trace_seconds": 0.3}
WORKLOAD = {"loss_steps": [3, 4],
            "limits": {"loss_step1": 1.0, "loss_step2": 1.0,
                       "loss_step3": 1.0, "grad_worst_leaf": 1.0,
                       "delta_worst_leaf": 1.0}}


def make_root(tmp: str, limits=None, config=None, chips: int = 1) -> str:
    """BENCHMARK.json cut to the Olmo-Hybrid cell (asking for ``chips``
    devices), its three data files at tiny size, the metric readers as
    they are; ``config`` overrides keys of the configuration. Returns
    ``tmp``."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    bm["workloads"] = [dict(w, chips=chips) for w in bm["workloads"]
                       if w["name"] == CELL]
    bm["configs"] = [c for c in bm["configs"]
                     if c["name"] == bm["workloads"][0]["config"]]
    bdir = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bdir, "metrics"), dirs_exist_ok=True)
    w = bm["workloads"][0]
    for rel, cut in ((bm["configs"][0]["file"], dict(CONFIG,
                                                     **(config or {}))),
                     (f"bench/traffic/{w['traffic']}.json", TRAFFIC),
                     (f"bench/workloads/{CELL}.json", WORKLOAD)):
        with open(os.path.join(ROOT, rel)) as f:
            data = json.load(f)
        data.update(cut)
        if limits and "limits" in data:
            data["limits"] = dict(data["limits"], **limits)
        with open(os.path.join(tmp, rel), "w") as f:
            json.dump(data, f)
    with open(os.path.join(tmp, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    return tmp
