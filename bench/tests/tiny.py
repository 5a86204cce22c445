"""A tiny copy of the benchmark's data files in a temporary root, for CPU
tests and rehearsals: the same harness, adapters and readers at sizes a
test run can hold."""

from __future__ import annotations

import json
import os
import shutil

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

# The DeepFM cell is not in BENCHMARK.json (PERF.md, section 7): its adapter,
# reference and generator are tested on this stand-in, whole at tiny size.
DEEPFM_CELL = "deepfm-criteo.b16k"
DEEPFM = {
    "config_entry": {"name": "deepfm-criteo", "source": "arXiv:1703.04247",
                     "file": "bench/configs/deepfm-criteo.json",
                     "reduced": [], "why": "stand-in for tests"},
    "workload_entry": {"name": DEEPFM_CELL, "config": "deepfm-criteo",
                       "traffic": "criteo-zipf-b16k", "chips": 1,
                       "why": "stand-in for tests"},
    "config": {"system": "deepfm", "num_dense": 13, "num_cat": 26,
               "embedding_dim": 10, "hidden": [32, 32, 32],
               "num_slots": 4096, "wide_salt": 1, "emb_salt": 2,
               "sparse_updater": "adagrad", "sparse_lr": 0.05,
               "adagrad_init": 0.1, "wide_init_scale": 0.0,
               "emb_init_scale": 0.01, "dense_updater": "adam",
               "dense_lr": 0.001},
    "traffic": {"kind": "criteo_zipf", "batch": 128, "num_dense": 13,
                "num_cat": 26, "zipf_alpha": 1.05, "pool_batches": 4,
                "warmup_steps": 1, "trace_seconds": 0.2,
                "cardinalities": [4096, 2048, 512, 3, 64, 1024] * 4
                + [4, 36]},
    # limits between the tiny size's own readings on the CPU, 14 seeds
    # (losses / grad / delta / rows): sound <= 1.5e-4 / 2.1e-6 / 1.5e-3 /
    # 4.1e-6; the program's bfloat16 path grad >= 4.8e-5, rows >= 1.8e-3
    # (on the chip at full size it reads like a sound run); the half-batch
    # fault >= 0.38 / 0.31
    "workload": {"loss_steps": [3, 4],
                 "limits": {"loss_step1": 1e-3, "loss_step2": 1e-3,
                            "grad_worst_leaf": 1e-5,
                            "delta_worst_leaf": 0.02,
                            "rows_grad_diff": 1e-4}},
}

TINY = {
    "lm": {
        "config": {"n_embd": 64, "n_head": 4, "n_layer": 2,
                   "vocab_size": 256, "n_positions": 64, "head_chunk": 16},
        "traffic": {"batch": 4, "seq_len": 64, "vocab": 256,
                    "pool_batches": 4, "warmup_steps": 1,
                    "trace_seconds": 0.2},
        # sound <= 1.6e-5 / 1.5e-3 / 2.4e-3; control 1.1e-2 / 3.4e-2
        "workload": {"loss_steps": [3, 4],
                     "limits": {"loss_step1": 6e-5, "loss_step2": 6e-5,
                                "loss_step3": 6e-5,
                                "grad_worst_leaf": 5e-3,
                                "delta_worst_leaf": 1e-2}},
    },
}


def _dump(path: str, data: dict) -> None:
    with open(path, "w") as f:
        json.dump(data, f)


def make_root(tmp: str, cells=None, benchmark=None, chips=1,
              limits=None) -> str:
    """Writes BENCHMARK.json and tiny data files under ``tmp``; metric
    readers are copied as they are. ``benchmark`` stands in for the
    repo's BENCHMARK.json (a cell not yet listed there); the DeepFM
    stand-in is added to it; every cell asks for ``chips`` devices;
    ``limits`` overrides single limits of every cell's output check.
    Returns ``tmp``."""
    bm = benchmark
    if bm is None:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            bm = json.load(f)
    if DEEPFM_CELL not in {w["name"] for w in bm["workloads"]}:
        bm["configs"].append(dict(DEEPFM["config_entry"]))
        bm["workloads"].append(dict(DEEPFM["workload_entry"]))
    bdir = os.path.join(tmp, "bench")
    for sub in ("configs", "traffic", "workloads"):
        os.makedirs(os.path.join(bdir, sub), exist_ok=True)
    shutil.copytree(os.path.join(BENCH, "metrics"),
                    os.path.join(bdir, "metrics"), dirs_exist_ok=True)
    cfgs = {c["name"]: c for c in bm["configs"]}
    for w in bm["workloads"]:
        if cells and w["name"] not in cells:
            continue
        w["chips"] = chips
        src = cfgs[w["config"]]["file"]
        if w["name"] == DEEPFM_CELL:
            parts = {k: dict(DEEPFM[k])
                     for k in ("config", "traffic", "workload")}
        else:
            parts = {}
            for key, path in (
                    ("config", os.path.join(ROOT, src)),
                    ("traffic", os.path.join(BENCH, "traffic",
                                             w["traffic"] + ".json")),
                    ("workload", os.path.join(BENCH, "workloads",
                                              w["name"] + ".json"))):
                with open(path) as f:
                    parts[key] = json.load(f)
                parts[key].update(TINY[parts["config"]["system"]][key])
        if limits:
            parts["workload"]["limits"] = dict(parts["workload"]["limits"],
                                               **limits)
        _dump(os.path.join(tmp, src), parts["config"])
        _dump(os.path.join(bdir, "traffic", w["traffic"] + ".json"),
              parts["traffic"])
        _dump(os.path.join(bdir, "workloads", w["name"] + ".json"),
              parts["workload"])
    _dump(os.path.join(tmp, "BENCHMARK.json"), bm)
    return tmp
