"""A configuration, a cell and a per-layer metric added as NEW files are
found by the names in BENCHMARK.json; no existing file is edited."""

import io
import json
import os

import pytest

import tiny
from benchlib import harness, spec


@pytest.fixture()
def root(tmp_path):
    return tiny.make_root(str(tmp_path), cells=["deepfm-criteo.b16k"])


def _edit_benchmark(root, fn):
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bm = json.load(f)
    fn(bm)
    with open(path, "w") as f:
        json.dump(bm, f)


def test_new_config_cell_traffic_and_metric_are_found(root):
    bdir = os.path.join(root, "bench")
    before = {}
    for d, _, files in os.walk(root):
        for name in files:
            p = os.path.join(d, name)
            if name != "BENCHMARK.json":
                before[p] = open(p, "rb").read()
    with open(os.path.join(bdir, "configs", "deepfm-criteo.json")) as f:
        cfg = json.load(f)
    cfg["hidden"] = [16]
    with open(os.path.join(bdir, "configs", "deepfm-small.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bdir, "traffic", "criteo-zipf-b16k.json")) as f:
        mix = json.load(f)
    mix["zipf_alpha"] = 0.0             # uniform ids: data only, no code
    with open(os.path.join(bdir, "traffic", "criteo-uniform.json"),
              "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bdir, "workloads",
                           "deepfm-criteo.b16k.json")) as f:
        wl = json.load(f)
    with open(os.path.join(bdir, "workloads", "deepfm-small.uniform.json"),
              "w") as f:
        json.dump(wl, f)
    with open(os.path.join(bdir, "metrics", "steps_in_window.py"),
              "w") as f:
        f.write("def read(run):\n    return float(run.n_steps)\n")

    def add(bm):
        bm["configs"].append({"name": "deepfm-small", "source": "test",
                              "file": "bench/configs/deepfm-small.json",
                              "reduced": [], "why": "test"})
        bm["workloads"].append({"name": "deepfm-small.uniform",
                                "config": "deepfm-small",
                                "traffic": "criteo-uniform", "chips": 1,
                                "why": "test"})
        bm["per_layer"].append({
            "name": "steps_in_window", "unit": "steps", "better": "higher",
            "source": "program_counter", "layer": "fused PS step",
            "moves": "samples_per_s_chip",
            "workloads": ["deepfm-small.uniform"]})
    _edit_benchmark(root, add)

    cell = spec.load_cell("deepfm-small.uniform", root)
    assert cell.config["hidden"] == [16]
    assert cell.traffic["zipf_alpha"] == 0.0
    assert "steps_in_window" in {m["name"] for m in cell.per_layer}
    assert "sparse_roofline" not in {m["name"] for m in cell.per_layer}
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell("deepfm-small.uniform", 5, 0.3, True,
                          require_tpu=False, root=root, out=out, err=err)
    assert rc == 0, err.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert line["metrics"]["steps_in_window"]["value"] == line["attempted"]
    assert line["metrics"]["steps_in_window"]["unit"] == "steps"
    # a reader with nothing to read is left out, never reported as 0
    assert "device_ms_per_step" not in line["metrics"]
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_a_metric_without_workloads_is_due_wherever_its_e2e_is(root):
    cell = spec.load_cell("deepfm-criteo.b16k", root)
    names = {m["name"] for m in cell.per_layer}
    assert {"step_ms_p50", "device_idle", "peak_hbm"} <= names
    assert "attn_roofline" not in names
    assert "tokens_per_s_chip" not in {m["name"] for m in cell.end_to_end}


def test_a_missing_file_is_named(root):
    os.remove(os.path.join(root, "bench", "traffic",
                           "criteo-zipf-b16k.json"))
    with pytest.raises(spec.SpecError, match="criteo-zipf-b16k"):
        spec.load_cell("deepfm-criteo.b16k", root)
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("nope", root)
