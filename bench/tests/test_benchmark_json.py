"""BENCHMARK.json against the contract's limits that a file can break
before a single run."""

import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bm():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_keys_and_size(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 65536
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    cells = 24
    assert (2 + 14 * cells) * (bm["run_seconds"] + 60) + cells * 180 \
        + 1200 <= 43200


def test_every_name_and_unit_is_of_allowed_characters(bm):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bm[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(names) == len(set(names))
    for w in bm["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    for c in bm["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES


def test_metric_entries_have_just_the_contracts_keys(bm):
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}


def test_files_lie_under_paths_and_cells_are_whole(bm):
    cfgs = {c["name"]: c for c in bm["configs"]}
    assert len({c["file"] for c in bm["configs"]}) == len(cfgs)
    cells = {w["name"] for w in bm["workloads"]}
    for c in bm["configs"]:
        assert c["file"].startswith(bm["paths"][0] + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            held = json.load(f)
        assert set(c["reduced"]) == set(held["reduced"])
    used = set()
    for w in bm["workloads"]:
        assert w["config"] in cfgs
        used.add(w["config"])
        for sub, name in (("traffic", w["traffic"]),
                          ("workloads", w["name"])):
            assert os.path.exists(os.path.join(
                ROOT, bm["paths"][0], sub, name + ".json")), (sub, name)
    assert used == set(cfgs)
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert set(m.get("workloads", [])) <= cells
        if m["name"] != "setup_s":
            assert os.path.exists(os.path.join(
                ROOT, bm["paths"][0], "metrics", m["name"] + ".py"))
    four = sum(1 for w in bm["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bm["workloads"]) // 4)


def test_a_roofline_or_mfu_moves_beside_a_whole_step_share(bm):
    per = bm["per_layer"]
    for m in per:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       for o in per), m["name"]


def test_every_cell_reports_setup_another_metric_and_a_layer(bm):
    from benchlib import spec
    for w in bm["workloads"]:
        cell = spec.load_cell(w["name"])
        e2e = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in e2e and len(e2e) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in e2e, (w["name"], m["name"])
