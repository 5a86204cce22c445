"""``correct`` at a size a test run can hold: a sound run passes; the
control (the program's own lower-precision path for DeepFM, the reference
one precision down for the LM, put in the program's place) fails; and a
run whose timed path is broken underneath (the harness's look for a chip
skipped, the rest of a run driven) comes out not correct, once for each
fault a training cell can have, the exchange between chips among them (on
four virtual devices: no cell of BENCHMARK.json has four chips yet).

The DeepFM cell is ``tiny``'s stand-in: it is held out of BENCHMARK.json
because on the chip, at full size, its control reads like a sound run
(PERF.md, section 6); on the CPU at this size it does not."""

import io
import json

import pytest

import tiny
from benchlib import check, harness, spec

CELLS = ["deepfm-criteo.b16k", "gpt2-xl.t1024-b16"]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tiny")), cells=CELLS)


def _run(root, cell, wrap=None, seed=2147483659):
    out, err = io.StringIO(), io.StringIO()
    rc = harness.run_cell(cell, seed, 0.2, False, require_tpu=False,
                          root=root, out=out, err=err, wrap_system=wrap)
    assert rc == 0, err.getvalue()
    return json.loads(out.getvalue().strip().splitlines()[-1]), \
        err.getvalue()


@pytest.mark.parametrize("cell", CELLS)
def test_a_sound_run_is_correct_and_prints_each_number_and_limit(root, cell):
    line, err = _run(root, cell)
    assert line["correct"] is True and line["failed"] == 0
    assert list(line)[-1] == "check"
    assert set(line["check"]) >= {"loss_step1", "loss_step2",
                                  "grad_worst_leaf", "delta_worst_leaf"}
    for row in line["check"].values():
        assert row["value"] <= row["limit"]
    assert err.strip().splitlines()[-1] == "check correct: True"
    assert "check grad_worst_leaf:" in err
    assert set(line["metrics"]) >= {"setup_s", "samples_per_s_chip",
                                    "loss_at_n"}
    assert line["compiles"]["window"]["cache_misses"] == 0


@pytest.mark.parametrize("cell", CELLS)
def test_a_step_that_leaves_its_state_unchanged_is_not_correct(root, cell):
    def wrap(system):
        import jax.numpy as jnp
        system.step = lambda batch: jnp.float32(0.5)
        return system
    line, err = _run(root, cell, wrap)
    assert line["correct"] is False
    assert line["check"]["delta_worst_leaf"]["value"] == pytest.approx(
        1.0, abs=1e-3)
    assert "EXCEEDED" in err and "check correct: False" in err


@pytest.mark.parametrize("cell", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(root, cell):
    def wrap(system):
        put = system.put
        system.put = lambda b: put({k: v[: v.shape[0] // 2]
                                    for k, v in b.items()})
        return system
    line, _ = _run(root, cell, wrap)
    assert line["correct"] is False
    g = line["check"]["grad_worst_leaf"]
    assert g["value"] > 3 * g["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_exchange_between_chips_left_out_is_not_correct(tmp_path, cell):
    """Every chip is fed chip 0's shard: the step's mean over the batch is
    then what chip 0 alone would compute with no exchange. Over four
    devices each sums its own share of the tower's bfloat16 cotangents
    before the exchange, so a sound gradient reads up to 6.5e-3 there
    (five seeds; 2.1e-6 on one device) and the fault 0.65 or more."""
    import numpy as np
    root = tiny.make_root(str(tmp_path), cells=[cell], chips=4,
                          limits={"grad_worst_leaf": 0.05})
    assert _run(root, cell)[0]["correct"] is True

    def wrap(system):
        put = system.put
        system.put = lambda b: put({
            k: np.concatenate([v[: v.shape[0] // 4]] * 4)
            for k, v in b.items()})
        return system
    line, _ = _run(root, cell, wrap)
    assert line["correct"] is False
    g = line["check"]["grad_worst_leaf"]
    assert g["value"] > 3 * g["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_comes_out_not_correct(root, cell):
    c = spec.load_cell(cell, root)
    phases = harness.Phases(harness.process_start_time())
    for seed in (5, 2147483777, 3000000001):
        mod = spec.load_system(c.config["system"])
        system = mod.build(c, seed, phases)
        system.free()
        ref = system.reference()
        control = mod.control_readings(system, phases)
        ok, rows = check.decide(control, ref, c.workload["limits"])
        assert not ok, rows
        assert check.decide(ref, ref, c.workload["limits"])[0]


def test_a_leaf_with_no_gradient_is_left_out_of_the_change_by_rule():
    ref = {"loss": [1.0], "grad": {"a": 1.0, "b": 2.0, "c": 1e-6},
           "delta": {"a": 1.0, "b": 1.0, "c": 1e-7}}
    prog = {"loss": [1.0], "grad": dict(ref["grad"]),
            "delta": {"a": 1.0, "b": 1.0, "c": 0.9}}
    got = check.numbers(prog, ref)
    assert got["delta_worst_leaf"][0] == 0.0
    prog["delta"]["b"] = 0.0
    assert check.numbers(prog, ref)["delta_worst_leaf"] == (1.0, "b")


def test_a_leaf_is_measured_against_the_median_leaf_if_that_is_larger():
    ref = {"tiny": 1e-9, "m1": 1.0, "m2": 1.0}
    prog = {"tiny": 3e-9, "m1": 1.0, "m2": 1.1}
    worst, at = check.worst_leaf(prog, ref)
    assert at == "m2" and worst == pytest.approx(0.1)
    assert check.worst_leaf({"m1": 1.0}, ref)[0] == float("inf")


def test_row_gradients_are_compared_by_the_norm_of_their_difference():
    import numpy as np
    r = {"emb": np.ones((4, 2), np.float32), "wide": np.ones((4, 1))}
    p = {"emb": r["emb"].copy(), "wide": r["wide"].copy()}
    p["emb"][0, 0] = 1.5        # norms differ by 3%, rows by 0.5 / sqrt(8)
    worst, at = check.rows_diff(p, r)
    assert at == "emb" and worst == pytest.approx(0.5 / 8 ** 0.5)
    assert check.rows_diff({"emb": r["emb"]}, r)[0] == float("inf")
    base = {"loss": [1.0], "grad": {"a": 1.0}, "delta": {"a": 1.0}}
    got = check.numbers(dict(base, rows=p), dict(base, rows=r))
    assert got["rows_grad_diff"][1] == "emb"
    assert "rows_grad_diff" not in check.numbers(base, base)


def test_a_missing_number_or_non_finite_loss_fails():
    ref = {"loss": [1.0, 1.0, 1.0], "grad": {"a": 1.0}, "delta": {"a": 1.0}}
    prog = {"loss": [1.0, float("nan"), 1.0], "grad": {"a": 1.0},
            "delta": {"a": 1.0}}
    ok, rows = check.decide(prog, ref, {"loss_step2": 1e-3, "other": 1.0})
    assert not ok
    assert {r["name"]: r["ok"] for r in rows} == {"loss_step2": False,
                                                  "other": False}
